"""Rate and percentile arithmetic: a stall inside the window must show."""
import numpy as np
import pytest

from bench import harness, readers, stats


def _offline(batch_seconds, b=8):
    run = harness.Run(cell={}, cfg={}, mix={}, peaks={}, seed=0, chips=1,
                      devices=[0])
    run.t0, run.t1 = 0.0, float(sum(batch_seconds))
    run.batches = [b] * len(batch_seconds)
    return run


def _served(service_s, gap_s=0.01, n=200, stall_at=None, stall_s=0.0):
    """One server, FIFO, one request at a time; a stall of `stall_s` before
    request `stall_at`."""
    run = harness.Run(cell={}, cfg={}, mix={}, peaks={}, seed=0, chips=1,
                      devices=[0])
    due = np.arange(n) * gap_s
    done, disp = np.zeros(n), np.zeros(n)
    free = 0.0
    for i in range(n):
        start = max(free, due[i]) + (stall_s if i == stall_at else 0.0)
        disp[i], done[i] = start, start + service_s
        free = done[i]
    run.due, run.submitted, run.dispatched, run.done = due, due, disp, done
    run.t0, run.t1 = 0.0, float(done.max())
    return run


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1.0, float("inf")], 95) == float("inf")


def test_a_stall_lowers_images_per_s():
    steady = readers.images_per_s(_offline([0.1] * 50))
    stalled = readers.images_per_s(_offline([0.1] * 49 + [1.1]))
    assert steady == pytest.approx(80.0)
    assert stalled < steady * 0.85


def test_a_stall_raises_the_p95_and_the_queue_wait():
    calm = _served(0.005)
    stalled = _served(0.005, stall_at=100, stall_s=0.3)
    assert readers.latency_ms(calm, 95) == pytest.approx(5.0)
    # every request due during the stall waits: far more than 5% of them
    assert readers.latency_ms(stalled, 95) > 100.0
    assert readers.queue_wait_ms(stalled, 95) > 100.0
    assert readers.latency_ms(stalled, 50) == pytest.approx(5.0)


def test_a_request_that_never_completes_is_missing_from_the_tail():
    run = _served(0.005, n=10)
    run.done[3] = np.nan
    assert readers.latency_ms(run, 95) is None      # lands on +inf
    assert readers.latency_ms(run, 50) == pytest.approx(5.0)
