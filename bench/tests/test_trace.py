"""The trace reduction, on hand-made events and on a recorded chip trace."""
import json

import pytest

from conftest import REPO

from bench import trace as tr

MS = 1e6    # ns


def _events():
    # device 0: ops [0,2] [1,3] (overlap) [5,6]; device 1: [0,10]
    # host: window [0,10], apply [0,4], wait [4,8], apply [8,10]
    return {
        "devices": {
            0: [("conv", 0 * MS, 2 * MS), ("fc", 1 * MS, 3 * MS),
                ("conv", 5 * MS, 6 * MS)],
            1: [("conv", 0 * MS, 10 * MS)],
        },
        "host": [("bench.window", 0, 10 * MS), ("bench.apply", 0, 4 * MS),
                 ("bench.wait", 4 * MS, 8 * MS),
                 ("bench.apply", 8 * MS, 10 * MS)],
    }


def test_busy_is_the_union_of_op_intervals():
    ev = _events()
    assert tr.busy_seconds(ev["devices"][0], 0, 10 * MS) == pytest.approx(
        4e-3)
    # clipped to the window
    assert tr.busy_seconds(ev["devices"][0], 1.5 * MS, 5.5 * MS) == \
        pytest.approx(2e-3)


def test_idle_share_is_the_mean_over_devices():
    ev = _events()
    lo, hi = tr.window(ev)
    assert tr.idle_share(ev, lo, hi, [0]) == pytest.approx(0.6)
    assert tr.idle_share(ev, lo, hi, [0, 1]) == pytest.approx(0.3)


def test_top_ops_sum_device_time_per_name():
    ev = _events()
    top = dict(tr.top_ops(ev, 0, 10 * MS, [0, 1]))
    assert top["conv"] == pytest.approx(13e-3)
    assert top["fc"] == pytest.approx(2e-3)


def test_idle_gaps_go_to_the_innermost_host_span():
    ev = _events()
    gaps = dict(tr.idle_gaps(ev, 0, 10 * MS, [0]))
    # idle on device 0: [3,5] and [6,10]
    assert gaps["bench.apply"] == pytest.approx(1e-3 + 2e-3)
    assert gaps["bench.wait"] == pytest.approx(1e-3 + 2e-3)
    assert sum(gaps.values()) == pytest.approx(6e-3)


def test_op_key_is_stable_across_compiles():
    hlo = ("%_run.5 = f32[128,55,55,96]{3,2,1,0:T(8,128)} custom-call("
           "f32[128,227,4,57,3]{4,3,2,1,0:T(8,128)} %bitcast.1), "
           'custom_call_target="tpu_custom_call"')
    assert tr.op_key(hlo) == "custom-call_f32_128_55_55_96"
    assert tr.op_key("%copy.4 = f32[128,227,57,4,3]{4,2,3,1,0} copy(f32[1])"
                     ) == "copy_f32_128_227_57_4_3"
    tup = "%copy-start = (f32[3,3]{1,0}, u32[]) copy-start(f32[3,3] %a)"
    assert tr.op_key(tup) == "copy-start"


def _recorded():
    path = REPO / "bench" / "tests" / "data" / "trace_alexnet_b128.json"
    rec = json.loads(path.read_text())
    rec["devices"] = {int(d): [tuple(e) for e in evs]
                      for d, evs in rec["devices"].items()}
    rec["host"] = [tuple(e) for e in rec["host"]]
    return rec


def test_a_recorded_chip_trace_reduces_as_it_did_on_the_chip():
    """Three AlexNet batches of 128 traced on a TPU v5e: the events as
    bench.trace.read() gave them, and the reduction made there."""
    rec = _recorded()
    want = rec["expect"]
    lo, hi = tr.window(rec)
    assert tr.device_busy(rec, lo, hi, [0]) == pytest.approx(want["busy_s"])
    assert tr.idle_share(rec, lo, hi, [0]) == pytest.approx(want["idle"])
    assert tr.top_ops(rec, lo, hi, [0]) == [
        [k, pytest.approx(v)] for k, v in want["top_ops"]]
    assert tr.idle_gaps(rec, lo, hi, [0]) == [
        [k, pytest.approx(v)] for k, v in want["idle_gaps"]]


def test_a_recorded_trace_keeps_its_invariants():
    rec = _recorded()
    lo, hi = tr.window(rec)
    span = (hi - lo) * 1e-9
    busy = tr.device_busy(rec, lo, hi, [0])[0]
    gaps = sum(v for _, v in tr.idle_gaps(rec, lo, hi, [0]))
    assert 0 < busy <= span
    assert busy + gaps == pytest.approx(span)
    # conv1's kernel is the largest op, as in every AlexNet trace so far
    assert tr.top_ops(rec, lo, hi, [0])[0][0] == \
        "custom-call_f32_128_55_55_96"
    # ops overlap at most where async copies run beside compute
    assert sum(v for _, v in tr.top_ops(rec, lo, hi, [0], n=1000)) >= busy
