"""Arithmetic shared by the metric readers in bench/metrics/.

Each reader takes a finished `harness.Run` and returns a number, or None
when the run holds nothing for it to read (no trace, no requests). A
share of a roofline or of a peak is never given as 0 for want of data.
"""
from __future__ import annotations

import numpy as np

from bench import counts, stats
from bench import trace as tr


def latencies_ms(run):
    """Due-to-ready latency of every request due in the window; a request
    that never completed is +inf."""
    if run.due is None:
        return None
    lat = (run.done - run.due) * 1e3
    return [float(x) if np.isfinite(x) else float("inf") for x in lat]


def latency_ms(run, q: float):
    lat = latencies_ms(run)
    if not lat:
        return None
    value = stats.percentile(lat, q)
    return value if np.isfinite(value) else None


def queue_wait_ms(run, q: float):
    """Due to the start of the step that dispatched the request."""
    if run.due is None:
        return None
    wait = (run.dispatched - run.due) * 1e3
    return stats.percentile([float(w) for w in wait], q)


def images_per_s(run):
    if not run.batches or run.due is not None:
        return None
    return stats.rate(run.images, run.window_s)


def occupancy_pct(run):
    slots = run.served + run.padded_slots
    return 100.0 * run.served / slots if slots else None


def _traced(run):
    """A trace that holds ops of at least one of the run's devices."""
    return (run.events is not None and run.trace_span is not None
            and any(run.events["devices"].get(d) for d in run.devices))


def idle_pct(run):
    if not _traced(run):
        return None
    lo, hi = run.trace_span
    return 100.0 * tr.idle_share(run.events, lo, hi, run.devices)


def roofline_pct(run):
    """Least time of the real rows' work over device busy time."""
    if not _traced(run) or not run.batches:
        return None
    lo, hi = run.trace_span
    busy = sum(tr.device_busy(run.events, lo, hi, run.devices))
    if busy <= 0:
        return None
    least = sum(counts.net_least_seconds(run.cfg, k, run.peaks)
                for k in run.batches)
    return 100.0 * least / busy


def mfu_pct(run):
    """FLOPs of the images completed over window x chips x peak."""
    if not run.batches or run.window_s <= 0:
        return None
    work = counts.net_flops(run.cfg, run.images)
    return 100.0 * work / (run.window_s * run.chips
                           * run.peaks["flops_bf16"])
