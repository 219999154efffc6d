"""The program's own spans over a run's window, and their place on the
profiler's clock.

`Scheduler.step` records one `serve.step` span per call in the program's
recorder (`repro.engine.spans`), on `time.perf_counter_ns()`, with its
phases as child spans: `serve.form`, `serve.pack`, `engine.apply`,
`serve.unpack`, `serve.wait`, `serve.account`. `window_steps(run)` keeps
the `serve.step` spans that start inside [run.t0, run.t1]. It finds
nothing (None) in a program without the recorder, in a window without
steps, and where the recorder's ring dropped records the window may have
held.

A traced run's device ops sit on the profiler's clock, and `trace.read`
keeps only the harness's `bench.*` host events. `to_trace(run)` maps
perf_counter onto that clock by a least-squares line through the pairs
the run holds on both clocks: the start and end of each `bench.step`, in
`run.steps` (perf_counter s) and in `run.events["host"]` (profiler ns), in
the same order. A line, not one offset, so that a clock that slews over
the window does not shift the map. `idle_by_phase(run, steps)` then splits
the device's idle time inside the window's steps by program phase, with
the same reduction as the breakdown's `idle_gaps`.

The profiler places a TPU's timeline on its clock anew in each session,
0.1 to 1.5 ms early in the sessions measured on a TPU v5e (PERF.md), which
would move idle from one phase to another. So `idle_by_phase` measures
that lag about each step (`device_lags`), moves the step's spans by it,
leaves out the steps whose ops then still start before the host enqueued
them (`placed`), and reads None where that is more than 5% of them: a
whole session placed off, not the few steps after a host stall (0.6% in
one run of three).

Nothing here imports the program at module level: on a program without
the recorder every reader returns None.
"""
from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import trace as tr

STEP = "serve.step"
WAIT = "serve.wait"
HARNESS_STEP = "bench.step"
DROPPED = "spans.dropped"
TOL_NS = 1e3               # the least room `placed` gives the fitted line
ISOLATED_NS = 4e6          # the gap before a step that gives a lag sample
LAG_STEPS = 15             # the samples a step's lag is the median of
MAX_UNPLACED = 0.05        # the share of steps `idle_by_phase` may leave out

Step = Tuple[object, Dict[str, object]]     # (step record, {child: record})


def _snapshot() -> Optional[Dict]:
    try:
        from repro.engine import spans
    except ImportError:         # a program that records no spans
        return None
    return spans.snapshot()


def window_steps(run) -> Optional[List[Step]]:
    """The `serve.step` records that start inside the window, in order,
    each with its child records by name."""
    snap = _snapshot()
    if snap is None or not snap["spans"]:
        return None
    recs = snap["spans"]
    lo, hi = run.t0 * 1e9, run.t1 * 1e9
    # records enter the ring as they end: the ones dropped ended before
    # the oldest one kept
    if snap["counters"].get(DROPPED, 0) and recs[0].end_ns >= lo:
        return None
    children: Dict[int, Dict[str, object]] = {}
    for r in recs:
        if r.parent:
            children.setdefault(r.parent, {})[r.name] = r
    steps = [(r, children.get(r.id, {})) for r in recs
             if r.name == STEP and lo <= r.start_ns <= hi]
    return steps or None


def _median_ms(values_ns: List[float]) -> Optional[float]:
    return statistics.median(values_ns) * 1e-6 if values_ns else None


def dispatch_ms(run) -> Optional[float]:
    """Median over the window's steps of `serve.unpack`'s end less
    `serve.step`'s start: host time before the batch's last program is
    enqueued."""
    steps = window_steps(run) or ()
    return _median_ms([k["serve.unpack"].end_ns - s.start_ns
                       for s, k in steps if "serve.unpack" in k])


def account_ms(run) -> Optional[float]:
    """Median `serve.account` duration over the window's steps: host time
    after the results are ready and before `step` returns."""
    steps = window_steps(run) or ()
    return _median_ms([a.end_ns - a.start_ns for a in
                       (k.get("serve.account") for _, k in steps) if a])


def to_trace(run) -> Optional[Tuple[Callable[[float], float], float]]:
    """perf_counter ns -> profiler ns, fitted on the run's `bench.step`
    spans, and the largest distance (ns) of a fitted pair from the line;
    None without a trace whose steps pair with `run.steps`."""
    if run.events is None or not run.steps:
        return None
    on_trace = sorted((s, e) for n, s, e in run.events["host"]
                      if n == HARNESS_STEP)
    if len(on_trace) != len(run.steps):
        return None
    x = np.asarray(run.steps, np.float64).ravel() * 1e9
    y = np.asarray(on_trace, np.float64).ravel()
    if np.ptp(x) <= 0:
        return None
    # fit about the first pair: the clocks' absolute values lose ns in
    # float64 sums
    x0, y0 = x[0], y[0]
    a, b = np.polyfit(x - x0, y - y0, 1)
    err = float(np.max(np.abs(b + a * (x - x0) - (y - y0))))
    return (lambda ns: y0 + b + a * (ns - x0)), err


def _op_starts(run) -> np.ndarray:
    """The start (profiler ns) of every op of the run's devices, sorted."""
    return np.sort(np.asarray([s for d in run.devices for _, s, _ in
                               run.events["devices"].get(d, ())], np.float64))


def device_lags(steps: List[Step], line: Callable[[float], float],
                starts: np.ndarray, lo: float) -> Optional[np.ndarray]:
    """Per step, how far (ns) the trace places the device's ops after the
    host's spans. A step's first program starts on the idle device as the
    packer's call returns: in sessions that place the device where its ops
    could have run, the first op starts at `serve.pack`'s end (p50 +1.4
    us over 3,772 steps on a TPU v5e; PERF.md). So a step that begins
    `ISOLATED_NS` or more after the previous one ended (or after `lo`, the
    window's start) gives a sample: the first op that starts in the second
    half of that gap, less the step's `serve.pack` end, found for any lag
    within half the gap. A step's lag is the median of the `LAG_STEPS`
    samples about it in time, since the profiler moves its placement now
    and then (by ~190 us about 1 s into a session). None without
    samples."""
    at, lag, prev = [], [], lo
    for s, kids in steps:
        pack = kids.get("serve.pack")
        start = line(s.start_ns)
        if pack is not None and start - prev >= ISOLATED_NS:
            i = np.searchsorted(starts, (start + prev) / 2)
            if i < len(starts):
                at.append(start)
                lag.append(starts[i] - line(pack.end_ns))
        prev = line(s.end_ns)
    if not lag:
        return None
    n = len(lag)
    k = min(LAG_STEPS, n)
    first = np.clip(np.searchsorted(at, [line(s.start_ns) for s, _ in steps])
                    - k // 2, 0, n - k)
    lag = np.asarray(lag)
    return np.asarray([np.median(lag[i:i + k]) for i in first])


def placed(steps: List[Step], line: Callable[[float], float],
           lags: np.ndarray, starts: np.ndarray, lo: float,
           tol: float) -> List[bool]:
    """Per step, whether its spans, moved by its lag, sit where the host
    could have caused its ops: its first op, the first to start after the
    previous step ended (after `lo`, the window's start), starts no
    earlier than its `serve.pack`, which enqueues its first program, less
    `tol` ns. That holds where every step blocks on its batch, as on one
    device. Device work between steps also breaks it (one such stretch,
    80 ms in a 1.6 s host stall). A step that formed no batch
    enqueued nothing: it is placed, and the next step answers for its
    stretch; a step whose batch ran no op is not."""
    out, prev = [], lo + lags[0]
    for (s, kids), lag in zip(steps, lags):
        pack = kids.get("serve.pack")
        if pack is None:
            out.append(True)
            continue
        end = line(s.end_ns) + lag
        i, j = np.searchsorted(starts, [prev, end], side="right")
        out.append(bool(i < j and
                        starts[i] >= line(pack.start_ns) + lag - tol))
        prev = end
    return out


def idle_by_phase(run, steps: List[Step],
                  ) -> Optional[Tuple[Dict[str, float], int]]:
    """Idle device seconds, summed over the run's devices, while the host
    is inside one of `steps` that the trace places (`placed`): by the
    innermost program span open over them (a phase, or `serve.step`
    between phases); and the number of those steps. The host's spans go
    onto the profiler's clock by `to_trace`, each step's moved by its
    `device_lags`. None without a device trace that pairs with the run,
    and where more than `MAX_UNPLACED` of the steps are not placed within
    the fitted line's own error."""
    if run.events is None or run.trace_span is None or not any(
            run.events["devices"].get(d) for d in run.devices):
        return None
    fit = to_trace(run)
    if fit is None:
        return None
    line, err = fit
    lo = run.trace_span[0]
    starts = _op_starts(run)
    lags = device_lags(steps, line, starts, lo)
    if lags is None:
        return None
    ok = placed(steps, line, lags, starts, lo, max(err, TOL_NS))
    if ok.count(False) > MAX_UNPLACED * len(steps):
        return None
    host = [(r.name, line(r.start_ns) + lag, line(r.end_ns) + lag)
            for (s, kids), lag, good in zip(steps, lags, ok) if good
            for r in (s, *kids.values())]
    gaps = tr.idle_gaps({"devices": run.events["devices"], "host": host},
                        min(h[1] for h in host), max(h[2] for h in host),
                        run.devices, n=len(host) + 1)
    return {name: s for name, s in gaps if name != "none"}, ok.count(True)


def idle_ms(run) -> Optional[float]:
    """Device-idle time inside the window's placed steps outside
    `serve.wait`, per step: the host time in `Scheduler.step` that leaves
    the device idle."""
    steps = window_steps(run)
    split = idle_by_phase(run, steps) if steps else None
    if split is None:
        return None
    idle, n = split
    return sum(s for name, s in idle.items() if name != WAIT) / n * 1e3
