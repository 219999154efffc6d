"""From a profiler trace to device busy time, idle share and a breakdown.

`read(path)` turns the JAX profiler's .xplane.pb into plain events:

  devices  {device id: [(op name, start ns, end ns), ...]}, the ops of each
           TPU's "XLA Ops" line
  host     [(span name, start ns, end ns), ...], the harness's own
           TraceAnnotation spans, all named "bench.*"

Host and device events share the profiler's clock. The rest is plain
arithmetic on those lists, kept here so that every PR reduces a trace the
same way (bench/tests/test_trace.py checks it on a recorded trace):

  busy_seconds   union of one device's op intervals inside the window
  idle_share     1 - busy / window, the mean over the devices
  top_ops        device seconds per op name, summed over devices
  idle_gaps      idle device seconds by the innermost host span open at the
                 time ("none" where no span was open)
"""
from __future__ import annotations

import functools
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


@functools.lru_cache(maxsize=1 << 16)
def op_key(name: str) -> str:
    """A stable name for an op from its HLO text ("%_run.5 = f32[128,55,55,
    96]{...} custom-call(...)"): the opcode, element type and result shape,
    as in "custom-call_f32_128_55_55_96". Text that does not parse keeps
    its instruction name without the numeric suffix."""
    m = re.match(r"%?([\w.-]+) = ([a-z]+\d*)\[([\d,]*)\]\S*\s+([\w-]+)\(",
                 name)
    if not m:
        head = name.split(" = ")[0].lstrip("%")
        return re.sub(r"\.\d+$", "", head)
    dims = "_".join(d for d in m.group(3).split(",") if d)
    return "_".join(x for x in (m.group(4), m.group(2), dims) if x)


def read(path: str) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                evs = devices.setdefault(int(m.group(1)), [])
                evs.extend((op_key(e.name), float(e.start_ns),
                            float(e.end_ns)) for e in line.events)
            elif not m:
                host.extend((e.name, float(e.start_ns), float(e.end_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def window(events: Dict) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in events["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} {WINDOW_SPAN} spans in the trace")
    return spans[0]


def _clip(intervals: Sequence[Interval], lo: float, hi: float):
    for name, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def _union(intervals: Sequence[Interval], lo: float, hi: float):
    """Merged (start, end) pairs of the intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for _, s, e in sorted(_clip(intervals, lo, hi), key=lambda t: t[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_seconds(intervals: Sequence[Interval], lo: float,
                 hi: float) -> float:
    return sum(e - s for s, e in _union(intervals, lo, hi)) * 1e-9


def device_busy(events: Dict, lo: float, hi: float,
                devices: Sequence[int]) -> List[float]:
    return [busy_seconds(events["devices"].get(d, ()), lo, hi)
            for d in devices]


def idle_share(events: Dict, lo: float, hi: float,
               devices: Sequence[int]) -> float:
    """Mean over `devices` of 1 - busy / window, as a fraction."""
    span = (hi - lo) * 1e-9
    busy = device_busy(events, lo, hi, devices)
    return sum(1.0 - b / span for b in busy) / len(busy)


def top_ops(events: Dict, lo: float, hi: float, devices: Sequence[int],
            n: int = 10) -> List[List]:
    total: Dict[str, float] = {}
    for d in devices:
        for name, s, e in _clip(events["devices"].get(d, ()), lo, hi):
            total[name] = total.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _gaps(intervals, lo, hi):
    t = lo
    for s, e in _union(intervals, lo, hi):
        if s > t:
            yield t, s
        t = max(t, e)
    if hi > t:
        yield t, hi


def _innermost(spans: Sequence[Interval], lo: float, hi: float):
    """[lo, hi] cut into (start, end, name) pieces, each owned by the
    latest-started host span open over it, or "none"."""
    points = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, _, e) in enumerate(spans)])
    open_: List[int] = []
    t, out = lo, []
    for x, is_start, i in points:
        if x > t:
            out.append((t, x, spans[open_[-1]][0] if open_ else "none"))
            t = x
        if is_start:
            open_.append(i)
        else:
            open_.remove(i)
    if hi > t:
        out.append((t, hi, "none"))
    return out


def idle_gaps(events: Dict, lo: float, hi: float, devices: Sequence[int],
              n: int = 10) -> List[List]:
    """Idle device seconds, summed over devices, by the innermost (latest
    started) host span covering each stretch of idle time."""
    spans = [sp for sp in _clip(events["host"], lo, hi)
             if sp[0] != WINDOW_SPAN]
    pieces = _innermost(spans, lo, hi)
    total: Dict[str, float] = {}
    for d in devices:
        j = 0
        for gs, ge in _gaps(events["devices"].get(d, ()), lo, hi):
            while j < len(pieces) and pieces[j][1] <= gs:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < ge:
                ps, pe, name = pieces[k]
                overlap = min(pe, ge) - max(ps, gs)
                if overlap > 0:
                    total[name] = total.get(name, 0.0) + overlap * 1e-9
                k += 1
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
