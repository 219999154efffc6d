"""Host spans and counters of the program's hot paths: always on, bounded.

`span(name, **attrs)` times a block on `time.perf_counter_ns()` and keeps
one `Record` for it in a ring of the last `RING` records; a span opened
inside another records that one as its parent. While a profiler session
is on, each span also opens a `jax.profiler.TraceAnnotation` of its name,
so a profiled run shows it on the profiler's clock beside the device ops.
(Outside a session the annotation would record nothing; opening it
anyway cost ~5 us a span in a served loop on a TPU v5e host.)
`count(name, n)` adds to a plain integer counter; `spans.dropped` counts
records pushed out of the full ring. `snapshot()` returns both; nothing
is written anywhere else.

The recorder holds times and counts only. The analytic MAC and cycle
record of what ran is `engine.Ledger`'s.

Records enter the ring as their spans end, so a parent comes after its
children.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

RING = 2 ** 17
DROPPED = "spans.dropped"
_now_ns = time.perf_counter_ns


class Record(NamedTuple):
    id: int
    parent: int                     # 0 for a span opened outside any other
    name: str
    start_ns: int                   # time.perf_counter_ns()
    end_ns: int
    attrs: Optional[Dict[str, Any]]


class _Open(threading.local):
    def __init__(self) -> None:
        self.ids: List[int] = []    # ids of this thread's open spans


# one recorder per process, always on and bounded (module docstring); the
# ring holds plain tuples, made `Record`s by `snapshot`
_ring: Deque[Tuple[Any, ...]] = collections.deque(maxlen=RING)
_counters: Dict[str, int] = {}  # analyze: allow[mutable-global] the process's counters
_ids = itertools.count(1)
_open = _Open()


class span:
    """Context manager timing one block. `attrs` may be added to while
    the span is open; a span that ends with none records None."""

    __slots__ = ("name", "attrs", "id", "parent", "start_ns", "end_ns",
                 "_trace")

    def __init__(self, name: str, **attrs: Any) -> None:
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        ids = _open.ids
        self.parent = ids[-1] if ids else 0
        self.id = i = next(_ids)
        ids.append(i)
        if TraceAnnotation.is_enabled():
            self._trace = t = TraceAnnotation(self.name)
            t.__enter__()
        else:
            self._trace = None
        self.start_ns = _now_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end_ns = end = _now_ns()
        if self._trace is not None:
            self._trace.__exit__(*exc)
        _open.ids.pop()
        if len(_ring) == _ring.maxlen:
            count(DROPPED)
        _ring.append((self.id, self.parent, self.name, self.start_ns, end,
                      self.attrs or None))


def count(name: str, n: int = 1) -> None:
    _counters[name] = _counters.get(name, 0) + n


def snapshot() -> Dict[str, Any]:
    """{"spans": the ring's records, oldest first, "counters": {...}}."""
    return {"spans": [Record(*r) for r in _ring],
            "counters": dict(_counters)}
