"""Explicit analytic ledgers for the engine (paper Eqs. 15-18, Fig. 5).

The ledger is an explicit object activated by a context manager, not state
kept on a process-global engine object, so several models can be served and
priced side by side, and jit does not confuse it:

    with engine.tracking() as ledger:
        logits = apply_model(params, x)
    print(ledger.report())

Recording happens at *call* time (eager) or *trace* time (under `jax.jit`),
from static shapes only — a plan is pure metadata and never enters the
jaxpr. Consequences, by design:

  * a jit cache hit replays the compiled function without re-recording; run
    the traced function once under `tracking()` (or record eagerly) to
    price a workload — totals for one trace of a function are deterministic
    and identical across re-traces;
  * inside `lax.scan` the body is traced once, so a scanned block records
    once per trace, not once per iteration.

Nested `tracking()` blocks stack: every active ledger records, so an outer
whole-serve ledger and an inner per-request ledger can coexist.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Iterator, List, Optional

from repro.core import analytics, modes
from repro.engine.plan import EnginePlan


@dataclasses.dataclass
class OpRecord:
    """One executed engine op."""

    kind: str                       # "conv2d" | "conv1d_dw" | "matmul" | "dense"
    mode: modes.Mode
    cost_cycles: int
    cost_ma_words: int
    macs: int
    plan: Optional[EnginePlan] = None


@dataclasses.dataclass
class FallbackRecord:
    """One backend degradation: an op whose planned backend raised and
    that re-ran further down the dispatch fallback chain
    (`EngineConfig.fallback="chain"`). Recorded at the same moment an
    `OpRecord` would be — call time eagerly, trace time under jit — so a
    compiled program's degradations show up once per trace."""

    kind: str                       # op kind ("dense", "conv2d", ...)
    src: str                        # the backend that failed
    dst: str                        # the backend that ran instead
    error: str                      # str() of the exception that forced it


class Ledger:
    """An append-only list of `OpRecord`s with the paper's rollups, plus
    the backend degradations (`FallbackRecord`) observed while active."""

    def __init__(self) -> None:
        self.records: List[OpRecord] = []
        self.fallbacks: List[FallbackRecord] = []

    def __iter__(self) -> Iterator[OpRecord]:
        return iter(self.records)

    def __len__(self) -> int:
        return len(self.records)

    def append(self, rec: OpRecord) -> None:
        self.records.append(rec)

    def clear(self) -> None:
        self.records.clear()
        self.fallbacks.clear()

    def record_plan(self, plan: EnginePlan) -> None:
        kind = "matmul" if plan.kind == "dense" else plan.kind
        self.append(OpRecord(kind, plan.mode, plan.cycles, plan.ma_words,
                             plan.macs, plan))

    # -- rollups (paper Table 4 / Fig. 5) ---------------------------------

    @property
    def total_cycles(self) -> int:
        return sum(r.cost_cycles for r in self.records)

    @property
    def total_ma_words(self) -> int:
        return sum(r.cost_ma_words for r in self.records)

    @property
    def total_macs(self) -> int:
        return sum(r.macs for r in self.records)

    @property
    def performance_efficiency(self) -> float:
        """MMIE-projected perf efficiency of everything recorded so far."""
        cyc = self.total_cycles
        return self.total_macs / (modes.MMIE_NUM_PES * cyc) if cyc else 0.0

    def report(self) -> str:
        lines = ["kind,mode(Wf,S),T,cycles,ma_words,macs,uf_max"]
        for r in self.records:
            lines.append(
                f"{r.kind},({r.mode.w_f},{r.mode.s}),{r.mode.t},"
                f"{r.cost_cycles},{r.cost_ma_words},{r.macs},"
                f"{analytics.utilization_factor_max(r.mode.w_f, r.mode.s):.3f}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Active-ledger stack (thread-local, like the EngineConfig stack: a
# tracking() block on one thread never observes — and paused() on one
# thread never suspends — another thread's ledgers)
# ---------------------------------------------------------------------------

class _Active(threading.local):
    def __init__(self) -> None:
        self.stack: List[Ledger] = []


_TLS = _Active()


@contextlib.contextmanager
def tracking(ledger: Optional[Ledger] = None) -> Iterator[Ledger]:
    """Activate a ledger for every engine op issued in the block (on this
    thread)."""
    led = ledger if ledger is not None else Ledger()
    _TLS.stack.append(led)
    try:
        yield led
    finally:
        _TLS.stack.remove(led)


def is_tracking() -> bool:
    return bool(_TLS.stack)


@contextlib.contextmanager
def paused() -> Iterator[None]:
    """Suspend this thread's active ledgers for the block. Used by program
    capture (`engine.trace_program` / `engine.compile`), which shape-traces
    the network without running it — those phantom ops must not be priced
    into a user's `tracking()` ledger."""
    saved = _TLS.stack[:]
    _TLS.stack.clear()
    try:
        yield
    finally:
        _TLS.stack.extend(saved)


def record(plan: EnginePlan) -> None:
    """Record `plan` into every ledger active on this thread (no-op when
    none)."""
    for led in _TLS.stack:
        led.record_plan(plan)


def record_fallback(rec: FallbackRecord) -> None:
    """Record a backend degradation into every active ledger (no-op when
    none) — dispatch's chokepoint calls this when the fallback chain
    reroutes an op."""
    for led in _TLS.stack:
        led.fallbacks.append(rec)
