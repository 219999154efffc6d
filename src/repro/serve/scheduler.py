"""Plan-driven batched serving scheduler over compiled engine programs.

The MMIE's headline claim is one engine time-shared across heterogeneous
work — conv nets and FC stacks on the same 192 PEs. This module is that
claim at serving granularity: heterogeneous requests (CNN forwards built by
`models.cnn.program`, transformer prefill / decode steps built by
`serve.engine.prefill_program` / `decode_program`, or anything from
`engine.trace_program`) enter one shared queue and are packed into batches
that dispatch onto per-program `CompiledNet`s.

Everything cost-aware reads the *analytic plan*, never a profile (one
caveat: a traced program whose layers run under `jax.lax.scan` records the
scanned block once per trace — the documented ledger semantics — so its
plan under-counts by the trip count; ordering/admission remain consistent
per program, but scanned-vs-layer-table costs are not 1:1 comparable):

  * admission   — `max_queue_cost_s` bounds the queue by the sum of the
    MMIE-projected `NetworkPlan.total_latency_s` of pending requests;
  * ordering    — the "spf" policy serves the program with the shortest
    per-request plan latency first ("fifo" keeps arrival order);
  * accounting  — each ticket gets an `engine.Ledger` of its own unit-plan
    ops, so per-request MACs / cycles / efficiency come straight off the
    plan that scheduled it.

Batching is *shape-bucketed*: requests are only packed with requests of the
same registered program (identical avals by construction) and batches are
padded up to a fixed bucket ladder (1, 2, 4, ... max_batch by default), so
the jit cache holds one entry per (program, bucket) and never grows with
traffic. Buckets execute `engine.compile(program.with_batch(bucket), cfg)`
— the batch rewrite re-plans, it never re-traces the model.

Parity contract: with the default config (`row_align=8`) a request's result
is bitwise identical whether it was served alone or packed into any bucket
— dense rows always flow through the same fixed-granularity GEMM tile (see
`EngineConfig.row_align`), and conv/pool/softmax work is per-example. The
parity test in tests/test_scheduler.py pins this against batch-1
`CompiledNet.apply`. Scope: the contract holds for *per-example* programs,
i.e. every op's result for one request depends only on that request's rows
— true of the CNN forwards, dense prefill/decode and attention paths here.
Programs with cross-request coupling (e.g. MoE fixed-capacity expert
dispatch, where one request's token drops depend on its batchmates' router
scores) batch fine but are outside the bitwise guarantee; batching them is
the caller's accuracy call.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import engine as E
from repro.engine import ledger as _ledger
from repro.engine import spans
from repro.serve import faults as _faults
from repro.serve.faults import (  # noqa: F401 (re-exported surface)
    FatalError, FaultInjector, TransientError, backoff_s)


class AdmissionError(RuntimeError):
    """Request rejected: admitting it would exceed `max_queue_cost_s`."""


_POLICIES = ("fifo", "spf")


@dataclasses.dataclass(eq=False)      # identity semantics: args hold arrays
class Ticket:
    """One admitted request and, after its batch ran, its result.

    `unit_latency_s` is the MMIE-projected latency of this request's
    batch-1 plan — the number admission and the "spf" policy order by.
    `ledger` holds the request's unit-plan ops once served.
    """

    rid: int
    model: str
    args: Tuple[Any, ...]           # per-request (batched-position) args
    submit_s: float
    unit_latency_s: float
    deadline_s: Optional[float] = None  # absolute perf_counter deadline
    cancelled: bool = False
    expired: bool = False
    ledger: E.Ledger = dataclasses.field(default_factory=E.Ledger)
    result: Any = None
    done: bool = False
    batch_index: int = -1           # row this request occupied in its batch
    batch_fill: int = 0             # real requests in the executed batch
    batch_bucket: int = 0           # padded bucket size the batch ran at
    batch_replica: int = 0          # mesh data group the batch dispatched to
    batch_seq: int = -1             # the batch's `serve.step` batch_seq
    # perf_counter time the batch's results were ready (the end of its
    # `serve.wait`); with replica spreading, the time it was dispatched
    done_s: float = 0.0

    @property
    def latency_s(self) -> float:
        """Wall-clock submit-to-completion latency (queueing + execution);
        NaN while the request is still pending."""
        if not self.done:
            return float("nan")
        return self.done_s - self.submit_s


@dataclasses.dataclass
class _Entry:
    """One registered program: its unit plan plus compiled-bucket cache."""

    name: str
    program: E.Program              # normalized to batch 1
    shared: Dict[int, Any]          # arg position -> bound value
    batch_positions: Tuple[int, ...]
    request_avals: Tuple[Any, ...]  # want-trees for submit() validation
    out_axes: Any                   # per-leaf output batch axis (or -1)
    unit_plan: E.NetworkPlan
    compiled: Dict[Tuple[int, int], E.CompiledNet] = dataclasses.field(
        default_factory=dict)          # (bucket, replica) -> CompiledNet
    pack_fn: Any = None             # one jitted packer (jit re-specializes
                                    # per bucket via the input structure)
    unpack: Dict[int, Any] = dataclasses.field(default_factory=dict)
    served: int = 0
    batches: int = 0
    padded_slots: int = 0


def _aval_of(x) -> Tuple[Tuple[int, ...], Any]:
    dtype = x.dtype if hasattr(x, "dtype") else jnp.result_type(x)
    return (tuple(getattr(x, "shape", ())), jnp.dtype(dtype))


class Scheduler:
    """Shared-queue batched scheduler over registered engine programs.

    config           — `EngineConfig` every bucket compiles under; defaults
                       to `EngineConfig(row_align=8, fallback="chain")` so
                       batched results are bitwise identical to batch-1
                       results and a kernel-level failure degrades
                       pallas -> xla -> ref instead of killing the batch
                       (safe: the backends are pinned bitwise-equal, see
                       engine/config.py). The config's
                       `tuning` mode flows into every (program, bucket)
                       `CompiledNet`: under `"cached"`/`"autotune"` each
                       bucket executes on the tuned kernel tiles — and
                       because tile keys are batch-invariant (engine/tune.py)
                       every bucket of a program shares one tile config, so
                       the bitwise parity contract above survives tuning and
                       fused epilogues (pinned in tests/test_scheduler.py).
    policy           — "fifo" (arrival order) or "spf" (shortest-plan-first:
                       serve the program whose per-request analytic latency
                       is smallest; FIFO within a program).
    max_batch        — largest batch one dispatch may carry.
    buckets          — batch-size ladder; batches are padded up to the next
                       bucket so the jit cache stays at one entry per
                       (program, bucket). Default: powers of two.
    max_queue_cost_s — admission budget: `submit` raises `AdmissionError`
                       once the queue's summed plan latency would pass it
                       (None = admit everything).
    mesh             — None serves on the default device. A (data, model)
                       mesh (with `config.parallel` set to match) spreads
                       batches round-robin across the mesh's data groups:
                       each (program, bucket) compiles one `CompiledNet`
                       per (1, model) submesh (`engine.parallel.
                       data_groups`), consecutive batches land on
                       different replicas, and dispatches stop blocking
                       per batch (`drain` syncs at the end) so replicas
                       overlap. The bitwise parity contract is unchanged
                       — replica placement never changes a result, and
                       model-axis sharding is exact under the default
                       `exact_only` policy (tests/test_parallel.py).
    faults           — an optional `serve.faults.FaultInjector` installed
                       for the dynamic extent of every dispatch (so the
                       kernel/pool hook sites see it) and consulted for
                       latency spikes at each step. None (default) leaves
                       every hook a no-op.
    """

    def __init__(self, config: Optional[E.EngineConfig] = None,
                 policy: str = "fifo", max_batch: int = 8,
                 buckets: Optional[Sequence[int]] = None,
                 max_queue_cost_s: Optional[float] = None,
                 mesh: Optional[Any] = None,
                 faults: Optional[_faults.FaultInjector] = None):
        if policy not in _POLICIES:
            raise ValueError(f"unknown policy {policy!r}; expected one of "
                             f"{_POLICIES}")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.config = config if config is not None \
            else E.EngineConfig(row_align=8, fallback="chain")
        self.mesh = mesh
        if mesh is not None:
            from repro.engine import parallel as parlib
            if self.config.parallel is None:
                raise ValueError(
                    "Scheduler(mesh=...) needs config.parallel (an "
                    "engine.ParallelConfig) to say how ops split over the "
                    "mesh's model axis")
            parlib.check_mesh(mesh, self.config.parallel)
            self._groups: Tuple[Any, ...] = parlib.data_groups(mesh)
        else:
            self._groups = (None,)
        self._rr = 0                    # round-robin replica cursor
        self.policy = policy
        self.max_batch = max_batch
        if buckets is None:
            buckets = []
            b = 1
            while b < max_batch:
                buckets.append(b)
                b *= 2
            buckets.append(max_batch)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if self.buckets[-1] != max_batch:
            raise ValueError(f"buckets {self.buckets} must end at "
                             f"max_batch={max_batch}")
        self.max_queue_cost_s = max_queue_cost_s
        self.faults = faults
        self.ledger = E.Ledger()        # unit plans of everything served
        # trace-time records of the *executed* dispatches: backend
        # degradations land here (ledger.fallbacks), once per traced bucket
        self.fault_ledger = E.Ledger()
        self._spikes = 0                # injected latency spikes absorbed
        self._entries: Dict[str, _Entry] = {}
        self._queue: List[Ticket] = []
        self._next_rid = 0
        self._batch_seq = 0             # batches formed so far
        self._wall_s = 0.0              # summed dispatch times (stats())

    def _inj_ctx(self):
        """Ambient-injector context for a dispatch: installs this
        scheduler's injector so the dispatch/kv_pool hook sites observe it
        (no-op — and no overhead beyond a null contextmanager — when the
        scheduler runs clean)."""
        if self.faults is None:
            return contextlib.nullcontext()
        return _faults.injecting(self.faults)

    # -- registration -------------------------------------------------------

    def register(self, name: str, program: E.Program,
                 shared_args: Sequence[Any] = ()) -> "_Entry":
        """Register `program` under `name`.

        The program must be executable (carry `fn`) and re-batchable (carry
        batch metadata); it is normalized to batch 1. Argument positions
        with no batch axis (weights, the decode position scalar, ...) are
        *shared*: bound once here via `shared_args` (in positional order)
        and reused for every request. `submit` then takes only the
        per-request batched arguments.

        The bitwise-parity guarantee (module docstring) applies to
        per-example programs; registering a program with cross-request ops
        (MoE capacity dispatch) is allowed but its batched results may
        legitimately differ from solo execution.
        """
        if name in self._entries:
            raise ValueError(f"model {name!r} already registered")
        if program.fn is None:
            raise ValueError(
                f"program {program.name!r} carries no executable fn — the "
                "scheduler can only serve programs built with trace_program "
                "or a model-side builder like cnn.program")
        prog1 = program.with_batch(1)   # also validates batch metadata
        batched, unbatched = [], []
        for i, axes in enumerate(prog1.batch_axes):
            leaves = jax.tree_util.tree_leaves(axes)
            if any(a >= 0 for a in leaves):
                if any(a < 0 for a in leaves):
                    # packing would silently reuse request 0's value for the
                    # unbatched leaves of every request in the batch
                    raise ValueError(
                        f"arg position {i} of program {prog1.name!r} mixes "
                        "batched and unbatched leaves in one pytree; bind "
                        "the unbatched data as its own (shared) argument "
                        "position instead")
                batched.append(i)
            else:
                unbatched.append(i)
        if len(shared_args) != len(unbatched):
            raise ValueError(
                f"program {prog1.name!r} has {len(unbatched)} unbatched arg "
                f"position(s) {tuple(unbatched)}; pass exactly that many "
                f"shared_args (got {len(shared_args)})")
        shared = dict(zip(unbatched, shared_args))
        # Output batch axes, derived the same way as the input ones: diff
        # the output avals at batch 1 vs batch 2 (pure shape evaluation —
        # ledgers paused so the dry traces don't record phantom ops).
        with _ledger.paused():
            out1 = jax.eval_shape(prog1.fn, *prog1.in_avals)
            out2 = jax.eval_shape(prog1.fn, *prog1.with_batch(2).in_avals)
        out_axes = E.infer_batch_axes((out1,), (out2,))[0]
        entry = _Entry(
            name=name, program=prog1, shared=shared,
            batch_positions=tuple(batched),
            request_avals=tuple(
                jax.tree_util.tree_map(_aval_of, prog1.in_avals[pos])
                for pos in batched),
            out_axes=out_axes,
            unit_plan=E.plan_network(prog1, self.config))
        self._entries[name] = entry
        return entry

    def compiled(self, name: str, bucket: int,
                 replica: int = 0) -> E.CompiledNet:
        """The (program, bucket, replica) `CompiledNet` — built once, then
        cached. `replica` indexes the mesh's data groups (always 0 when the
        scheduler runs without a mesh)."""
        entry = self._entries[name]
        key = (bucket, replica)
        if key not in entry.compiled:
            entry.compiled[key] = E.compile(
                entry.program.with_batch(bucket), self.config,
                mesh=self._groups[replica])
        return entry.compiled[key]

    def _pack_fn(self, entry: _Entry):
        """Jitted request packer: the batch's per-request arg tuples in,
        the batched values of the program's batched positions out — one
        dispatch per batch instead of one per pytree leaf. Bucket-agnostic:
        jax.jit re-specializes on the input tuple length."""
        if entry.pack_fn is None:
            axes_by_pos = tuple(entry.program.batch_axes[pos]
                                for pos in entry.batch_positions)

            @jax.jit
            def pack(per):
                out = []
                for j, axes in enumerate(axes_by_pos):
                    leaves = [p[j] for p in per]
                    out.append(jax.tree_util.tree_map(
                        lambda ax, *ls: ls[0] if ax < 0
                        else jnp.concatenate(ls, axis=ax), axes, *leaves))
                return tuple(out)

            entry.pack_fn = pack
        return entry.pack_fn

    def _unpack_fn(self, entry: _Entry, bucket: int):
        """Jitted result splitter: batched output in, `bucket` per-request
        keepdim row slices out (again one dispatch per batch)."""
        if bucket in entry.unpack:
            return entry.unpack[bucket]
        out_axes = entry.out_axes

        @jax.jit
        def unpack(out):
            return tuple(
                jax.tree_util.tree_map(
                    lambda leaf, ax: leaf if ax < 0
                    else jax.lax.index_in_dim(leaf, i, axis=ax,
                                              keepdims=True),
                    out, out_axes)
                for i in range(bucket))

        entry.unpack[bucket] = unpack
        return unpack

    def _dispatch(self, entry: _Entry, bucket: int,
                  per: Tuple[Tuple[Any, ...], ...],
                  replica: Optional[int] = None,
                  ) -> Tuple[Tuple[Any, ...], int, int]:
        """The jitted batch path (pack -> shared-arg splice -> apply ->
        unpack), shared by `step` and `warmup` so the pre-paid traces are
        exactly the serving traces. Returns the per-request results and
        the perf_counter_ns times the dispatch began (`serve.pack`'s
        start) and the results were ready (`serve.wait`'s end). With
        multiple mesh data groups the batch lands on the round-robin
        replica and the call does NOT block — consecutive batches overlap
        across replicas; `drain` syncs — so the second time is the
        dispatch's end (`serve.unpack`'s), not readiness."""
        if replica is None:
            replica = self._rr % len(self._groups)
            self._rr += 1
        with spans.span("serve.pack") as pack:
            packed = iter(self._pack_fn(entry)(per))
            args = [entry.shared[pos] if pos in entry.shared
                    else next(packed)
                    for pos in range(len(entry.program.in_avals))]
        with self._inj_ctx(), _ledger.tracking(self.fault_ledger):
            net = self.compiled(entry.name, bucket, replica)
            with spans.span("engine.apply"):
                out = net.apply(*args)
        with spans.span("serve.unpack") as unpack:
            results = self._unpack_fn(entry, bucket)(out)
        if len(self._groups) > 1:
            return results, pack.start_ns, unpack.end_ns
        with spans.span("serve.wait") as wait:
            jax.block_until_ready(results)
        return results, pack.start_ns, wait.end_ns

    def warmup(self, name: Optional[str] = None) -> None:
        """Pre-pay every bucket's jit cost before opening traffic: runs one
        zero-filled batch through the full `_dispatch` path for each
        (program, bucket, replica), so no real request stalls on XLA
        compilation."""
        for n in ([name] if name else list(self._entries)):
            entry = self._entries[n]
            zeros = tuple(
                jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype),
                    entry.program.in_avals[pos])
                for pos in entry.batch_positions)
            for bucket in self.buckets:
                for replica in range(len(self._groups)):
                    jax.block_until_ready(self._dispatch(
                        entry, bucket, (zeros,) * bucket, replica=replica)[0])

    # -- admission ----------------------------------------------------------

    def queue_cost_s(self) -> float:
        """Summed MMIE-projected latency of every pending request."""
        return sum(t.unit_latency_s for t in self._queue)

    def pending(self) -> int:
        return len(self._queue)

    def submit(self, name: str, *args: Any,
               timeout_s: Optional[float] = None) -> Ticket:
        """Admit one request for program `name`.

        `args` are the per-request values of the program's batched argument
        positions, in order, each shaped exactly like the program's batch-1
        avals (leading batch axis of size 1 on the recorded batch axes).
        `timeout_s` sets a wall-clock deadline relative to now; a ticket
        still queued when its deadline passes is dropped (marked
        `expired`) instead of served.
        Raises `AdmissionError` when the queue's plan-cost budget is full,
        `KeyError` for unknown programs, `ValueError` for shape mismatches.
        """
        try:
            entry = self._entries[name]
        except KeyError:
            raise KeyError(f"unknown model {name!r}; registered: "
                           f"{sorted(self._entries)}") from None
        if len(args) != len(entry.batch_positions):
            raise ValueError(
                f"{name!r} takes {len(entry.batch_positions)} per-request "
                f"arg(s) (positions {entry.batch_positions} of the program "
                f"signature); got {len(args)}")
        for val, pos, want in zip(args, entry.batch_positions,
                                  entry.request_avals):
            got = jax.tree_util.tree_map(_aval_of, val)
            if want != got:
                raise ValueError(
                    f"request arg for position {pos} of {name!r} does not "
                    f"match the program's batch-1 avals:\n  want {want}\n"
                    f"  got  {got}")
        unit = entry.unit_plan.total_latency_s
        if self.max_queue_cost_s is not None \
                and self.queue_cost_s() + unit > self.max_queue_cost_s:
            served = sum(e.served for e in self._entries.values())
            raise AdmissionError(
                f"queue plan-cost {self.queue_cost_s():.6f}s + request "
                f"{unit:.6f}s exceeds max_queue_cost_s="
                f"{self.max_queue_cost_s:.6f}s ({len(self._queue)} pending "
                f"across {len({t.model for t in self._queue})} program(s), "
                f"{served} served in "
                f"{sum(e.batches for e in self._entries.values())} batches, "
                f"budget {self.queue_cost_s() / self.max_queue_cost_s:.0%} "
                "used)")
        now = time.perf_counter()
        ticket = Ticket(rid=self._next_rid, model=name, args=tuple(args),
                        submit_s=now, unit_latency_s=unit,
                        deadline_s=None if timeout_s is None
                        else now + timeout_s)
        self._next_rid += 1
        self._queue.append(ticket)
        return ticket

    def cancel(self, ticket: Ticket) -> bool:
        """Drop a still-queued ticket; returns False once it already ran
        (results are not retracted) or was previously dropped."""
        if ticket.done or ticket.cancelled or ticket.expired:
            return False
        ticket.cancelled = True
        ticket.args = ()
        self._queue = [t for t in self._queue if t is not ticket]
        return True

    def _expire(self) -> None:
        now = time.perf_counter()
        keep = []
        for t in self._queue:
            if t.deadline_s is not None and now > t.deadline_s:
                t.expired = True
                t.args = ()
            else:
                keep.append(t)
        self._queue = keep

    # -- dispatch -----------------------------------------------------------

    def _pick_model(self) -> str:
        if self.policy == "spf":
            return min(self._queue,
                       key=lambda t: (t.unit_latency_s, t.rid)).model
        return self._queue[0].model

    def _bucket_for(self, k: int) -> int:
        for b in self.buckets:
            if b >= k:
                return b
        return self.buckets[-1]

    def step(self) -> List[Ticket]:
        """Form and execute one batch; returns the tickets it served.

        The call is one `serve.step` span (engine/spans.py) with the
        attributes `queue_depth` (pending at entry) and, once a batch is
        formed, `model`, `bucket`, `rows` and `batch_seq`, which every
        ticket of the batch keeps. Its children tile it in order:
        `serve.form` (expire, pick, select, pad), `serve.pack`,
        `engine.apply`, `serve.unpack`, `serve.wait` (blocking on the
        results) and `serve.account` (per-ticket fields and ledgers)."""
        with spans.span("serve.step", queue_depth=len(self._queue)) as sp:
            return self._step(sp.attrs)

    def _step(self, attrs: Dict[str, Any]) -> List[Ticket]:
        with spans.span("serve.form"):
            self._expire()
            if not self._queue:
                return []
            if self.faults is not None:
                spike = self.faults.latency("step")
                if spike:
                    self._spikes += 1
                    time.sleep(spike)
            name = self._pick_model()
            entry = self._entries[name]
            batch = [t for t in self._queue
                     if t.model == name][:self.max_batch]
            self._queue = [t for t in self._queue if t not in batch]
            k = len(batch)
            bucket = self._bucket_for(k)
            # pad at the ticket level: repeat the first request's arg
            # pytrees (array references, no copies) so the jitted packer
            # always sees exactly `bucket` request tuples
            per = (tuple(t.args for t in batch)
                   + (batch[0].args,) * (bucket - k))
            replica = self._rr % len(self._groups)
            self._rr += 1
        self._batch_seq += 1
        seq = self._batch_seq
        attrs.update(model=name, bucket=bucket, rows=k, batch_seq=seq)
        results, t0, t1 = self._dispatch(entry, bucket, per,
                                         replica=replica)
        self._wall_s += (t1 - t0) * 1e-9
        done_s = t1 * 1e-9

        with spans.span("serve.account"):
            entry.batches += 1
            entry.served += k
            entry.padded_slots += bucket - k
            for i, ticket in enumerate(batch):
                ticket.result = results[i]
                ticket.args = ()    # served: release the request inputs
                ticket.done = True
                ticket.batch_index = i
                ticket.batch_fill = k
                ticket.batch_bucket = bucket
                ticket.batch_replica = replica
                ticket.batch_seq = seq
                ticket.done_s = done_s
                for plan in entry.unit_plan.plans:
                    ticket.ledger.record_plan(plan)
                    self.ledger.record_plan(plan)
        return batch

    def drain(self) -> List[Ticket]:
        """Serve until the queue is empty; tickets in completion order.
        With replica spreading active, dispatches were issued without
        blocking — the final sync here waits for every in-flight batch."""
        done: List[Ticket] = []
        while self._queue:
            done.extend(self.step())
        if len(self._groups) > 1 and done:
            jax.block_until_ready([t.result for t in done])
        return done

    # -- stats --------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Serving counters. `dispatch_wall_s` sums each batch's dispatch,
        from its `serve.pack` start to its results' readiness (the end of
        `serve.wait`; over replicas, of `serve.unpack`), on the spans'
        clock; `throughput_rps` is `served` over it. Forming the batch,
        an injected latency spike and the accounting are outside it."""
        per_model = {
            n: {
                "served": e.served,
                "batches": e.batches,
                "padded_slots": e.padded_slots,
                "occupancy": (e.served / (e.served + e.padded_slots)
                              if e.served else 0.0),
                "unit_plan_latency_s": e.unit_plan.total_latency_s,
                "compiled_buckets": sorted({b for b, _ in e.compiled}),
            }
            for n, e in self._entries.items()
        }
        served = sum(e.served for e in self._entries.values())
        return {
            "policy": self.policy,
            "max_batch": self.max_batch,
            "tuning": self.config.tuning,
            "replicas": len(self._groups),
            "buckets": list(self.buckets),
            "served": served,
            "batches": sum(e.batches for e in self._entries.values()),
            "dispatch_wall_s": self._wall_s,
            "throughput_rps": served / self._wall_s if self._wall_s else 0.0,
            "pending": len(self._queue),
            "plan_macs_served": self.ledger.total_macs,
            "plan_cycles_served": self.ledger.total_cycles,
            # backend degradations observed at dispatch-trace time
            "fallbacks": [(f.kind, f.src, f.dst)
                          for f in self.fault_ledger.fallbacks],
            "latency_spikes": self._spikes,
            "faults": (self.faults.summary()
                       if self.faults is not None else None),
            "models": per_model,
        }


def latency_percentiles(tickets: Sequence[Any],
                        pcts: Sequence[float] = (50, 95, 99),
                        ) -> Dict[str, float]:
    """Wall-clock submit-to-completion percentiles over served tickets
    (works for both `Ticket` and `GenTicket`)."""
    import numpy as np
    lats = sorted(t.latency_s for t in tickets if t.done)
    if not lats:
        return {f"p{p:g}_ms": 0.0 for p in pcts}
    return {f"p{p:g}_ms": float(np.percentile(np.asarray(lats), p) * 1e3)
            for p in pcts}


# ---------------------------------------------------------------------------
# Continuous batching over the paged KV block pool
# ---------------------------------------------------------------------------

_GEN_STATUSES = ("queued", "running", "done", "cancelled", "expired",
                 "failed")
_TERMINAL = ("done", "cancelled", "expired", "failed")


@dataclasses.dataclass(eq=False)
class GenTicket:
    """One generation request in the continuous scheduler.

    `prompt` is the submitted prompt, immutable; `context` is the prefix
    the request's cache currently encodes (grows past `prompt` only when a
    preemption forces generated tokens back through prefill). `tokens` is
    every token generated so far; `status` walks
    queued -> running -> done | cancelled | expired | failed.

    "failed" is terminal: the numerics guard quarantined the request
    (non-finite logits) or its transient-error retry budget ran out;
    `error` says why. `retries` counts backoff-and-requeue cycles
    (admission-time pool storms / transient kernel errors), `migrations`
    counts replica failovers (`ReplicaSpread` drained a lost replica and
    re-prefilled this request on a survivor) — both surfaced like
    `preemptions`, and migration shares preemption's parity carve-out: a
    re-prefilled context is not bitwise-guaranteed against the
    uninterrupted stream.
    """

    rid: int
    prompt: Tuple[int, ...]
    steps: int
    submit_s: float
    deadline_s: Optional[float] = None  # absolute perf_counter deadline
    context: Tuple[int, ...] = ()
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = "queued"
    pos: int = 0                    # next cache position to be written
    preemptions: int = 0
    retries: int = 0                # transient-failure requeue count
    migrations: int = 0             # replica-failover count
    error: Optional[str] = None     # why status == "failed"
    not_before_s: float = 0.0       # backoff: earliest re-admission time
    replica: int = 0                # mesh data group serving this request
    done_s: float = 0.0

    @property
    def done(self) -> bool:
        return self.status == "done"

    @property
    def latency_s(self) -> float:
        if self.status not in _TERMINAL:
            return float("nan")
        return self.done_s - self.submit_s


class ContinuousScheduler:
    """Per-step admission decode scheduler over a paged `KVBlockPool`.

    Where `Scheduler` forms a batch and *drains* it (every request in a
    dispatch enters and leaves together, so the batch hollows out as short
    requests finish), this scheduler re-forms the decode batch *every
    step*: finished rows leave, waiting requests join (their prompt runs
    through a batch-1 `prefill_ingest_program` compiled at its exact
    length, interleaved between decode steps), and each request's KV cache
    lives in pool blocks allocated on demand — no dense
    `(max_batch, max_len)` buffers, no stranded rows.

    Admission is driven by pool occupancy plus the analytic plan:

      * blocks    — a request joins only when the pool can cover its full
        prompt plus the next decode write (`free_blocks`), and is evicted
        (youngest-first) when a longer-lived request needs a block the
        pool cannot supply;
      * plan cost — `max_live_cost_s` bounds the running set by the
        summed MMIE-projected latency of one batch-1 paged decode step
        per live request (`NetworkPlan.total_latency_s` of
        `paged_decode_program`, gather reconstruction included), the same
        analytic admission currency `Scheduler.max_queue_cost_s` uses.

    Parity contract (tests/test_continuous.py): under the default
    `EngineConfig(row_align=8)` a request's tokens are bitwise identical
    whether it ran solo (`max_batch=1`), rode a static drained batch
    (`admission="drain"`), or rode a continuous batch in which neighbours
    joined and finished mid-generation. Three mechanisms compose: prefill
    is always batch-1 at the exact prompt length; `row_align` makes every
    decode bucket's GEMMs row-for-row identical; the decode mask zeroes
    positions past `pos` exactly, so recycled-block garbage never reaches
    a logit (see kv_pool.py). The one carve-out is *preemption*: a
    preempted request re-prefills its prompt + generated tokens, and a
    length-S+k prefill is not bitwise-guaranteed against S-prefill +
    k decode steps — so preemption is surfaced (`GenTicket.preemptions`)
    and never happens when the pool is sized for the offered load.
    """

    def __init__(self, cfg, params, *, max_len: int, num_blocks: int,
                 block_size: int = 8, max_batch: int = 8,
                 buckets: Optional[Sequence[int]] = None,
                 config: Optional[E.EngineConfig] = None,
                 admission: str = "continuous",
                 max_live_cost_s: Optional[float] = None,
                 max_slots: int = 64, state_dtype=jnp.bfloat16,
                 mesh: Optional[Any] = None,
                 faults: Optional[_faults.FaultInjector] = None,
                 guard: Optional[bool] = None, max_retries: int = 3,
                 fault_site: str = ""):
        if admission not in ("continuous", "drain"):
            raise ValueError(f"unknown admission {admission!r}; expected "
                             "'continuous' or 'drain'")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        from repro.serve import engine as serve_engine
        from repro.serve.kv_pool import KVBlockPool, PoolExhausted
        self._serve_engine = serve_engine
        self._PoolExhausted = PoolExhausted
        self.cfg = cfg
        self.params = params
        self.config = config if config is not None \
            else E.EngineConfig(row_align=8, fallback="chain")
        # a model-parallel mesh for every decode/prefill compile: this
        # scheduler owns ONE replica (one paged pool) — spreading across
        # data groups is ReplicaSpread's job, so the mesh here is expected
        # to be a (1, model) group (or any mesh whose model axis matches
        # config.parallel; the data axis is simply replicated over)
        self.mesh = mesh
        if mesh is not None:
            from repro.engine import parallel as parlib
            if self.config.parallel is None:
                raise ValueError(
                    "ContinuousScheduler(mesh=...) needs config.parallel "
                    "(an engine.ParallelConfig) to say how ops split over "
                    "the mesh's model axis")
            parlib.check_mesh(mesh, self.config.parallel)
        self.admission = admission
        self.max_batch = max_batch
        self.max_live_cost_s = max_live_cost_s
        if buckets is None:
            buckets = []
            b = 1
            while b < max_batch:
                buckets.append(b)
                b *= 2
            buckets.append(max_batch)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        if self.buckets[-1] != max_batch:
            raise ValueError(f"buckets {self.buckets} must end at "
                             f"max_batch={max_batch}")
        # fault-tolerance knobs: `faults` is this scheduler's injector
        # (installed for the dynamic extent of its dispatches so the
        # dispatch/kv_pool hooks observe it); `guard` compiles the
        # numerics-guard program variants (default: only when injecting —
        # the clean path keeps the unguarded programs, so fault hooks add
        # zero dispatches); `max_retries` bounds transient-failure
        # requeues per ticket; `fault_site` namespaces this scheduler's
        # fault-point sites (ReplicaSpread sets "r<i>:" per replica).
        self.faults = faults
        self.guard = (faults is not None) if guard is None else bool(guard)
        self.max_retries = int(max_retries)
        self.fault_site = fault_site
        self.fault_ledger = E.Ledger()  # trace-time dispatch records
        self.pool = KVBlockPool(cfg, max_len=max_len, block_size=block_size,
                                num_blocks=num_blocks, max_slots=max_slots,
                                state_dtype=state_dtype)
        self.pool.fault_site = fault_site
        self.layout = self.pool.layout
        # analytic unit cost of one live request: a batch-1 paged decode
        # step (attention/FFN GEMMs + the paged-gather reconstruction)
        self.unit_step_plan = E.plan_network(
            serve_engine.paged_decode_program(cfg, self.layout, 1),
            self.config)
        self.unit_step_s = self.unit_step_plan.total_latency_s
        self._decode: Dict[int, E.CompiledNet] = {}
        self._prefill: Dict[int, E.CompiledNet] = {}
        self._waiting: List[GenTicket] = []
        self._running: List[GenTicket] = []
        self._next_rid = 0
        # counters (totals + per-step history, for stats())
        self._steps = 0
        self._tokens_out = 0
        self._fill_sum = 0.0
        self._admitted = 0
        self._evicted = 0
        self._expired = 0
        self._cancelled = 0
        self._failed = 0                # quarantined / retry-exhausted
        self._retries = 0               # transient requeue events
        self._spikes = 0                # injected latency spikes absorbed
        self._decode_faults = 0         # transient decode-dispatch errors
        self._consec_decode_faults = 0
        self._admit_history: List[int] = []
        self._evict_history: List[int] = []
        self._wall_s = 0.0
        # exactly-once termination invariant: rid -> terminal status. Every
        # terminal transition routes through _mark_terminal, which raises
        # FatalError on a double-termination — the chaos harness's core
        # property, enforced in-band.
        self._terminated: Dict[int, str] = {}

    def _inj_ctx(self):
        if self.faults is None:
            return contextlib.nullcontext()
        return _faults.injecting(self.faults)

    def _mark_terminal(self, t: GenTicket, status: str,
                       error: Optional[str] = None) -> None:
        """The single gate to a terminal status: records completion time,
        bumps the matching counter, and enforces that no ticket ever
        terminates twice."""
        if t.rid in self._terminated:
            raise FatalError(
                f"request {t.rid} terminated twice: already "
                f"{self._terminated[t.rid]!r}, now {status!r}")
        if t.status in _TERMINAL:
            raise FatalError(
                f"request {t.rid} re-terminated: {t.status!r} -> {status!r}")
        self._terminated[t.rid] = status
        t.status = status
        t.error = error
        t.done_s = time.perf_counter()
        self._failed += status == "failed"
        self._expired += status == "expired"
        self._cancelled += status == "cancelled"

    # -- compiled-program caches --------------------------------------------

    def decode_compiled(self, bucket: int) -> E.CompiledNet:
        """The paged decode step at `bucket` rows (pool arrays donated).
        Under `guard` this is the numerics-guard program variant (poison
        mask in, per-row finite verdict out); the clean path compiles the
        unguarded program, identical to a fault-free scheduler's."""
        if bucket not in self._decode:
            prog = self._serve_engine.paged_decode_program(
                self.cfg, self.layout, bucket, guard=self.guard)
            self._decode[bucket] = E.compile(prog, self.config,
                                             donate_argnums=(1,),
                                             mesh=self.mesh)
        return self._decode[bucket]

    def prefill_compiled(self, seq: int) -> E.CompiledNet:
        """Batch-1 prefill-ingest at exact prompt length `seq` (pool
        arrays donated) — one jit entry per distinct length."""
        if seq not in self._prefill:
            prog = self._serve_engine.prefill_ingest_program(
                self.cfg, self.layout, seq, guard=self.guard)
            self._prefill[seq] = E.compile(prog, self.config,
                                           donate_argnums=(1,),
                                           mesh=self.mesh)
        return self._prefill[seq]

    # -- request lifecycle --------------------------------------------------

    def validate_request(self, prompt: Sequence[int],
                         steps: int) -> Tuple[int, ...]:
        """Shape/capacity checks for one request; returns the normalized
        prompt. Factored out of `submit` so `ReplicaSpread` can validate
        a request even when no healthy replica can accept it yet."""
        prompt = tuple(int(t) for t in prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        total = len(prompt) + steps
        if total > self.layout.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + steps ({steps}) exceeds "
                f"max_len={self.layout.max_len}")
        # guarantee forward progress: a request alone in the pool must fit
        need = -(-total // self.layout.block_size)
        if need > self.pool.allocator.num_blocks - 1:
            raise ValueError(
                f"request needs {need} blocks but the pool only has "
                f"{self.pool.allocator.num_blocks - 1} usable ones")
        return prompt

    def submit(self, prompt: Sequence[int], steps: int,
               timeout_s: Optional[float] = None) -> GenTicket:
        """Queue one greedy-generation request: `steps` tokens after
        `prompt`. `timeout_s` is a wall-clock deadline relative to now;
        past it the request is dropped (queued or mid-generation) and its
        blocks return to the pool."""
        prompt = self.validate_request(prompt, steps)
        now = time.perf_counter()
        t = GenTicket(rid=self._next_rid, prompt=prompt, steps=steps,
                      submit_s=now, context=prompt,
                      deadline_s=None if timeout_s is None
                      else now + timeout_s)
        self._next_rid += 1
        self._waiting.append(t)
        return t

    def cancel(self, ticket: GenTicket) -> bool:
        """Cancel a queued or running request. A running request's KV
        blocks return to the pool immediately (before the next step)."""
        if ticket.status == "queued":
            self._mark_terminal(ticket, "cancelled")
            self._waiting = [t for t in self._waiting if t is not ticket]
            return True
        if ticket.status == "running":
            self.pool.release(ticket.rid)
            self._mark_terminal(ticket, "cancelled")
            self._running = [t for t in self._running if t is not ticket]
            return True
        return False

    def pending(self) -> int:
        return len(self._waiting)

    def running(self) -> int:
        return len(self._running)

    # -- internal step machinery --------------------------------------------

    def _expire_deadlines(self) -> None:
        now = time.perf_counter()

        def past(t):
            return t.deadline_s is not None and now > t.deadline_s

        for t in [t for t in self._running if past(t)]:
            self.pool.release(t.rid)
            self._mark_terminal(t, "expired")
        self._running = [t for t in self._running if t.status == "running"]
        for t in [t for t in self._waiting if past(t)]:
            self._mark_terminal(t, "expired")
        self._waiting = [t for t in self._waiting if t.status == "queued"]

    def _can_admit(self, t: GenTicket) -> bool:
        seq = len(t.context)
        # blocks for the whole prompt plus the next decode write
        need = seq // self.layout.block_size + 1
        if self.pool.allocator.free_blocks < need:
            return False
        if not self.pool._free_slots:
            return False
        if self.max_live_cost_s is not None and \
                (len(self._running) + 1) * self.unit_step_s \
                > self.max_live_cost_s:
            return False
        return True

    def _admit(self, t: GenTicket) -> bool:
        """Prefill-ingest `t` into the pool and join the running set.

        Atomic under failure: an injected pool storm or a transient
        kernel error mid-admission returns every claimed resource and
        re-raises for the caller's retry/backoff path. Returns False when
        the numerics guard quarantined the admission (the ticket is then
        terminal "failed"), True on success.
        """
        seq = len(t.context)
        self.pool.register(t.rid)
        try:
            with self._inj_ctx():          # pool-storm hook sees injector
                self.pool.ensure(t.rid, seq)  # prompt + next decode write
            pre = self.prefill_compiled(seq)
            table_row = jnp.asarray(self.pool.allocator.tables[t.rid],
                                    jnp.int32)
            slot = jnp.int32(self.pool._slot_of[t.rid])
            toks = jnp.asarray([t.context], jnp.int32)
            with self._inj_ctx(), _ledger.tracking(self.fault_ledger):
                if self.guard:
                    fire = (self.faults is not None and self.faults.fire(
                        "numerics", site=f"{self.fault_site}pre:{t.rid}"))
                    poison = jnp.float32(float("nan") if fire else 0.0)
                    tok, ok, self.pool.arrays = pre.apply(
                        self.params, self.pool.arrays, table_row, slot,
                        toks, poison)
                else:
                    ok = None
                    tok, self.pool.arrays = pre.apply(
                        self.params, self.pool.arrays, table_row, slot,
                        toks)
        except (self._PoolExhausted, TransientError):
            self.pool.release(t.rid)
            raise
        if ok is not None and not bool(ok):
            self._quarantine(t, "non-finite prefill logits")
            return False
        t.tokens.append(int(tok[0]))
        t.pos = seq
        t.status = "running"
        self._running.append(t)
        self._admitted += 1
        return True

    def _quarantine(self, t: GenTicket, reason: str) -> None:
        """Numerics-guard quarantine: scrub-and-release the request's pool
        state (poison must never recycle into other requests' blocks — the
        parity contract needs finite pool contents) and fail the ticket.
        Batchmates are untouched: the guarded program poisons logits
        row-selectively via `jnp.where`, so their tokens stay bitwise
        identical to the clean run."""
        self.pool.scrub_release(t.rid)
        self._mark_terminal(t, "failed", error=reason)

    def _retry(self, t: GenTicket, err: str) -> None:
        """Transient admission failure: requeue with capped exponential
        backoff (deterministic jitter keyed by rid), or fail once the
        retry budget is spent."""
        t.retries += 1
        if t.retries > self.max_retries:
            self._mark_terminal(
                t, "failed",
                error=f"retry budget exhausted ({self.max_retries}): {err}")
            return
        self._retries += 1
        t.not_before_s = time.perf_counter() + backoff_s(
            t.retries, base=0.002, cap=0.1,
            seed=self.faults.seed if self.faults is not None else 0,
            token=f"{self.fault_site}{t.rid}")
        t.status = "queued"
        self._waiting.insert(0, t)

    def _preempt(self, t: GenTicket) -> None:
        """Evict a running request: free its blocks and requeue it at the
        front. Its generated-so-far tokens fold into `context`, so on
        re-admission one prefill rebuilds the cache and emits the next
        token (the module-docstring parity carve-out)."""
        self.pool.release(t.rid)
        t.context = t.context + tuple(t.tokens[len(t.context)
                                               - len(t.prompt):])
        t.status = "queued"
        t.preemptions += 1
        self._running = [r for r in self._running if r is not t]
        self._waiting.insert(0, t)
        self._evicted += 1

    def _finish(self, t: GenTicket) -> None:
        self.pool.release(t.rid)
        self._mark_terminal(t, "done")

    def _bucket_for(self, k: int) -> int:
        for b in self.buckets:
            if b >= k:
                return b
        return self.buckets[-1]

    # -- the per-step loop ---------------------------------------------------

    def step(self) -> List[GenTicket]:
        """One scheduler step: expire deadlines, admit from the queue
        (continuous: whenever a batch row and pool capacity are free;
        drain: only once the running set empties), ensure every running
        row's next block (preempting youngest-first on exhaustion), run
        one batched paged decode step, retire finished requests. Returns
        the tickets that reached a terminal status this step (done, or
        failed by the numerics guard / retry budget)."""
        t0 = time.perf_counter()
        if self.faults is not None:
            spike = self.faults.latency(f"{self.fault_site}step")
            if spike:
                self._spikes += 1
                time.sleep(spike)
        self._expire_deadlines()

        admitted_now = 0
        finished: List[GenTicket] = []
        if self.admission == "continuous" or not self._running:
            now = time.perf_counter()
            for t in list(self._waiting):
                if len(self._running) >= self.max_batch:
                    break
                if t.not_before_s > now:
                    continue        # backing off: invisible to head-of-line
                if not self._can_admit(t):
                    break           # head-of-line blocking preserved
                self._waiting.remove(t)
                try:
                    ok = self._admit(t)
                except (self._PoolExhausted, TransientError) as e:
                    # atomic failure: _admit returned every resource;
                    # requeue with backoff (or fail if the budget is spent)
                    self._retry(t, str(e))
                    if t.status == "failed":
                        finished.append(t)
                    continue
                if not ok:          # guard quarantined the admission
                    finished.append(t)
                    continue
                admitted_now += 1
                if len(t.tokens) >= t.steps:
                    # finished at prefill: never occupies a decode row
                    self._finish(t)
                    self._running = [r for r in self._running if r is not t]
                    finished.append(t)
        self._admit_history.append(admitted_now)
        evicted_now = 0

        if not self._running:
            self._evict_history.append(evicted_now)
            self._wall_s += time.perf_counter() - t0
            return finished

        # grow each running row's table to cover its next write; on
        # exhaustion evict the youngest admit until the older ones fit
        i = 0
        while i < len(self._running):
            t = self._running[i]
            try:
                with self._inj_ctx():      # pool-storm hook sees injector
                    self.pool.ensure(t.rid, t.pos)
                i += 1
            except self._PoolExhausted:
                victim = self._running[-1]
                if victim is t and len(self._running) == 1 \
                        and self.faults is None:
                    raise RuntimeError(
                        "single running request exhausted the pool — "
                        "impossible when submit()'s whole-request fit "
                        "check passed")  # pragma: no cover
                # with an injector a lone running request CAN see a storm;
                # preemption (not failure) keeps it alive through backoff
                self._preempt(victim)
                evicted_now += 1
                if victim is t:
                    break
        self._evict_history.append(evicted_now)

        k = len(self._running)
        if k:
            bucket = self._bucket_for(k)
            rids = [t.rid for t in self._running]
            tables = self.pool.table_rows(rids, bucket)
            slots = self.pool.slot_rows(rids, bucket)
            last = [t.tokens[-1] for t in self._running]
            toks = jnp.asarray(last + [0] * (bucket - k),
                               jnp.int32)[:, None]
            pos = jnp.asarray([t.pos for t in self._running]
                              + [0] * (bucket - k), jnp.int32)
            dec = self.decode_compiled(bucket)
            try:
                with self._inj_ctx(), _ledger.tracking(self.fault_ledger):
                    if self.guard:
                        mask = [float("nan") if (
                            self.faults is not None and self.faults.fire(
                                "numerics",
                                site=f"{self.fault_site}{t.rid}"))
                            else 0.0 for t in self._running]
                        poison = jnp.asarray(mask + [0.0] * (bucket - k),
                                             jnp.float32)
                        tok, okv, self.pool.arrays = dec.apply(
                            self.params, self.pool.arrays, tables, slots,
                            toks, pos, poison)
                    else:
                        okv = None
                        tok, self.pool.arrays = dec.apply(
                            self.params, self.pool.arrays, tables, slots,
                            toks, pos)
            except TransientError as e:
                # trace-time kernel fault with no fallback left: the step
                # produced nothing (a trace error never consumes the
                # donated pool arrays), so the same rows retry next step.
                self._decode_faults += 1
                self._consec_decode_faults += 1
                if self._consec_decode_faults >= 8:
                    raise FatalError(
                        f"{self._consec_decode_faults} consecutive decode "
                        f"steps failed; last: {e}") from e
                self._wall_s += time.perf_counter() - t0
                return finished
            self._consec_decode_faults = 0
            tok = jax.device_get(tok)
            okl = None if okv is None else jax.device_get(okv)
            self._steps += 1
            self._fill_sum += k / bucket
            for i, t in enumerate(self._running):
                if okl is not None and not bool(okl[i]):
                    # the guard poisoned only this row's logits (jnp.where
                    # row-select), so batchmates' tokens are untouched
                    self._quarantine(t, "non-finite decode logits")
                    finished.append(t)
                    continue
                t.tokens.append(int(tok[i]))
                t.pos += 1
                self._tokens_out += 1
            for t in [t for t in self._running
                      if t.status == "running"
                      and len(t.tokens) >= t.steps]:
                self._finish(t)
                finished.append(t)
            self._running = [t for t in self._running
                             if t.status == "running"]
        self._wall_s += time.perf_counter() - t0
        return finished

    def run(self) -> List[GenTicket]:
        """Serve until queue and batch are empty; terminal tickets in
        completion order. Sleeps through backoff windows: when every
        waiting request is backing off, the loop waits for the earliest
        `not_before_s` instead of spinning or declaring no-progress."""
        done: List[GenTicket] = []
        while self._waiting or self._running:
            before = (len(self._waiting), len(self._running),
                      self._tokens_out, self._admitted, self._expired,
                      self._cancelled, self._failed, self._retries)
            done.extend(self.step())
            after = (len(self._waiting), len(self._running),
                     self._tokens_out, self._admitted, self._expired,
                     self._cancelled, self._failed, self._retries)
            if before == after and self._waiting and not self._running:
                now = time.perf_counter()
                wake = [t.not_before_s for t in self._waiting
                        if t.not_before_s > now]
                if wake:
                    time.sleep(min(0.25, min(wake) - now))
                    continue
                raise RuntimeError(
                    f"no progress: {len(self._waiting)} waiting but none "
                    "admittable (pool or live-cost budget too small for "
                    "the head request)")
        return done

    # -- stats ---------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Serving counters plus pool occupancy. `decode_fill` is the mean
        real-rows / bucket-rows ratio over decode steps (the quantity
        drain-mode scheduling strands); `pool` carries the block-pool
        snapshot (occupancy, free-block low-water mark); the
        `*_per_step` lists hold the per-step admitted/evicted counts."""
        return {
            "admission": self.admission,
            "max_batch": self.max_batch,
            "buckets": list(self.buckets),
            "steps": self._steps,
            "tokens_out": self._tokens_out,
            "decode_fill": (self._fill_sum / self._steps
                            if self._steps else 0.0),
            "admitted": self._admitted,
            "evicted": self._evicted,
            "expired": self._expired,
            "cancelled": self._cancelled,
            "failed": self._failed,
            "retries": self._retries,
            "latency_spikes": self._spikes,
            "decode_faults": self._decode_faults,
            "guard": self.guard,
            # backend degradations observed at dispatch-trace time
            "fallbacks": [(f.kind, f.src, f.dst)
                          for f in self.fault_ledger.fallbacks],
            "faults": (self.faults.summary()
                       if self.faults is not None else None),
            "admitted_per_step": list(self._admit_history),
            "evicted_per_step": list(self._evict_history),
            "pending": len(self._waiting),
            "running": len(self._running),
            "dispatch_wall_s": self._wall_s,
            "throughput_tps": (self._tokens_out / self._wall_s
                               if self._wall_s else 0.0),
            "unit_step_s": self.unit_step_s,
            "unit_step_gather_s": self.unit_step_plan.gather_latency_s,
            "compiled_decode_buckets": sorted(self._decode),
            "compiled_prefill_lens": sorted(self._prefill),
            "pool": self.pool.snapshot(),
        }


# ---------------------------------------------------------------------------
# Replica spreading across mesh data-parallel groups
# ---------------------------------------------------------------------------


class ReplicaSpread:
    """Data-parallel front over one `ContinuousScheduler` per replica,
    with replica health tracking and failover.

    Two placement modes share one code path:

      * mesh mode     — `engine.data_groups` splits a (data, model) mesh
        into `data` submeshes of shape (1, model); each gets its *own*
        `ContinuousScheduler` — its own paged `KVBlockPool` (`num_blocks`
        is per replica), its own compiled-bucket cache, its own admission
        state. KV pages never cross a data group, so tensor-parallel
        collectives run inside one (1, model) group and no cross-group
        traffic exists at all.
      * meshless mode — `replicas=N` with no mesh builds N independent
        single-device schedulers (the chaos harness's failover substrate:
        no multi-device runtime needed to exercise replica loss).

    Routing is least-loaded over *healthy* replicas: a new request goes
    to the healthy replica with the fewest pending + running requests
    (ties to the lowest index, so placement is deterministic for a
    deterministic submit order). When no replica is healthy, requests
    wait in an orphan queue and are placed as soon as a probe readmits a
    replica.

    Failover: a "replica" fault-point fire (or a `TransientError`
    escaping a replica's step) bumps that replica's consecutive-failure
    count; at `trip_after` the replica *trips* — it is marked unhealthy,
    its pool state is abandoned, and every in-flight request is drained:
    generated tokens fold into `context` (exactly the preemption
    mechanics), `GenTicket.migrations` increments, and the request
    re-prefills on the least-loaded surviving replica (orphan queue when
    none survive). A tripped replica is probed after a capped
    deterministic backoff (`serve.faults.backoff_s`); a successful probe
    readmits it and flushes orphans onto it.

    The per-request bitwise parity contract is unchanged for requests the
    fault path never touched; a migrated request shares preemption's
    carve-out (one re-prefill of prompt + generated tokens).
    """

    def __init__(self, cfg, params, *, mesh: Optional[Any] = None,
                 replicas: Optional[int] = None,
                 config: Optional[E.EngineConfig] = None,
                 faults: Optional[_faults.FaultInjector] = None,
                 trip_after: int = 2, probe_backoff_s: float = 0.02,
                 **kwargs):
        if (mesh is None) == (replicas is None):
            raise ValueError(
                "pass exactly one of mesh= (data-parallel groups) or "
                "replicas= (meshless independent schedulers)")
        if mesh is not None:
            from repro.engine import parallel as parlib
            if config is None:
                config = E.EngineConfig(row_align=8, fallback="chain",
                                        parallel=parlib.ParallelConfig())
            if config.parallel is None:
                raise ValueError(
                    "ReplicaSpread needs config.parallel (an "
                    "engine.ParallelConfig) describing the mesh's model "
                    "axis")
            parlib.check_mesh(mesh, config.parallel)
            self.groups: Tuple[Any, ...] = parlib.data_groups(mesh)
        else:
            if replicas < 1:
                raise ValueError(f"replicas must be >= 1, got {replicas}")
            if config is None:
                config = E.EngineConfig(row_align=8, fallback="chain")
            self.groups = (None,) * replicas
        self.mesh = mesh
        self.config = config
        self.faults = faults
        self.trip_after = int(trip_after)
        self.probe_backoff_s = float(probe_backoff_s)
        self.replicas: Tuple[ContinuousScheduler, ...] = tuple(
            ContinuousScheduler(cfg, params, config=config, mesh=g,
                                faults=faults, fault_site=f"r{i}:",
                                **kwargs)
            for i, g in enumerate(self.groups))
        # per-replica health: consecutive-failure trip + probe backoff
        self.health: List[Dict[str, Any]] = [
            {"healthy": True, "consec_failures": 0, "trips": 0,
             "probes": 0, "down_until": 0.0}
            for _ in self.groups]
        self._orphans: List[GenTicket] = []   # placed once a probe succeeds
        self._migrations = 0                  # drained-and-replaced tickets

    def _load(self, r: ContinuousScheduler) -> int:
        return r.pending() + r.running()

    def _healthy(self) -> List[int]:
        return [i for i, h in enumerate(self.health) if h["healthy"]]

    def _target(self) -> Optional[int]:
        """Least-loaded healthy replica index, or None when all are down."""
        up = self._healthy()
        if not up:
            return None
        return min(up, key=lambda j: (self._load(self.replicas[j]), j))

    def _place(self, t: GenTicket, i: int) -> None:
        """Adopt ticket `t` into replica `i`'s waiting queue: the rid is
        reassigned from the target's counter (rid spaces are per replica;
        the exactly-once invariant rides the ticket's own status)."""
        r = self.replicas[i]
        t.rid = r._next_rid
        r._next_rid += 1
        t.replica = i
        t.status = "queued"
        r._waiting.append(t)

    def _fail_replica(self, i: int, reason: str) -> None:
        """Trip replica `i`: mark it down with a probe backoff, abandon
        its pool state, and migrate every queued/running request to the
        least-loaded surviving replica (orphan queue when none survive).
        Running requests fold generated tokens into `context` (the
        preemption mechanics) so one re-prefill rebuilds their cache."""
        r = self.replicas[i]
        h = self.health[i]
        h["healthy"] = False
        h["trips"] += 1
        h["consec_failures"] = 0
        h["down_until"] = time.perf_counter() + backoff_s(
            h["trips"], base=self.probe_backoff_s, cap=1.0,
            seed=self.faults.seed if self.faults is not None else 0,
            token=f"trip:{i}")
        drained = list(r._running) + list(r._waiting)
        for t in r._running:
            r.pool.release(t.rid)
            t.context = t.context + tuple(t.tokens[len(t.context)
                                                   - len(t.prompt):])
            t.migrations += 1
            self._migrations += 1
        r._running = []
        r._waiting = []
        for t in drained:
            t.status = "queued"
            t.not_before_s = 0.0
            j = self._target()
            if j is None:
                t.replica = -1
                self._orphans.append(t)
            else:
                self._place(t, j)

    def _probe(self, i: int) -> bool:
        """Probe a tripped replica once its backoff expires; on success
        readmit it (and flush orphans onto it), on failure back off
        again. The probe consults the "replica" fault point at site
        `probe:<i>` so chaos schedules can hold a replica down."""
        h = self.health[i]
        h["probes"] += 1
        if self.faults is not None and self.faults.fire(
                "replica", site=f"probe:{i}"):
            h["down_until"] = time.perf_counter() + backoff_s(
                h["trips"] + h["probes"], base=self.probe_backoff_s,
                cap=1.0, seed=self.faults.seed, token=f"probe:{i}")
            return False
        h["healthy"] = True
        h["consec_failures"] = 0
        h["down_until"] = 0.0
        self._flush_orphans()
        return True

    def _flush_orphans(self) -> None:
        while self._orphans:
            j = self._target()
            if j is None:
                return
            self._place(self._orphans.pop(0), j)

    def submit(self, prompt: Sequence[int], steps: int,
               timeout_s: Optional[float] = None) -> GenTicket:
        """Route one request to the least-loaded healthy replica and
        queue it there; the returned ticket's `replica` records the
        placement (-1 while orphaned: every replica is down and the
        request waits for a probe to readmit one)."""
        i = self._target()
        if i is not None:
            t = self.replicas[i].submit(prompt, steps, timeout_s)
            t.replica = i
            return t
        r0 = self.replicas[0]
        norm = r0.validate_request(prompt, steps)
        now = time.perf_counter()
        t = GenTicket(rid=-1, prompt=norm, steps=steps, submit_s=now,
                      context=norm, replica=-1,
                      deadline_s=None if timeout_s is None
                      else now + timeout_s)
        self._orphans.append(t)
        return t

    def cancel(self, ticket: GenTicket) -> bool:
        """Cancel a request wherever it lives: still orphaned (no healthy
        replica has adopted it), queued, or running on its replica —
        including a replica currently marked unhealthy (its queues were
        drained at trip time, so the ticket always lives where
        `ticket.replica` says)."""
        if ticket in self._orphans:
            self._orphans.remove(ticket)
            ticket.status = "cancelled"
            ticket.done_s = time.perf_counter()
            return True
        if ticket.replica < 0:
            return False
        return self.replicas[ticket.replica].cancel(ticket)

    def pending(self) -> int:
        return sum(r.pending() for r in self.replicas) + len(self._orphans)

    def running(self) -> int:
        return sum(r.running() for r in self.replicas)

    def step(self) -> List[GenTicket]:
        """One scheduling step on every healthy replica (each replica
        interleaves its own prefills and runs one decode step), probing
        tripped replicas whose backoff expired; terminal tickets from all
        replicas, replica-major. Consults the "replica" fault point at
        site `replica:<i>` before each replica's step — a fire counts a
        consecutive failure and trips the replica at `trip_after`."""
        now = time.perf_counter()
        if self._orphans and self._healthy():
            self._flush_orphans()
        done: List[GenTicket] = []
        for i, r in enumerate(self.replicas):
            h = self.health[i]
            if not h["healthy"]:
                if now >= h["down_until"]:
                    self._probe(i)
                continue
            if not (r._waiting or r._running):
                continue
            if self.faults is not None and self.faults.fire(
                    "replica", site=f"replica:{i}"):
                h["consec_failures"] += 1
                if h["consec_failures"] >= self.trip_after:
                    self._fail_replica(i, "injected replica loss")
                continue
            try:
                out = r.step()
            except TransientError:
                h["consec_failures"] += 1
                if h["consec_failures"] >= self.trip_after:
                    self._fail_replica(i, "transient step failure")
                continue
            h["consec_failures"] = 0
            done.extend(out)
        return done

    def run(self) -> List[GenTicket]:
        """Serve until every replica's queue and batch are empty and no
        orphans remain; terminal tickets in completion order. When the
        only obstacle is time (tripped replicas backing off toward their
        probe, or requests in a retry backoff window), the loop sleeps
        instead of declaring no-progress."""
        done: List[GenTicket] = []
        while self.pending() or self.running():
            before = (self.pending(), self.running(), len(self._orphans),
                      self._migrations, tuple(h["healthy"]
                                              for h in self.health),
                      sum(r._tokens_out for r in self.replicas),
                      sum(r._expired + r._cancelled + r._failed
                          + r._retries for r in self.replicas))
            done.extend(self.step())
            after = (self.pending(), self.running(), len(self._orphans),
                     self._migrations, tuple(h["healthy"]
                                             for h in self.health),
                     sum(r._tokens_out for r in self.replicas),
                     sum(r._expired + r._cancelled + r._failed
                         + r._retries for r in self.replicas))
            if before == after and self.pending() and not self.running():
                now = time.perf_counter()
                waits = [h["down_until"] for h in self.health
                         if not h["healthy"]]
                waits += [t.not_before_s for r in self.replicas
                          for t in r._waiting if t.not_before_s > now]
                waits = [w for w in waits if w > now]
                if waits:
                    time.sleep(min(0.25, min(waits) - now))
                    continue
                raise RuntimeError(
                    f"no progress: {self.pending()} waiting but none "
                    "admittable on any replica (per-replica pool or "
                    "live-cost budget too small for the head request)")
        return done

    def stats(self) -> Dict[str, Any]:
        """Aggregate counters plus each replica's full `stats()` dict and
        its health record (trips, probes, consecutive failures)."""
        per = [r.stats() for r in self.replicas]
        wall = sum(s["dispatch_wall_s"] for s in per)
        tokens = sum(s["tokens_out"] for s in per)
        return {
            "replicas": len(self.replicas),
            "healthy_replicas": len(self._healthy()),
            "tokens_out": tokens,
            "steps": sum(s["steps"] for s in per),
            "admitted": sum(s["admitted"] for s in per),
            "evicted": sum(s["evicted"] for s in per),
            "expired": sum(s["expired"] for s in per),
            "cancelled": sum(s["cancelled"] for s in per),
            "failed": sum(s["failed"] for s in per),
            "retries": sum(s["retries"] for s in per),
            "migrations": self._migrations,
            "orphans": len(self._orphans),
            "pending": self.pending(),
            "running": self.running(),
            # replicas step in sequence on one host process, so the
            # aggregate wall is the sum of per-replica dispatch time
            "dispatch_wall_s": wall,
            "throughput_tps": tokens / wall if wall else 0.0,
            "health": [dict(h) for h in self.health],
            "per_replica": per,
        }
