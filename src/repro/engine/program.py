"""Whole-network planning: Program -> compile(cfg) -> CompiledNet.

The paper's headline numbers are *network-level* — Table 4 schedules every
layer of AlexNet / VGG-16 / ResNet-50 onto the same 192 PEs — but the
per-call engine API only ever sees one op. This module adds the two-phase
compile/execute model on top of it:

  * `Program`     — an ordered, shape-complete op graph (a tuple of
    `plan.OpSpec`s) plus, optionally, the executable forward function it
    was derived from. Built from layer tables (`models.cnn.program`) or
    captured from any JAX forward with `trace_program(fn, *avals)` — the
    transformer / SSM forwards behind `serve.engine` included.
  * `NetworkPlan` — the tuple of per-op `EnginePlan`s with the paper's
    Table-4 aggregates (conv @200 MHz vs FC @40 MHz latency, memory-access
    bytes, performance efficiency), computed from shapes alone, without
    running the model.
  * `compile(program, cfg)` -> `CompiledNet` — plans every op under one
    frozen `EngineConfig` (per-layer pallas-vs-xla selection when
    `cfg.policy == "auto"`), exposes `.plan` / `.cost`, and a jitted
    `.apply(*args)` that executes the forward with each op pinned to its
    planned backend (strict: shape divergence from the captured op
    sequence raises instead of silently re-planning).

Capture and execution both run through `api.capturing` / `api.replaying`,
so a compiled network and an eager call see the exact same planning logic.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.core import gfid, modes
from repro.engine import api
from repro.engine import parallel as parlib
from repro.engine import tune as tunelib
from repro.engine.config import EngineConfig, current_config, using_config
from repro.engine.plan import (EnginePlan, OpSpec, auto_backend,
                               parse_einsum, plan_op, with_precision)

_CONV_KINDS = ("conv2d", "conv1d_dw")


@dataclasses.dataclass(frozen=True)
class Program:
    """An ordered, shape-complete engine-op graph for one network.

    `ops` alone fully determines the `NetworkPlan` (analytics need no
    arrays); `fn`/`in_avals` carry the executable forward for
    `CompiledNet.apply` and are excluded from equality/hash so a Program is
    usable as a dict / jit-static key.

    Batch metadata (`batch_size` plus per-leaf `batch_axes`) makes the
    program *re-batchable*: `with_batch(B)` rewrites the op graph and the
    input avals to batch B without re-tracing the model, so a serving
    scheduler can re-plan (and `engine.compile`) one traced program at any
    batch bucket. `batch_axes` is a tuple (one entry per positional arg) of
    pytrees matching `in_avals`, with an int leaf per array leaf: the axis
    carrying the batch, or -1 for unbatched leaves (weights, scalars) —
    see `infer_batch_axes`.
    """

    name: str
    ops: Tuple[OpSpec, ...]
    fn: Optional[Callable[..., Any]] = dataclasses.field(
        default=None, compare=False)
    in_avals: Tuple[Any, ...] = dataclasses.field(
        default=(), compare=False)
    batch_size: Optional[int] = dataclasses.field(
        default=None, compare=False)
    batch_axes: Optional[Tuple[Any, ...]] = dataclasses.field(
        default=None, compare=False)

    def __len__(self) -> int:
        return len(self.ops)

    def with_batch(self, batch: int) -> "Program":
        """The same program re-planned at batch `batch` — op shapes and
        input avals rewritten along the recorded batch axes, no re-trace.

        Conv ops carry the batch on x axis 0 by the engine's NHWC/(B,L,D)
        contract; a dense op is rebatched when its leading x axis is an
        x-free (pure row) label of size `batch_size`. Ops that fold the
        batch elsewhere (e.g. MoE capacity dims) are left unchanged — their
        analytic cost then underestimates the rebatched network, which only
        matters for planning, never for execution (`engine.compile`
        re-captures the executable op sequence from `fn` at the new avals).
        """
        if self.batch_size is None or self.batch_axes is None:
            raise ValueError(
                f"program {self.name!r} carries no batch metadata; build it "
                "with cnn.program / serve.prefill_program / serve."
                "decode_program, or pass batch_size= and batch_axes= to "
                "trace_program (see engine.infer_batch_axes)")
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        if batch == self.batch_size:
            return self
        ops = tuple(_rebatch_op(op, self.batch_size, batch)
                    for op in self.ops)
        in_avals = tuple(
            jax.tree_util.tree_map(
                lambda aval, ax: _rebatch_aval(aval, ax, self.batch_size,
                                               batch),
                arg, axes)
            for arg, axes in zip(self.in_avals, self.batch_axes))
        return dataclasses.replace(self, ops=ops, in_avals=in_avals,
                                   batch_size=batch)


def infer_batch_axes(avals_a: Tuple[Any, ...], avals_b: Tuple[Any, ...],
                     ) -> Tuple[Any, ...]:
    """Derive per-leaf batch axes by diffing the same arg avals built at two
    different batch sizes: the single axis whose size changed is the batch
    axis; leaves with identical shapes (weights, scalars) get -1.

    Using -1 (not None) keeps the axes tree structurally identical to the
    aval tree under `jax.tree_util` (None leaves would vanish).
    """
    def leaf(a, b):
        sa, sb = tuple(a.shape), tuple(b.shape)
        if sa == sb:
            return -1
        if len(sa) != len(sb):
            raise ValueError(f"rank changed with batch: {sa} vs {sb}")
        diffs = [i for i, (x, y) in enumerate(zip(sa, sb)) if x != y]
        if len(diffs) != 1:
            raise ValueError(
                f"ambiguous batch axis: {sa} vs {sb} differ on axes {diffs}")
        return diffs[0]

    return tuple(jax.tree_util.tree_map(leaf, a, b)
                 for a, b in zip(avals_a, avals_b))


def _rebatch_aval(aval: Any, axis: int, old: int, new: int) -> Any:
    if axis < 0:
        return aval
    shape = list(aval.shape)
    if shape[axis] != old:
        raise ValueError(
            f"batch axis {axis} of aval {tuple(aval.shape)} has size "
            f"{shape[axis]}, expected batch_size={old}")
    shape[axis] = new
    return jax.ShapeDtypeStruct(tuple(shape), aval.dtype)


def _rebatch_op(op: OpSpec, old: int, new: int) -> OpSpec:
    """Rewrite one op's batch dim (leading x axis) from `old` to `new`."""
    if op.kind == "gather":
        # the batch lives on the block table (w) leading dim; x is the pool,
        # whose num_blocks may coincidentally equal the old batch size
        if op.w_shape and op.w_shape[0] == old:
            return dataclasses.replace(op, w_shape=(new,) + op.w_shape[1:])
        return op
    if not op.x_shape or op.x_shape[0] != old:
        return op
    if op.kind == "dense":
        st = parse_einsum(op.spec, len(op.x_shape), len(op.w_shape))
        if st.x_labels[0] not in st.x_free:
            return op                   # leading dim is not a pure row dim
    return dataclasses.replace(op, x_shape=(new,) + op.x_shape[1:])


def trace_program(fn: Callable[..., Any], *avals: Any,
                  name: str = "traced",
                  batch_size: Optional[int] = None,
                  batch_axes: Optional[Tuple[Any, ...]] = None) -> Program:
    """Capture `fn`'s engine ops into a `Program` by abstract evaluation.

    `avals` are pytrees of `jax.ShapeDtypeStruct` (or concrete arrays) —
    the capture runs under `jax.eval_shape`, so no FLOPs are spent and no
    device buffers are touched. Every `engine.*` op `fn` issues is recorded
    in call order with its static shapes; ops outside the engine (elementwise
    math, pooling, attention softmax, ...) are executed abstractly but not
    recorded, exactly like a `tracking()` ledger would price them.

    Pass `batch_size` (the batch the avals were built at) together with
    `batch_axes` (per-arg axis trees, see `infer_batch_axes`) to make the
    program re-batchable via `Program.with_batch`.
    """
    if (batch_size is None) != (batch_axes is None):
        raise ValueError("pass batch_size and batch_axes together")
    return Program(name=name, ops=_capture_ops(fn, avals)[0], fn=fn,
                   in_avals=tuple(avals), batch_size=batch_size,
                   batch_axes=batch_axes)


def _capture_ops(fn: Callable[..., Any], avals: Tuple[Any, ...],
                 ) -> Tuple[Tuple[OpSpec, ...], Tuple[Optional[str], ...]]:
    """Shape-trace `fn` and return (op sequence, per-op explicit precision
    overrides — None where the call left precision to the config)."""
    ops: list = []
    precs: list = []
    # The fresh lambda defeats jax.eval_shape's trace cache: a cached trace
    # would skip the function body and record nothing.
    with api.capturing(ops, precs), using_config(EngineConfig(backend="xla")):
        jax.eval_shape(lambda *a: fn(*a), *avals)
    return tuple(ops), tuple(precs)


# ---------------------------------------------------------------------------
# NetworkPlan — Table-4 aggregates from plans alone
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """Per-op plans plus the paper's network-level rollups (Table 4).

    Aggregation matches `core.analytics.NetworkCost` exactly: conv-side
    cycles are priced at the 200 MHz conv clock, FC-side (every `dense`
    plan) at the 40 MHz FC clock; memory accesses are 16-bit words.
    """

    name: str
    plans: Tuple[EnginePlan, ...]

    @property
    def conv_plans(self) -> Tuple[EnginePlan, ...]:
        return tuple(p for p in self.plans if p.kind in _CONV_KINDS)

    @property
    def fc_plans(self) -> Tuple[EnginePlan, ...]:
        return tuple(p for p in self.plans if p.kind == "dense")

    @property
    def gather_plans(self) -> Tuple[EnginePlan, ...]:
        """Paged-KV gather ops (serving memory moves, zero MACs)."""
        return tuple(p for p in self.plans if p.kind == "gather")

    # -- cycles / latency --------------------------------------------------

    @property
    def conv_cycles(self) -> int:
        return sum(p.cycles for p in self.conv_plans)

    @property
    def fc_cycles(self) -> int:
        return sum(p.cycles for p in self.fc_plans)

    @property
    def gather_cycles(self) -> int:
        return sum(p.cycles for p in self.gather_plans)

    @property
    def conv_latency_s(self) -> float:
        return self.conv_cycles / modes.MMIE_CONV_FREQ_HZ

    @property
    def fc_latency_s(self) -> float:
        return self.fc_cycles / modes.MMIE_FC_FREQ_HZ

    @property
    def gather_latency_s(self) -> float:
        """Paged-KV reconstruction time, priced at the conv (memory-system)
        clock — a pure data move never waits on the 40 MHz FC array."""
        return self.gather_cycles / modes.MMIE_CONV_FREQ_HZ

    # -- multi-device placement (engine/parallel.py) -----------------------

    @property
    def shards(self) -> Tuple[Optional[Any], ...]:
        """Per-op `ShardDecision`s, in plan order (None = unsharded plan)."""
        return tuple(p.shard for p in self.plans)

    @property
    def collective_words(self) -> int:
        """Ring-collective wire traffic (16-bit words) of every sharded op's
        combine step — all-gathers for shard-N layers, all-reduces for
        shard-K — folded into `total_latency_s` exactly like PR 6 folded
        paged-gather costs."""
        return sum(p.shard.wire_words for p in self.plans
                   if p.shard is not None)

    @property
    def collective_cycles(self) -> int:
        return sum(p.shard.collective_cycles for p in self.plans
                   if p.shard is not None)

    @property
    def collective_latency_s(self) -> float:
        """Inter-chip combine time, priced at the conv (memory-system)
        clock over the `modes.MMIE_LINK_WORDS_PER_CYCLE` link."""
        return self.collective_cycles / modes.MMIE_CONV_FREQ_HZ

    # -- per-device execution cycles (== the global cycles when unsharded) --

    @property
    def conv_exec_cycles(self) -> int:
        return sum(p.exec_cycles for p in self.conv_plans)

    @property
    def fc_exec_cycles(self) -> int:
        return sum(p.exec_cycles for p in self.fc_plans)

    @property
    def gather_exec_cycles(self) -> int:
        return sum(p.exec_cycles for p in self.gather_plans)

    @property
    def total_latency_s(self) -> float:
        """End-to-end analytic latency of one device's critical path:
        per-device compute cycles (`exec_cycles` — equal to the global
        cycles for every replicated or unsharded op, so this is numerically
        unchanged from the single-device plan when no op shards) plus the
        collective wire time. `conv/fc_latency_s` and `table4_row` stay on
        global cycles — the paper's whole-network Table-4 goldens are
        device-count-invariant."""
        return (self.conv_exec_cycles / modes.MMIE_CONV_FREQ_HZ
                + self.fc_exec_cycles / modes.MMIE_FC_FREQ_HZ
                + self.gather_exec_cycles / modes.MMIE_CONV_FREQ_HZ
                + self.collective_latency_s)

    # -- memory accesses ---------------------------------------------------

    @property
    def conv_ma_words(self) -> int:
        return sum(p.ma_words for p in self.conv_plans)

    @property
    def fc_ma_words(self) -> int:
        return sum(p.ma_words for p in self.fc_plans)

    # -- executed memory traffic (precision-aware; ma_words stays the
    #    paper's 16-bit Table-4 model so the goldens are precision-invariant)

    @property
    def conv_exec_ma_words(self) -> int:
        return sum(p.exec_ma_words for p in self.conv_plans)

    @property
    def fc_exec_ma_words(self) -> int:
        return sum(p.exec_ma_words for p in self.fc_plans)

    @property
    def exec_ma_words(self) -> int:
        """Memory words actually moved by the execution precision: int8
        plans halve their 16-bit-word booking (two int8 values per word),
        fp32 plans book `ma_words` unchanged."""
        return sum(p.exec_ma_words for p in self.plans)

    @property
    def conv_ma_bytes(self) -> int:
        return self.conv_ma_words * modes.MMIE_WORD_BYTES

    @property
    def fc_ma_bytes(self) -> int:
        return self.fc_ma_words * modes.MMIE_WORD_BYTES

    # -- MACs / efficiency -------------------------------------------------

    @property
    def conv_macs(self) -> int:
        return sum(p.macs for p in self.conv_plans)

    @property
    def fc_macs(self) -> int:
        return sum(p.macs for p in self.fc_plans)

    @property
    def total_macs(self) -> int:
        return self.conv_macs + self.fc_macs

    @property
    def conv_perf_efficiency(self) -> float:
        cyc = self.conv_cycles
        return self.conv_macs / (modes.MMIE_NUM_PES * cyc) if cyc else 0.0

    @property
    def fc_perf_efficiency(self) -> float:
        cyc = self.fc_cycles
        return self.fc_macs / (modes.MMIE_NUM_PES * cyc) if cyc else 0.0

    @property
    def performance_efficiency(self) -> float:
        cyc = self.conv_cycles + self.fc_cycles
        return self.total_macs / (modes.MMIE_NUM_PES * cyc) if cyc else 0.0

    def table4_row(self) -> Dict[str, float]:
        """The network's Table-4 row, straight off the plan."""
        return {
            "net": self.name,
            "conv_ms": self.conv_latency_s * 1e3,
            "fc_ms": self.fc_latency_s * 1e3,
            "conv_MA_MB": self.conv_ma_bytes / 1e6,
            "fc_MA_MB": self.fc_ma_bytes / 1e6,
            "conv_eff": self.conv_perf_efficiency,
            "fc_eff": self.fc_perf_efficiency,
        }

    def report(self) -> str:
        lines = ["kind,backend,mode(Wf,S),cycles,ma_words,macs,eff"]
        for p in self.plans:
            lines.append(
                f"{p.kind},{p.backend},({p.mode.w_f},{p.mode.s}),"
                f"{p.cycles},{p.ma_words},{p.macs},"
                f"{p.performance_efficiency:.3f}")
        return "\n".join(lines)


def _select_backend(op: OpSpec, cfg: EngineConfig) -> str:
    if cfg.policy == "auto":
        return auto_backend(op, cfg.backend)
    return cfg.backend


def plan_network(program: Program,
                 cfg: Optional[EngineConfig] = None) -> NetworkPlan:
    """Plan every op of `program` under `cfg` (no execution, no arrays).
    With `cfg.parallel` set, every plan also carries its per-op
    `ShardDecision` so the aggregate latencies price collectives."""
    cfg = current_config() if cfg is None else cfg
    return NetworkPlan(program.name, tuple(
        parlib.attach(op,
                      with_precision(plan_op(op, _select_backend(op, cfg)),
                                     op, cfg.precision),
                      cfg.parallel)
        for op in program.ops))


# ---------------------------------------------------------------------------
# compile -> CompiledNet
# ---------------------------------------------------------------------------

class CompiledNet:
    """A network compiled against one `EngineConfig`.

    .plan   — `NetworkPlan` over the program's op graph (Table-4 analytics).
    .cost   — the plan's aggregate Table-4 row (dict).
    .apply  — jitted executor: every engine op runs on its planned backend,
              in the captured order, under a `jax.named_scope` of its
              name (dispatch.run_op). Shape-specialized like any compiled
              artifact: executing with shapes that change the op sequence
              raises (recompile instead).
    .mesh   — the (data, model) device mesh `.apply` is `shard_map`ped
              over, or None for single-device execution. Inputs enter
              replicated; each op then follows its pinned `ShardDecision`
              (slice + backend + collective for sharded GEMMs, the plain
              backend call for replicated ops), so the body is one trace
              shared by all devices and replay stays strict.
    """

    def __init__(self, program: Program, config: EngineConfig,
                 plan: NetworkPlan,
                 exec_pairs: Optional[Tuple[Tuple[OpSpec, EnginePlan], ...]],
                 donate_argnums: Tuple[int, ...] = (),
                 mesh=None):
        self.program = program
        self.config = config
        self.plan = plan
        self.exec_pairs = exec_pairs
        self.mesh = mesh
        self._jitted = (None if program.fn is None
                        else jax.jit(self._run,
                                     donate_argnums=donate_argnums))

    def _replayed(self, *args):
        with using_config(self.config), api.replaying(self.exec_pairs):
            return self.program.fn(*args)

    def _run(self, *args):
        if self.mesh is None:
            return self._replayed(*args)
        from jax.sharding import PartitionSpec as P
        from repro.parallel.compat import shard_map_compat
        body = shard_map_compat(self._replayed, mesh=self.mesh,
                                in_specs=tuple(P() for _ in args),
                                out_specs=P())
        return body(*args)

    @property
    def cost(self) -> Dict[str, float]:
        return self.plan.table4_row()

    def apply(self, *args):
        if self._jitted is None:
            raise ValueError(
                f"program {self.program.name!r} carries no executable fn "
                "(analytic op tables only) — build it with trace_program or "
                "a model-side builder like cnn.program to execute")
        return self._jitted(*args)

    __call__ = apply

    def backends(self) -> Tuple[str, ...]:
        """Per-op backend assignment of the execution plan, in call order."""
        pairs = self.exec_pairs if self.exec_pairs is not None else ()
        return tuple(plan.backend for _, plan in pairs)

    def tiles(self) -> Tuple[Optional[Tuple[int, ...]], ...]:
        """Per-op tuned tile configs of the execution plan, in call order
        (None = kernel default / not a Pallas-tiled op)."""
        pairs = self.exec_pairs if self.exec_pairs is not None else ()
        return tuple(plan.tile_config for _, plan in pairs)

    def shards(self) -> Tuple[str, ...]:
        """Per-op shard strategies of the execution plan, in call order
        ("replicate" for every op of an unsharded net)."""
        pairs = self.exec_pairs if self.exec_pairs is not None else ()
        return tuple("replicate" if plan.shard is None
                     else plan.shard.strategy for _, plan in pairs)

    def precisions(self) -> Tuple[str, ...]:
        """Per-op execution precision, in call order — "fp32" for every op
        the int8 contract does not cover, whatever the config asked for."""
        pairs = self.exec_pairs if self.exec_pairs is not None else ()
        return tuple(plan.precision for _, plan in pairs)

    def lowerings(self) -> Tuple[str, ...]:
        """Per-op lowering, in call order: for an fp32 conv on the "xla" or
        "pallas" backend "fold" where its stride phases and taps fold into
        the contraction (`gfid.folds_taps`: `gfid.conv2d_gfid` on XLA, the
        Pallas wrapper `kernels.ops.gfid_conv2d` over `gfid.fold_operands`),
        "band" where the XLA lowering runs the band loop; for every other
        op its backend."""
        def lowering(op, plan) -> str:
            if op.kind == "conv2d" and plan.backend in ("xla", "pallas"):
                if plan.precision != "int8" and gfid.folds_taps(op.w_shape):
                    return "fold"
                if plan.backend == "xla":
                    return "band"
            return plan.backend

        pairs = self.exec_pairs if self.exec_pairs is not None else ()
        return tuple(lowering(op, plan) for op, plan in pairs)


def compile(program: Program,  # noqa: A001 (mirrors engine.compile API)
            cfg: Optional[EngineConfig] = None, *,
            donate_argnums: Tuple[int, ...] = (),
            mesh=None, verify: str = "off") -> CompiledNet:
    """Two-phase entry point: plan the whole network under `cfg`, return a
    `CompiledNet` with the analytic `NetworkPlan` and a jitted `.apply`.

    The analytic plan covers `program.ops` (which may follow the paper's
    layer counting, e.g. ResNet main-path booking). The execution plan is
    captured fresh from `program.fn` at the program's avals, so `.apply`
    always matches the real op sequence — including layers the paper's
    counting omits (projection shortcuts).

    Tile resolution happens here, per `cfg.tuning` (see engine/tune.py):
    every Pallas-bound op's tuned tile config is resolved at compile time
    and pinned into its exec pair — under `"autotune"` cache misses are
    benchmarked (and persisted) now, so `.apply` never pays tuning cost.

    `donate_argnums` is forwarded to `jax.jit` for `.apply`: a serving
    step that threads large mutable state (the paged KV pool) through the
    compiled net donates it instead of copying it every step.

    Multi-device: with `cfg.parallel` set, `.apply` is `shard_map`ped over
    a (data, model) mesh — `mesh` when given (e.g. one `data_groups`
    submesh from a serving replica), else a fresh
    `parallel.make_mesh(cfg.parallel)` — and every exec op carries its
    pinned `ShardDecision`. Passing `mesh` without `cfg.parallel` is an
    error: the mesh alone does not say how to split ops.

    `verify` gates the static contract verifier (`repro.analyze`) over
    the (program, cfg, donate_argnums) triple before anything is built:
    `"off"` (default) skips it entirely — zero overhead; `"warn"` emits
    one `AnalyzeWarning` per finding; `"error"` raises `AnalyzeError`
    when any error-severity contract violation is found.
    """
    cfg = current_config() if cfg is None else cfg
    if verify not in ("off", "warn", "error"):
        raise ValueError(f"verify must be 'off', 'warn' or 'error'; "
                         f"got {verify!r}")
    if verify != "off":
        # imported lazily: analyze depends on this module
        from repro.analyze import AnalyzeError, AnalyzeWarning, verify_program
        report = verify_program(program, cfg, donate_argnums=donate_argnums)
        if verify == "error" and not report.ok:
            raise AnalyzeError(report)
        for d in report:
            warnings.warn(f"{d}", AnalyzeWarning, stacklevel=2)
    pcfg = cfg.parallel
    if mesh is not None and pcfg is None:
        raise ValueError(
            "compile(mesh=...) needs cfg.parallel (a ParallelConfig) to "
            "decide per-op placements; a bare mesh says nothing about how "
            "to split ops")
    if pcfg is not None:
        if mesh is None and pcfg.devices > 1:
            mesh = parlib.make_mesh(pcfg)
        if mesh is not None:
            parlib.check_mesh(mesh, pcfg)
    net_plan = plan_network(program, cfg)
    exec_pairs = None
    if program.fn is not None:
        exec_ops, exec_precs = _capture_ops(program.fn, program.in_avals)
        # an op the forward left unnamed is named by kind and position
        exec_ops = tuple(op if op.name else dataclasses.replace(
            op, name=f"{op.kind}{i}") for i, op in enumerate(exec_ops))
        # shard decisions are pinned into the exec pairs only when a mesh
        # actually backs them: a sharded plan executes collectives, which
        # only exist inside the shard_mapped body
        exec_pcfg = pcfg if mesh is not None else None
        # precision pins before tile resolution so the tuner keys on it;
        # a per-op override baked into the forward (cnn.program
        # precisions=...) wins over the config's precision
        exec_pairs = tuple(
            (op, parlib.attach(
                op, tunelib.attach(
                    op, with_precision(plan_op(op, _select_backend(op, cfg)),
                                       op, prec or cfg.precision),
                    cfg, allow_autotune=True),
                exec_pcfg))
            for op, prec in zip(exec_ops, exec_precs))
    return CompiledNet(program, cfg, net_plan, exec_pairs,
                       donate_argnums=donate_argnums, mesh=mesh)
