"""Functional entrypoints of the multi-mode engine.

One call surface for every dense op in the repo (the paper's "conv and FC
on the same PEs" contract):

    y = engine.conv2d(x, w, stride=2, pad=3)          # conv modes
    y = engine.conv1d_depthwise(x, taps)              # 1-D short-conv mode
    y = engine.dense(x, w)                            # FC mode, (…,n)@(n,m)
    y = engine.einsum("ecd,edf->ecf", x, w)           # FC mode, general

Every call builds the op's `OpSpec` from its static shapes, computes the
pure `EnginePlan` (cached), records it into any active `tracking()` ledger,
and dispatches to the selected backend from the registry. Resolution order
for the backend: the explicit ``backend=`` argument, then the plan of an
executing `CompiledNet` (program replay), then the ambient
`EngineConfig` (`using_config` / `using_backend` context or the process
default — see `engine/config.py`); `interpret` and the accumulation policy
resolve explicit-argument-first against the same config. The numeric
precision resolves the same way: an explicit ``precision=`` argument wins
(validated hard — ``"int8"`` on an op outside the int8 contract raises),
then a replayed plan's pinned `plan.precision`, then the ambient config's
`precision` (silently downgraded to fp32 for unsupported ops).

Numerics: `accum_dtype=None` (the default for `einsum`) reproduces a plain
`jnp.einsum` / `@` — same dot_general, same output dtype — so migrating a
model onto the engine is bit-identical. `dense` defaults to fp32
accumulation (`preferred_element_type=jnp.float32`), the convention of
every parameter GEMM in `repro.models`. `out_dtype` casts the result when
given (`matmul` always casts back to `x.dtype`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.engine import dispatch, ledger as ledger_mod, plan as planlib
from repro.engine import tune as tunelib
from repro.engine.config import (  # noqa: F401 (re-exported compat surface)
    EngineConfig, current_config, default_backend, set_default_config,
    using_backend, using_config)


class _Unset:
    def __repr__(self) -> str:      # keeps signatures readable in help()
        return "<per-op default>"


_UNSET = _Unset()

_ACCUM_DEFAULTS = {"conv2d": jnp.float32, "dense": jnp.float32,
                   "einsum": None}


def _resolve_accum(arg, op_kind: str):
    if not isinstance(arg, _Unset):
        return arg                      # explicit argument wins (None = native)
    accum = current_config().accum
    if accum is None:
        return _ACCUM_DEFAULTS[op_kind]
    if accum == "native":
        return None
    return jnp.dtype(accum)


# ---------------------------------------------------------------------------
# Program capture & replay (used by engine/program.py)
# ---------------------------------------------------------------------------

class _ProgramState(threading.local):
    def __init__(self) -> None:
        # each capture frame is (ops_list, precisions_list_or_None)
        self.capture: List[Tuple[List[planlib.OpSpec],
                                 Optional[List[Optional[str]]]]] = []
        self.replay: List["_Cursor"] = []


class _Cursor:
    """Mutable position over a compiled (OpSpec, EnginePlan) sequence."""

    def __init__(self, pairs: Sequence[Tuple[planlib.OpSpec,
                                             planlib.EnginePlan]]):
        self.pairs = tuple(pairs)
        self.index = 0

    def next_for(self, op: planlib.OpSpec,
                 ) -> Tuple[planlib.OpSpec, planlib.EnginePlan]:
        """The compiled (op, plan) pair `op` executes: the compiled op is
        equal to `op` and carries the program's name for it."""
        if self.index >= len(self.pairs):
            raise RuntimeError(
                f"compiled program expected {len(self.pairs)} engine ops but "
                f"a further {op.kind} op was issued — the executed function "
                "diverged from its captured op sequence (did the input "
                "shapes change since compile()?)")
        want, plan = self.pairs[self.index]
        if want != op:
            raise RuntimeError(
                f"compiled program op {self.index} mismatch: planned "
                f"{want.kind}{want.x_shape}x{want.w_shape}, executing "
                f"{op.kind}{op.x_shape}x{op.w_shape} — recompile for these "
                "input shapes")
        self.index += 1
        return want, plan


_PROG = _ProgramState()


@contextlib.contextmanager
def capturing(into: List[planlib.OpSpec],
              precisions_into: Optional[List[Optional[str]]] = None,
              ) -> Iterator[List[planlib.OpSpec]]:
    """Record the `OpSpec` of every engine call in the block, in call order
    (ledgers are paused: a capture is a dry shape-trace, not a run).

    `precisions_into`, when given, receives one entry per op: the call's
    *explicit* ``precision=`` argument, or None when the op left precision
    to the ambient config — `engine.compile` uses this to honor per-op
    precision overrides baked into a program's forward (e.g.
    ``models.cnn.program(..., precisions={"fc6": "int8"})``)."""
    _PROG.capture.append((into, precisions_into))
    try:
        with ledger_mod.paused():
            yield into
    finally:
        _PROG.capture.pop()     # LIFO: by position, not by (==) value


@contextlib.contextmanager
def replaying(pairs: Sequence[Tuple[planlib.OpSpec, planlib.EnginePlan]],
              ) -> Iterator[_Cursor]:
    """Execute the block against a compiled plan sequence: each engine call
    consumes the next (OpSpec, EnginePlan) pair and runs on the plan's
    backend. Divergence from the captured sequence raises."""
    cur = _Cursor(pairs)
    _PROG.replay.append(cur)
    try:
        yield cur
    finally:
        _PROG.replay.pop()
    if cur.index != len(cur.pairs):
        raise RuntimeError(
            f"compiled program executed {cur.index} of {len(cur.pairs)} "
            "planned engine ops — the function diverged from its captured "
            "op sequence")


def _plan_for(op: planlib.OpSpec, backend_arg: Optional[str],
              ) -> Tuple[planlib.OpSpec, planlib.EnginePlan]:
    """Capture/replay hook + plan resolution for one issued op. Returns
    the op (under replay the compiled one, which carries the program's
    name for it) and its plan."""
    for ops, precs in _PROG.capture:
        ops.append(op)
        if precs is not None:
            precs.append(None)      # _pin_precision backfills explicit args
    if _PROG.replay:
        op, plan = _PROG.replay[-1].next_for(op)
        if backend_arg is None:
            return op, plan
        dispatch.get_backend(backend_arg)          # explicit arg still wins
        return op, planlib.plan_op(op, backend_arg)
    if backend_arg is not None:
        name = backend_arg
    else:
        cfg = current_config()
        name = (planlib.auto_backend(op, cfg.backend)
                if cfg.policy == "auto" else cfg.backend)
    dispatch.get_backend(name)          # validate before caching a plan
    return op, planlib.plan_op(op, name)


def _interp(interpret: Optional[bool]) -> Optional[bool]:
    return current_config().interpret if interpret is None else interpret


def _pin_precision(op: planlib.OpSpec, plan: planlib.EnginePlan,
                   arg: Optional[str]) -> planlib.EnginePlan:
    """Resolve the op's numeric precision and pin it onto the plan.

    Resolution mirrors the backend argument: an explicit ``precision=``
    wins — validated hard, even during program replay — then a replayed
    plan's pinned `plan.precision`, then the ambient config's `precision`
    (silently downgraded to fp32 for ops the int8 contract does not cover).
    Runs *before* tile resolution so the tuner keys on the precision.
    """
    if arg is not None:
        if arg not in planlib.PRECISIONS:
            raise ValueError(f"unknown precision {arg!r}; expected one of "
                             f"{planlib.PRECISIONS}")
        if arg == "int8" and not planlib.supports_int8(op):
            raise ValueError(
                f"precision='int8' requested for {op.kind} "
                f"{op.x_shape}x{op.w_shape}, but the int8 contract only "
                "covers conv2d and canonical-GEMM dense ops")
        prec = arg
        # surface the explicit override to any active capture, so a
        # compiled program's exec pairs pin it (not just this eager call)
        for _, precs in _PROG.capture:
            if precs:
                precs[-1] = arg
    elif _PROG.replay:
        prec = plan.precision           # pinned by engine.compile
    else:
        cfg = current_config()
        prec = (cfg.precision if cfg.precision == "fp32"
                or planlib.supports_int8(op) else "fp32")
    if plan.precision != prec:
        plan = dataclasses.replace(plan, precision=prec)
    return plan


def _maybe_tile(op: planlib.OpSpec,
                plan: planlib.EnginePlan) -> planlib.EnginePlan:
    """Eager-path tile resolution: pin a *cached* tuned tile under
    `cfg.tuning != "off"`. Replayed plans (a `CompiledNet` executing) are
    returned untouched — whatever `engine.compile` pinned (including a
    deliberate None on a cache miss) IS the execution contract; re-resolving
    here would let a cache written after compile change a compiled net's
    K-blocking (and so its accumulation order) at first-apply time.
    Autotuning itself only ever happens at compile time, never per call."""
    if _PROG.replay:
        return plan
    cfg = current_config()
    if cfg.tuning == "off" or plan.backend != "pallas":
        return plan
    return tunelib.attach(op, plan, cfg)


def _check_epilogue(bias: Optional[jax.Array], act: Optional[str],
                    n_out: int, what: str) -> None:
    if act is not None and act not in dispatch.EPILOGUE_ACTS:
        raise ValueError(
            f"unknown epilogue activation {act!r} for {what}; expected one "
            f"of {sorted(dispatch.EPILOGUE_ACTS)}")
    if bias is not None and tuple(bias.shape) != (n_out,):
        raise ValueError(
            f"epilogue bias for {what} must have shape ({n_out},) — one "
            f"entry per output feature; got {tuple(bias.shape)}")


def _row_pad_amount(structure: planlib.EinsumStructure,
                    x_shape: Tuple[int, ...]) -> int:
    """Rows to zero-pad onto x's leading axis under `cfg.row_align`.

    Padding applies only when the leading x axis is a pure batch-row dim (an
    x-free label, so rows are independent and the output can be sliced
    back). XLA lowers the contraction's free dims to the GEMM M dimension;
    pinning M to a multiple of R keeps the per-row accumulation kernel
    independent of the batch size, which is what makes scheduler-batched
    execution bitwise identical to batch-1 execution (see
    `EngineConfig.row_align`).
    """
    align = current_config().row_align
    if not align or not x_shape or x_shape[0] == 0:
        return 0
    if structure.x_labels[0] not in structure.x_free:
        return 0                        # leading dim is contract/batch-label
    return -x_shape[0] % align


# ---------------------------------------------------------------------------
# Ops
# ---------------------------------------------------------------------------

def conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1, pad: int = 0,
           groups: int = 1, bias: Optional[jax.Array] = None,
           act: Optional[str] = None, backend: Optional[str] = None,
           accum_dtype=_UNSET, precision: Optional[str] = None,
           interpret: Optional[bool] = None, name: str = "") -> jax.Array:
    """Conv mode. x: (B,H,W,C_in) NHWC; w: (H_f,W_f,C_in/g,C_out) HWIO.
    Returns (B,H_out,W_out,C_out) in x.dtype.

    `bias` ((C_out,)) and `act` ("relu" | "gelu") form the op's fused
    epilogue: conv+bias+activation is one kernel launch on the Pallas
    backend (applied in the fp32 accumulator before writeback) and ordinary
    fused post-ops elsewhere. On the int8 path (`precision="int8"` here or
    on the config) dequant+bias+act fuse into the same writeback, so the
    quantized conv is still one launch; `accum_dtype` is then ignored (the
    int8 contract pins an exact int32 accumulator).

    `name` labels the op (`OpSpec.name`): a compiled program's device ops
    carry it as their `jax.named_scope` (dispatch.run_op)."""
    op = planlib.OpSpec("conv2d", tuple(map(int, x.shape)),
                        tuple(map(int, w.shape)), stride=int(stride),
                        pad=int(pad), groups=int(groups), name=name)
    _check_epilogue(bias, act, op.w_shape[3], "conv2d")
    op, plan = _plan_for(op, backend)
    plan = _pin_precision(op, plan, precision)
    plan = _maybe_tile(op, plan)
    ledger_mod.record(plan)
    out = dispatch.run_op(op, plan, lambda be, pl: be.conv2d(
        x, w, pl, stride=stride, pad=pad, groups=groups,
        accum_dtype=_resolve_accum(accum_dtype, "conv2d"),
        interpret=_interp(interpret), bias=bias, act=act))
    return out.astype(x.dtype)


def conv1d_depthwise(x: jax.Array, w: jax.Array, *, causal: bool = True,
                     backend: Optional[str] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """1-D depthwise mode (Mamba/xLSTM short conv). x: (B,L,D); w: (W_f,D)."""
    op = planlib.OpSpec("conv1d_dw", tuple(map(int, x.shape)),
                        tuple(map(int, w.shape)), causal=bool(causal))
    op, plan = _plan_for(op, backend)
    ledger_mod.record(plan)
    out = dispatch.run_op(op, plan, lambda be, pl: be.conv1d_depthwise(
        x, w, pl, causal=causal, interpret=_interp(interpret)))
    return out.astype(x.dtype)


def einsum(spec: str, x: jax.Array, w: jax.Array, *,
           bias: Optional[jax.Array] = None, act: Optional[str] = None,
           backend: Optional[str] = None, accum_dtype=_UNSET,
           out_dtype=None, precision: Optional[str] = None,
           interpret: Optional[bool] = None, name: str = "") -> jax.Array:
    """FC mode for any two-operand dense contraction (weights second).

    `bias` ((n_out,), one entry per trailing output feature) and `act`
    ("relu" | "gelu") form the fused epilogue (in-kernel on the Pallas
    GEMM's canonical path, post-ops elsewhere); the trailing output label
    must be a weight-side (w-free) dim for a bias to be well-defined.
    `name` labels the op, as in `conv2d`."""
    op = planlib.OpSpec("dense", tuple(map(int, x.shape)),
                        tuple(map(int, w.shape)), spec=spec, name=name)
    structure = planlib.parse_einsum(spec, x.ndim, w.ndim)
    if bias is not None:
        # a per-feature bias needs a weight-side trailing output dim; a
        # bare activation is elementwise and valid on any output layout
        if not structure.out_labels \
                or structure.out_labels[-1] not in structure.w_free:
            raise ValueError(
                f"epilogue bias on einsum {spec!r}: the trailing output "
                "label must be a weight-only (w-free) dim to carry a "
                "per-feature bias")
        lab = structure.out_labels[-1]
        n_out = op.w_shape[structure.w_labels.index(lab)]
        _check_epilogue(bias, act, n_out, f"einsum {spec!r}")
    elif act is not None:
        _check_epilogue(None, act, 0, f"einsum {spec!r}")
    op, plan = _plan_for(op, backend)
    plan = _pin_precision(op, plan, precision)
    plan = _maybe_tile(op, plan)
    ledger_mod.record(plan)
    pad = _row_pad_amount(structure, op.x_shape)
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    if plan.shard is not None and plan.shard.collective != "none":
        # a sharded plan only ever arrives via replay inside a
        # shard_mapped CompiledNet.apply (engine.compile pins decisions
        # exclusively when a mesh backs them), so the collective axis is
        # in scope here; the fallback chain preserves `pl.shard`, so a
        # degraded hop still runs the same collective
        from repro.engine import parallel as _parlib
        out = dispatch.run_op(op, plan, lambda be, pl: _parlib.sharded_einsum(
            be, spec, x, w, pl, structure,
            accum_dtype=_resolve_accum(accum_dtype, "einsum"),
            interpret=_interp(interpret), bias=bias, act=act))
    else:
        out = dispatch.run_op(op, plan, lambda be, pl: be.einsum(
            spec, x, w, pl, structure,
            accum_dtype=_resolve_accum(accum_dtype, "einsum"),
            interpret=_interp(interpret), bias=bias, act=act))
    if pad:
        ax = structure.out_labels.index(structure.x_labels[0])
        out = jax.lax.slice_in_dim(out, 0, op.x_shape[0], axis=ax)
    return out if out_dtype is None else out.astype(out_dtype)


def dense(x: jax.Array, w: jax.Array, *, bias: Optional[jax.Array] = None,
          act: Optional[str] = None, backend: Optional[str] = None,
          accum_dtype=_UNSET, out_dtype=None,
          precision: Optional[str] = None,
          interpret: Optional[bool] = None, name: str = "") -> jax.Array:
    """FC mode (W_f = 1): x (..., n) @ w (n, m) -> (..., m), with an
    optional fused bias ((m,)) / activation epilogue."""
    if isinstance(accum_dtype, _Unset):
        accum_dtype = _resolve_accum(accum_dtype, "dense")
    return einsum(planlib.dense_spec(x.ndim), x, w, bias=bias, act=act,
                  backend=backend, accum_dtype=accum_dtype,
                  out_dtype=out_dtype, precision=precision,
                  interpret=interpret, name=name)


def proj(x: jax.Array, w: jax.Array, *, backend: Optional[str] = None,
         precision: Optional[str] = None,
         interpret: Optional[bool] = None) -> jax.Array:
    """FC-mode parameter GEMM with plain-`@` numerics (`accum_dtype=None`:
    same dot_general, same output dtype) — the drop-in replacement for
    `x @ w` on model parameter paths. An explicit `precision="int8"` (or
    an ambient int8 config) trades the plain-`@` guarantee for the
    quantized contract, like any other FC-mode op."""
    return dense(x, w, backend=backend, accum_dtype=None,
                 precision=precision, interpret=interpret)


def paged_gather(pool: jax.Array, table: jax.Array, *,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Paged-KV block gather (serving memory move).

    pool:  (num_blocks, block_size, *feature) — a `serve.kv_pool` block
           pool array.
    table: (B, blocks_per_req) int32 — per-request block ids.
    Returns (B, blocks_per_req * block_size, *feature): each request's
    dense cache view reconstructed from its blocks.

    Routed through the engine like any dense op so compiled serving
    programs stay honest about reconstruction cost: the op records a
    zero-MAC "gather" plan (cycles priced as a pure memory move), is
    captured into `Program` graphs, and dispatches per backend — the
    Pallas scalar-prefetch kernel (`kernels.paged`) or the XLA `take`
    reference, bitwise identical by the kernel parity test.
    """
    op = planlib.OpSpec("gather", tuple(map(int, pool.shape)),
                        tuple(map(int, table.shape)))
    op, plan = _plan_for(op, backend)
    ledger_mod.record(plan)
    return dispatch.run_op(op, plan, lambda be, pl: dispatch.gather_impl(be)(
        pool, table, pl, interpret=_interp(interpret)))


# `matmul`: fp32 accumulation, result cast back to the input dtype (the fused
# epilogue, when given, runs before the cast — i.e. in fp32).
def matmul(x: jax.Array, w: jax.Array, *, bias: Optional[jax.Array] = None,
           act: Optional[str] = None, backend: Optional[str] = None,
           precision: Optional[str] = None,
           interpret: Optional[bool] = None, name: str = "") -> jax.Array:
    return dense(x, w, bias=bias, act=act, backend=backend,
                 accum_dtype=jnp.float32, out_dtype=x.dtype,
                 precision=precision, interpret=interpret, name=name)
