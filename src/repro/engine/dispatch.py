"""Pluggable backend registry for the multi-mode engine.

Replaces the if/elif backend chains of the old `core.engine.MultiModeEngine`
with named, registrable backends. A backend implements the three op kinds of
the engine against a precomputed `EnginePlan`:

  * ``"xla"``    — pure-JAX GFID lowering (`core.gfid` shifted GEMMs); the
                   default everywhere.
  * ``"pallas"`` — `repro.kernels` Pallas TPU kernels (Mosaic on TPU;
                   interpreted only where JAX's default backend is the CPU).
  * ``"ref"``    — XLA's native conv / dot: the "direct engine" baseline the
                   paper compares the dataflow against.

Third parties register alternatives with `register_backend("mine", be)` and
select them per call (`engine.dense(..., backend="mine")`) or ambiently
(`with engine.using_backend("mine"):`).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gfid, quant
from repro.engine.plan import canonical_gemm
# the epilogue registry lives in a Pallas-free leaf module: importing the
# engine must not pull jax.experimental.pallas in for xla/ref-only users
from repro.kernels.epilogue import ACTS as EPILOGUE_ACTS
from repro.kernels.epilogue import dequant_epilogue


def apply_epilogue(out: jax.Array, bias: Optional[jax.Array],
                   act: Optional[str]) -> jax.Array:
    """The unfused reference epilogue: bias broadcast-added on the trailing
    axis, then the activation — what the XLA/ref backends (and any fallback
    path) run after the op, numerically identical to the Pallas kernels'
    in-accumulator epilogue for fp32."""
    if bias is not None:
        out = out + bias
    if act is not None:
        out = EPILOGUE_ACTS[act](out)
    return out


@dataclasses.dataclass(frozen=True)
class EngineBackend:
    """One execution strategy for the engine's three op kinds.

    Callables receive the already-computed `EnginePlan` so a backend can read
    the mode / MXU tiling — and, when `engine.tune` pinned one, the tuned
    `plan.tile_config` — instead of re-deriving them. `plan.precision`
    carries the resolved numeric precision: the built-in backends run the
    shared quantize→int32→dequant contract when it is "int8"; custom
    backends that never read it silently run fp32. `einsum` receives the
    literal spec plus its parsed `EinsumStructure`. `conv2d` and `einsum`
    accept the fused-epilogue kwargs (`bias=`, `act=`): the Pallas backend
    folds them into the kernel's fp32 accumulator, the XLA/ref backends
    apply them as ordinary post-ops via `apply_epilogue` (XLA fuses them
    under jit anyway); custom backends that ignore them via `**kw` silently
    drop the epilogue, so handle both kwargs when registering one.
    """

    name: str
    conv2d: Callable[..., jax.Array]
    conv1d_depthwise: Callable[..., jax.Array]
    einsum: Callable[..., jax.Array]
    # Serving paged-KV block gather (`engine.paged_gather`). Defaults to
    # None so backends registered before the op existed keep working:
    # dispatch falls back to the XLA `take` lowering (`xla_gather`).
    gather: Optional[Callable[..., jax.Array]] = None


_REGISTRY: Dict[str, EngineBackend] = {}  # analyze: allow[mutable-global] backend registry, write-once per name


def register_backend(backend: EngineBackend, *, overwrite: bool = False) -> None:
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered "
                         "(pass overwrite=True to replace)")
    _REGISTRY[backend.name] = backend


def get_backend(name: str) -> EngineBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown engine backend {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Execution chokepoint: kernel-fault hook + graceful degradation chain
# ---------------------------------------------------------------------------

# Degradation order on kernel failure: Pallas kernels fall back to the
# GFID XLA lowering, which falls back to XLA-native ops. Safe for results
# by construction: the three built-in backends are pinned bitwise equal on
# every covered op (kernel/int8/gather parity suites), so a hop down the
# chain changes where an op ran, never what it returned. Custom backends
# get no chain unless registered here.
DEGRADATION: Dict[str, Tuple[str, ...]] = {
    "pallas": ("xla", "ref"),
    "xla": ("ref",),
    "ref": (),
}


def fallback_chain(name: str) -> Tuple[str, ...]:
    return DEGRADATION.get(name, ())


def run_op(op, plan, call):
    """Execute one planned op through the kernel-fault chokepoint.

    `call(backend, plan)` performs the actual backend invocation; every
    engine entrypoint (api.py) routes through here. Three behaviors:

      * no injector installed and `EngineConfig.fallback == "none"` (the
        default): a direct tail call — zero overhead, no exception
        handling, byte-identical behavior to the pre-fault-layer engine;
      * an installed `serve.faults` injector may fire the "kernel" point
        for this (op kind, backend) visit, raising `KernelFault` exactly
        where a real lowering/execution failure would surface;
      * under ``fallback="chain"`` any backend exception (injected or
        real) sends the op down `DEGRADATION`, re-planned onto the
        fallback backend (tile config dropped — tuned tiles are
        backend-specific); each hop is recorded into every active
        `Ledger` (`ledger.fallbacks`) and onto the injector. Only when
        the whole chain failed does the last error propagate.

    Ops execute at trace time under jit, so both faults and fallbacks here
    are per-trace events: a compiled program degrades (or not) at compile
    time and then replays deterministically — a fallback can never flip
    between steps of a serving loop.
    """
    from repro.engine.config import current_config
    from repro.serve import faults as _faults

    inj = _faults.active()
    chained = current_config().fallback == "chain"
    if inj is None and not chained:
        return call(get_backend(plan.backend), plan)

    chain = (plan.backend,) + (fallback_chain(plan.backend) if chained
                               else ())
    last_err: Optional[Exception] = None
    for name in chain:
        pl = plan if name == plan.backend else dataclasses.replace(
            plan, backend=name, tile_config=None)
        try:
            if inj is not None and inj.fire("kernel",
                                            site=f"{op.kind}:{name}"):
                raise _faults.KernelFault(
                    f"injected kernel fault: {op.kind} on backend {name!r}")
            out = call(get_backend(name), pl)
        except Exception as e:      # the chain IS the handler
            if not chained:
                raise
            last_err = e
            continue
        if name != plan.backend:
            from repro.engine import ledger as _ledger
            _ledger.record_fallback(_ledger.FallbackRecord(
                op.kind, plan.backend, name, str(last_err)))
            if inj is not None:
                inj.note_fallback(op.kind, plan.backend, name)
        return out
    assert last_err is not None
    raise last_err


# ---------------------------------------------------------------------------
# int8 quantized lowerings shared by the non-Pallas backends
# ---------------------------------------------------------------------------

def _wants_int8(plan) -> bool:
    return getattr(plan, "precision", "fp32") == "int8"


def _quant_conv2d(conv_i32, x, w, *, stride, pad, groups, bias, act):
    """Quantize (shared rule), run an exact-int32 conv lowering, dequant
    through the pinned epilogue chain. `conv_i32` is either the GFID
    shifted-GEMM (`gfid.conv2d_gfid_int8`) or XLA's native int8 conv
    (`gfid.conv2d_reference_int8`) — both exact, hence bitwise equal."""
    xq, wq, sx, sw = quant.quantize_conv_operands(x, w)
    acc = conv_i32(xq, wq, stride, pad, groups)
    out = dequant_epilogue(acc, sx * sw, bias, act)
    return out.astype(x.dtype)


def _quant_canonical_einsum(x, w, structure, *, bias, act):
    """Quantized lowering of a canonical (M, K) @ (K, N) contraction: the
    same canonicalization as the Pallas path, the shared quantization rule,
    the exact int32 GEMM, and the pinned dequant epilogue."""
    c = structure.contract[0]
    xm = jnp.moveaxis(x, structure.x_labels.index(c), -1)
    w2 = w if structure.w_labels[0] == c else w.T
    xq, wq, sx, sw = quant.quantize_matmul_operands(xm, w2)
    acc = quant.int8_matmul_i32(xq, wq)
    out = dequant_epilogue(acc, sx * sw, bias, act)
    # canonical => out_labels == x_free + w_free, which is exactly the
    # (lead..., N) layout the contraction produced: no transpose needed
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# "xla" — pure-JAX GFID shifted-GEMM lowering
# ---------------------------------------------------------------------------

def _xla_conv2d(x, w, plan, *, stride, pad, groups, accum_dtype, interpret,
                bias=None, act=None):
    if _wants_int8(plan):
        return _quant_conv2d(gfid.conv2d_gfid_int8, x, w, stride=stride,
                             pad=pad, groups=groups, bias=bias, act=act)
    out = gfid.conv2d_gfid(x, w, stride, pad, groups,
                           accum_dtype=accum_dtype or jnp.float32)
    return apply_epilogue(out, bias, act)


def _xla_conv1d_dw(x, w, plan, *, causal, interpret):
    return gfid.conv1d_depthwise_gfid(x, w, causal=causal)


def _xla_einsum(spec, x, w, plan, structure, *, accum_dtype, interpret,
                bias=None, act=None):
    if _wants_int8(plan) and canonical_gemm(structure, w.ndim):
        return _quant_canonical_einsum(x, w, structure, bias=bias, act=act)
    if accum_dtype is not None:
        out = jnp.einsum(spec, x, w, preferred_element_type=accum_dtype)
    else:
        out = jnp.einsum(spec, x, w)
    return apply_epilogue(out, bias, act)


def xla_gather(pool, table, plan, *, interpret):
    """Reference paged-KV gather: pool (num_blocks, block_size, *feature)
    indexed by table (B, blocks_per_req) int32 -> (B, blocks_per_req *
    block_size, *feature) — a bitwise-exact block copy (`jnp.take`), the
    parity baseline for the Pallas kernel and the fallback for backends
    registered without a `gather` entry."""
    b, blocks_per_req = table.shape
    out = jnp.take(pool, table, axis=0)
    return out.reshape((b, blocks_per_req * pool.shape[1]) + pool.shape[2:])


def gather_impl(backend: "EngineBackend") -> Callable[..., jax.Array]:
    """The backend's paged-gather entry, or the XLA fallback."""
    return backend.gather if backend.gather is not None else xla_gather


# ---------------------------------------------------------------------------
# "ref" — XLA-native direct ops (the paper's comparison baseline)
# ---------------------------------------------------------------------------

def _ref_conv2d(x, w, plan, *, stride, pad, groups, accum_dtype, interpret,
                bias=None, act=None):
    if _wants_int8(plan):
        return _quant_conv2d(gfid.conv2d_reference_int8, x, w, stride=stride,
                             pad=pad, groups=groups, bias=bias, act=act)
    out = gfid.conv2d_reference(x, w, stride, pad, groups)
    return apply_epilogue(out, bias, act)


def _ref_conv1d_dw(x, w, plan, *, causal, interpret):
    return gfid.conv1d_depthwise_xla(x, w, causal=causal)


# ---------------------------------------------------------------------------
# "pallas" — repro.kernels TPU kernels
# ---------------------------------------------------------------------------

def _pallas_conv2d(x, w, plan, *, stride, pad, groups, accum_dtype, interpret,
                   bias=None, act=None):
    from repro.kernels import ops
    return ops.gfid_conv2d(x, w, stride=stride, pad=pad, groups=groups,
                           tile=plan.tile_config, bias=bias, act=act,
                           interpret=interpret,
                           precision=getattr(plan, "precision", "fp32"))


def _pallas_conv1d_dw(x, w, plan, *, causal, interpret):
    from repro.kernels import ops
    return ops.gfid_conv1d_depthwise(x, w, causal=causal, interpret=interpret)


def _pallas_einsum(spec, x, w, plan, structure, *, accum_dtype, interpret,
                   bias=None, act=None):
    """Canonicalize to (M, K) @ (K, N) for the blocked-GEMM kernel when the
    contraction allows it; batched-weight specs (stacked experts) fall back
    to the XLA lowering — the MoE grouped GEMM kernel is future work. The
    fused epilogue rides the kernel on the canonical path and falls back to
    `apply_epilogue` with it."""
    st = structure
    if not canonical_gemm(st, w.ndim):
        return _xla_einsum(spec, x, w, plan, st, accum_dtype=accum_dtype,
                           interpret=interpret, bias=bias, act=act)
    from repro.kernels import ops
    c = st.contract[0]
    xm = jnp.moveaxis(x, st.x_labels.index(c), -1)
    w2 = w if st.w_labels[0] == c else w.T
    return ops.gfid_matmul(xm, w2, tile=plan.tile_config, bias=bias, act=act,
                           interpret=interpret,
                           precision=getattr(plan, "precision", "fp32"))


def _pallas_gather(pool, table, plan, *, interpret):
    from repro.kernels import ops
    return ops.paged_gather(pool, table, interpret=interpret)


register_backend(EngineBackend("xla", _xla_conv2d, _xla_conv1d_dw,
                               _xla_einsum, gather=xla_gather))
register_backend(EngineBackend("ref", _ref_conv2d, _ref_conv1d_dw,
                               _xla_einsum, gather=xla_gather))
register_backend(EngineBackend("pallas", _pallas_conv2d, _pallas_conv1d_dw,
                               _pallas_einsum, gather=_pallas_gather))
