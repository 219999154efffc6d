"""Plan-driven multi-device parallelism for the engine.

The MMIE argument — keep every PE busy by reshaping the dataflow per layer
— lifted from PEs to devices: each op of a compiled network gets its own
placement over the mesh's tensor-parallel ("model") axis, chosen by the
same analytic-plan machinery that already picks pallas-vs-xla per layer.

  * `ParallelConfig` — the frozen parallelism policy carried by
    `EngineConfig.parallel`: mesh extent (`data` x `model`) plus the
    per-layer strategy policy ("auto" | "replicate" | "shard_k" |
    "shard_n").
  * `decide(op, base, pcfg)` — the per-op policy: canonical 2-D GEMMs may
    split their contraction (shard-K, all-reduce) or output-column
    (shard-N, all-gather) dim across the model axis; everything else
    replicates. Under "auto" the candidate with the smallest analytic
    latency wins — compute cycles / ways on the FC clock plus ring
    collective words on the (slow, `modes.MMIE_LINK_WORDS_PER_CYCLE`)
    inter-chip link — mirroring how `plan.auto_backend` compares kernels.
  * `sharded_einsum(...)` — the execution of a non-replicated decision
    inside a `shard_map`ped `CompiledNet.apply`: slice the local operand
    by `jax.lax.axis_index`, run the op's planned backend on the slice,
    combine with the decision's collective.

Numerics contract: shard-N is *bitwise identical* to single-device
execution — each output column is produced by exactly one device running
the same full-K accumulation (the Pallas kernel's K-blocking is pinned by
`tile_config` before the N split, so even its in-kernel accumulation order
is unchanged), and the all-gather only concatenates. shard-K sums fp32
partials across devices, which is NOT bitwise against a single-device
full-K accumulation (float addition is non-associative), so the default
policy (`exact_only=True`) never auto-selects it; it is available by
explicit policy for throughput work that tolerates ~1e-5 relative error
(tested to allclose, the documented carve-out mirroring the continuous
scheduler's preemption carve-out).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax

from repro.core import modes
from repro.engine import plan as planlib
from repro.engine.plan import EnginePlan, OpSpec, ShardDecision

_POLICIES = ("auto", "replicate", "shard_k", "shard_n")


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Frozen mesh/parallelism policy (hashable; jit-static friendly).

    data       — data-parallel mesh extent: independent replicas the
                 serving schedulers spread (program, bucket) batches
                 across (`serve.scheduler`). Each replica sees its own
                 (1, model) submesh.
    model      — tensor-parallel extent: devices one `CompiledNet.apply`
                 spreads a single op across (the axis `decide` splits).
    policy     — per-op strategy selection: "auto" prices replicate /
                 shard_k / shard_n per op from the analytic plan and picks
                 the cheapest; a strategy name forces it for every op that
                 can legally run it (falling back to replicate otherwise).
    exact_only — keep the bitwise parity contract: "auto" never picks
                 shard_k (all-reduced fp32 partial sums are not bitwise
                 against single-device accumulation). An explicit
                 policy="shard_k" overrides this knob — forcing the
                 strategy IS the opt-out.
    """

    data: int = 1
    model: int = 1
    policy: str = "auto"
    exact_only: bool = True

    def __post_init__(self) -> None:
        if self.policy not in _POLICIES:
            raise ValueError(f"unknown parallel policy {self.policy!r}; "
                             f"expected one of {_POLICIES}")
        for name in ("data", "model"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")

    @property
    def devices(self) -> int:
        return self.data * self.model


# ---------------------------------------------------------------------------
# mesh plumbing
# ---------------------------------------------------------------------------

def device_mesh(shape, axes):
    """A `Mesh` of `shape` named `axes` over the first prod(shape) local
    devices. Its axes are Auto: the sharding rules place activations with
    `with_sharding_constraint`, which may only name Auto axes (on jax 0.9
    `jax.make_mesh` makes Explicit ones unless told otherwise)."""
    import numpy as np
    shape, devs = tuple(int(v) for v in shape), jax.devices()
    if len(devs) < math.prod(shape):
        raise ValueError(
            f"a {dict(zip(axes, shape))} mesh needs {math.prod(shape)} "
            f"devices but only {len(devs)} exist (force host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "first jax use, or shrink the mesh)")
    arr = np.asarray(devs[:math.prod(shape)]).reshape(shape)
    return jax.sharding.Mesh(arr, tuple(axes))


def make_mesh(pcfg: ParallelConfig):
    """A (data, model) `Mesh` over the first `pcfg.devices` local devices."""
    return device_mesh((pcfg.data, pcfg.model), ("data", "model"))


def check_mesh(mesh, pcfg: ParallelConfig) -> None:
    """Validate that `mesh` can execute plans decided under `pcfg`: it must
    carry a "model" axis of exactly `pcfg.model` devices (shard decisions
    bake the ways into slice sizes). Any data extent is fine — a compiled
    net simply replicates over it."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if shape.get("model", 1) != pcfg.model:
        raise ValueError(
            f"mesh model axis is {shape.get('model', 1)}-way but the config "
            f"plans model={pcfg.model}-way sharding; meshes and "
            "ParallelConfigs must agree (see engine.parallel.make_mesh)")


def data_groups(mesh) -> Tuple[object, ...]:
    """Split a (data, model) mesh into per-data-slice (1, model) submeshes —
    one independent tensor-parallel group per serving replica. Axis names
    are preserved, so a `CompiledNet` compiled against a group runs the
    same "model"-axis collectives as on the full mesh."""
    names = mesh.axis_names
    if "data" not in names:
        return (mesh,)
    d_ax = names.index("data")
    devs = mesh.devices
    groups = []
    for i in range(devs.shape[d_ax]):
        sub = devs.take(indices=[i], axis=d_ax)
        groups.append(jax.sharding.Mesh(sub, names))
    return tuple(groups)


# ---------------------------------------------------------------------------
# the per-op placement policy
# ---------------------------------------------------------------------------

def _gemm_dims(op: OpSpec):
    """(structure, M, K, N) of a canonical-GEMM dense op, else None."""
    if op.kind != "dense":
        return None
    st = planlib.parse_einsum(op.spec, len(op.x_shape), len(op.w_shape))
    if not planlib.canonical_gemm(st, len(op.w_shape)):
        return None
    dims = dict(zip(st.x_labels, op.x_shape))
    dims.update(zip(st.w_labels, op.w_shape))
    k = int(dims[st.contract[0]])
    n = int(dims[st.w_free[0]])
    m = int(math.prod(dims[l] for l in st.x_free))
    return st, m, k, n


def _latency_s(cycles: int, sd: ShardDecision) -> float:
    """Analytic seconds of one dense op under `sd`: per-device compute on
    the FC clock plus ring-collective wire time on the link clock."""
    comp = cycles if sd.strategy == "replicate" or sd.ways <= 1 \
        else -(-cycles // sd.ways)
    return comp / modes.MMIE_FC_FREQ_HZ \
        + sd.collective_cycles / modes.MMIE_CONV_FREQ_HZ


def decide(op: OpSpec, base: EnginePlan,
           pcfg: ParallelConfig) -> ShardDecision:
    """The per-op sharding decision for `op` under `pcfg`.

    Only canonical 2-D GEMMs (`plan.canonical_gemm`) are splittable — the
    same predicate that gates the Pallas kernel, because both need the op
    to BE one (M, K) @ (K, N). A strategy is a candidate only when the
    split dim divides evenly by `model` (a ragged split would change local
    GEMM shapes per device and break the fixed-tile batch-invariance
    contract). Convs, depthwise convs, gathers and non-canonical einsums
    replicate: every device runs the full op, bitwise identical by
    construction.
    """
    ways = pcfg.model
    if ways <= 1:
        return ShardDecision("replicate", ways)
    gemm = _gemm_dims(op)
    if gemm is None:
        return ShardDecision("replicate", ways)
    _, m, k, n = gemm
    words = m * n                       # global output words the combine moves
    cand = {"replicate": ShardDecision("replicate", ways)}
    if n and n % ways == 0:
        cand["shard_n"] = ShardDecision("shard_n", ways, words=words)
    if k and k % ways == 0:
        cand["shard_k"] = ShardDecision("shard_k", ways, words=words)
    if pcfg.policy != "auto":
        return cand.get(pcfg.policy, cand["replicate"])
    if pcfg.exact_only:
        cand.pop("shard_k", None)       # inexact: never auto-selected
    order = ("replicate", "shard_n", "shard_k")     # tie-break: exact first
    return min(cand.values(),
               key=lambda sd: (_latency_s(base.cycles, sd),
                               order.index(sd.strategy)))


def attach(op: OpSpec, plan: EnginePlan,
           pcfg: Optional[ParallelConfig]) -> EnginePlan:
    """Pin the op's shard decision into its plan (a `dataclasses.replace`
    of the pure analytic plan, exactly like `tune.attach` pins tiles)."""
    if pcfg is None:
        return plan
    return dataclasses.replace(plan, shard=decide(op, plan, pcfg))


# ---------------------------------------------------------------------------
# sharded execution (inside a shard_mapped CompiledNet.apply)
# ---------------------------------------------------------------------------

def sharded_einsum(be, spec: str, x, w, plan: EnginePlan, structure, *,
                   accum_dtype, interpret, bias, act):
    """Execute a non-replicated dense plan inside `shard_map`.

    shard_n: slice w (and bias) to this device's N columns, run the op's
    planned backend on the slice, all-gather the column blocks back in
    mesh order — a pure concatenation, bitwise identical to the unsharded
    op. shard_k: slice x and w to this device's K range, run the backend
    *without* the epilogue, all-reduce the partial sums, then apply
    bias/act once on the combined result (the epilogue must see the full
    sum, and an in-kernel fused epilogue would apply it per partial).
    """
    sd = plan.shard
    idx = jax.lax.axis_index(sd.axis)
    st = structure
    if sd.strategy == "shard_n":
        n_lab = st.w_free[0]
        w_ax = st.w_labels.index(n_lab)
        part = w.shape[w_ax] // sd.ways
        w_loc = jax.lax.dynamic_slice_in_dim(w, idx * part, part, axis=w_ax)
        b_loc = None if bias is None else \
            jax.lax.dynamic_slice_in_dim(bias, idx * part, part, axis=0)
        out = be.einsum(spec, x, w_loc, plan, st, accum_dtype=accum_dtype,
                        interpret=interpret, bias=b_loc, act=act)
        out_ax = st.out_labels.index(n_lab)
        return jax.lax.all_gather(out, sd.axis, axis=out_ax, tiled=True)
    # shard_k
    from repro.engine import dispatch
    c = st.contract[0]
    x_ax = st.x_labels.index(c)
    w_ax = st.w_labels.index(c)
    part = x.shape[x_ax] // sd.ways
    x_loc = jax.lax.dynamic_slice_in_dim(x, idx * part, part, axis=x_ax)
    w_loc = jax.lax.dynamic_slice_in_dim(w, idx * part, part, axis=w_ax)
    out = be.einsum(spec, x_loc, w_loc, plan, st, accum_dtype=accum_dtype,
                    interpret=interpret, bias=None, act=None)
    out = jax.lax.psum(out, sd.axis)
    return dispatch.apply_epilogue(out, bias, act)
