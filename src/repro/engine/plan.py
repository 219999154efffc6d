"""EnginePlan — the pure, hashable execution plan of one engine op.

A plan is a function of *op shapes alone* (plus the static knobs stride /
pad / groups / backend): no array data, no mutable state. That makes it

  * safe to compute at trace time under `jax.jit` (shapes are static),
  * cacheable (`functools.lru_cache` below — re-traces hit the cache),
  * usable as a dict key / static jit argument (frozen dataclass of ints,
    strings and `modes.Mode`),

and nothing about it depends on where or how often it is called. Each plan
carries the paper-side schedule (the Table-3 mode and its analytic cost,
Eqs. 15-18) and the TPU-side schedule (the MXU tile triple of
`modes.mxu_tiling_for_mode`) for the op, so dispatch, analytics and any
future tiling policy all read from one object.

Einsum planning: a dense contraction `einsum(spec, x, w)` is classified per
axis label into batch (x, w and out), contraction (x and w, not out),
x-free and w-free dims. Its FC-mode cost is `fc_cost(n=prod(contract),
m=prod(w_free))` scaled by every remaining x dim — the 2-D `matmul` cost,
generalized to stacked-expert and transposed-head weights.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Tuple

from repro.core import analytics, modes

Shape = Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """The shape-complete invocation record of one engine op.

    Where `EnginePlan` is the engine's *decision* about an op, `OpSpec` is
    the op itself — kind, operand shapes and static knobs — i.e. one node
    of a `program.Program` graph. It is a frozen dataclass of ints and
    strings: hashable, usable as a dict key, re-plannable under any
    `EngineConfig` via `plan_op`.
    """

    kind: str                       # "conv2d" | "conv1d_dw" | "dense" | "gather"
    x_shape: Shape
    w_shape: Shape
    spec: str = ""                  # einsum spec ("dense" kind only)
    stride: int = 1
    pad: int = 0
    groups: int = 1
    causal: bool = True             # conv1d_dw only
    name: str = dataclasses.field(default="", compare=False)  # layer label

    def __post_init__(self) -> None:
        if self.kind not in ("conv2d", "conv1d_dw", "dense", "gather"):
            raise ValueError(f"unknown op kind {self.kind!r}")


def plan_op(op: OpSpec, backend: str) -> EnginePlan:
    """Plan one `OpSpec` for `backend` (shared lru caches with the per-op
    planners, so compile-then-execute never plans twice)."""
    if op.kind == "conv2d":
        return plan_conv2d(op.x_shape, op.w_shape, op.stride, op.pad,
                           op.groups, backend)
    if op.kind == "conv1d_dw":
        return plan_conv1d_depthwise(op.x_shape, op.w_shape, backend)
    if op.kind == "gather":
        return plan_gather(op.x_shape, op.w_shape, backend)
    return plan_einsum(op.spec, op.x_shape, op.w_shape, backend)


def auto_backend(op: OpSpec, fallback: str = "xla") -> str:
    """The "auto" backend-selection policy: pallas vs `fallback` per layer.

    The Pallas kernels are blocked MXU GEMMs, so they win when the op maps
    onto full (8, 128)-tile GEMM work and lose to the XLA lowering when the
    contraction is ragged or batched:

      * dense ops go to pallas when they canonicalize to a 2-D
        `(M, K) @ (K, N)` (single contract label, 2-D weights, no batched
        weights) with K and N each >= 128 — one full MXU k/cout tile;
      * 1x1 convs (mode T=1: a pure GEMM per pixel row) go to pallas under
        the same >=128 channel-fill test;
      * wider conv filters and depthwise 1-D convs stay on `fallback`
        (the shifted-GEMM loop fuses better under XLA).
    """
    if op.kind == "conv2d":
        plan = plan_op(op, fallback)
        c_in = op.w_shape[2]
        c_out = op.w_shape[3]
        if plan.mode.t == 1 and c_in >= 128 and c_out >= 128:
            return "pallas"
        return fallback
    if op.kind == "dense":
        st = parse_einsum(op.spec, len(op.x_shape), len(op.w_shape))
        canonical = (len(op.w_shape) == 2 and len(st.contract) == 1
                     and not st.batch)
        if canonical and min(op.w_shape) >= 128:
            return "pallas"
    return fallback


@dataclasses.dataclass(frozen=True)
class ShardDecision:
    """How one op is split across the mesh's tensor-parallel ("model") axis.

    The per-op analogue of the pallas-vs-xla backend choice, for devices
    instead of kernels (engine/parallel.py owns the policy):

      * ``"replicate"`` — every device runs the full op (no collective);
      * ``"shard_k"``   — the contraction (K) dim is split; each device
        produces a full-shape partial sum, combined by an all-reduce;
      * ``"shard_n"``   — the weight-free output (N) dim is split; each
        device produces a column slice, combined by an all-gather.

    `words` is the op's *global* output size in 16-bit words — what the
    combining collective moves. Wire traffic follows the standard ring
    formulas: an all-gather moves (w-1)/w of the result per device, an
    all-reduce twice that (reduce-scatter + all-gather).
    """

    strategy: str                   # "replicate" | "shard_k" | "shard_n"
    ways: int                       # size of the mesh axis ("model")
    axis: str = "model"             # mesh axis name the collective runs over
    words: int = 0                  # global output words (0 for replicate)

    def __post_init__(self) -> None:
        if self.strategy not in ("replicate", "shard_k", "shard_n"):
            raise ValueError(f"unknown shard strategy {self.strategy!r}")
        if self.ways < 1:
            raise ValueError(f"ways must be >= 1, got {self.ways}")

    @property
    def collective(self) -> str:
        if self.ways <= 1 or self.strategy == "replicate":
            return "none"
        return "all_reduce" if self.strategy == "shard_k" else "all_gather"

    @property
    def wire_words(self) -> int:
        """Ring-collective wire traffic per device, in 16-bit words."""
        if self.collective == "none":
            return 0
        passes = 2 if self.collective == "all_reduce" else 1
        return -(-passes * (self.ways - 1) * self.words // self.ways)

    @property
    def collective_cycles(self) -> int:
        """Link cycles (at the conv clock) the combining collective costs."""
        if not self.wire_words:
            return 0
        return -(-self.wire_words // modes.MMIE_LINK_WORDS_PER_CYCLE)


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """Everything the engine decided about one op, from shapes alone."""

    kind: str                       # "conv2d" | "conv1d_dw" | "dense"
    backend: str                    # registry name ("pallas" | "xla" | "ref" | ...)
    mode: modes.Mode                # paper mode (W_f, S) with Table-3 schedule
    tiling: Tuple[int, int, int]    # MXU (row_tile, k_tile, cout_tile) analogue
    cycles: int                     # MMIE-projected cycles (batch included)
    ma_words: int                   # MMIE memory accesses, 16-bit words
    macs: int                       # useful multiply-accumulates
    note: str = ""                  # plan caveats (fallbacks, decimation, ...)
    # Tuned kernel tile pinned by engine.compile / the eager cached lookup
    # (engine/tune.py): (bm, bk, bn) for dense, (cib, cob) for conv2d. None
    # keeps the kernel's built-in default. The lru-cached planners below
    # never set it — a tuned plan is always a dataclasses.replace of a pure
    # analytic plan, so the plan caches stay tuning-agnostic.
    tile_config: Optional[Tuple[int, ...]] = None
    # Multi-device placement pinned by engine.compile when the config
    # carries a ParallelConfig (engine/parallel.py). Like tile_config, the
    # lru-cached planners never set it — a sharded plan is always a
    # dataclasses.replace of the pure single-device analytic plan, so the
    # plan caches stay parallelism-agnostic and `cycles` / `macs` /
    # `ma_words` keep their global (whole-op) meaning everywhere.
    shard: Optional["ShardDecision"] = None
    # Execution precision pinned by engine.compile / the per-call resolver
    # (api._resolve_precision): "fp32" or "int8". Like tile_config/shard,
    # the lru-cached planners never set it — a quantized plan is a
    # dataclasses.replace of the fp32 analytic plan, so `ma_words` keeps
    # its paper Table-4 (16-bit-word, fp32-model) meaning everywhere and
    # the reduced traffic is booked separately via `exec_ma_words`.
    precision: str = "fp32"

    @property
    def performance_efficiency(self) -> float:
        """Paper Fig. 5 metric: useful MACs over peak array MACs."""
        return self.macs / (modes.MMIE_NUM_PES * self.cycles) if self.cycles \
            else 0.0

    @property
    def exec_ma_words(self) -> int:
        """Memory-access words as executed: `ma_words` for fp32, halved
        (ceil) for int8 — int8 operands occupy half a 16-bit MMIE word.
        The analytic `ma_words` stays pinned to the paper's fp32 model so
        the Table-4 goldens never move with the precision axis; collective
        wire words (`ShardDecision.wire_words`) are NOT scaled — sharded
        ops all-reduce/all-gather fp32 partials, not int8 operands."""
        if self.precision == "int8":
            return -(-self.ma_words // 2)
        return self.ma_words

    @property
    def exec_cycles(self) -> int:
        """Cycles on the critical path of one device: `cycles / ways` for a
        genuinely split op, the full `cycles` when replicated (every device
        repeats the whole op) or unsharded. Collective cycles are booked
        separately (`ShardDecision.collective_cycles`) — they run on the
        link clock, not the PE array."""
        if self.shard is None or self.shard.ways <= 1 \
                or self.shard.strategy == "replicate":
            return self.cycles
        return -(-self.cycles // self.shard.ways)


def _mode_for(w_f: int, s: int) -> modes.Mode:
    """Mode lookup that tolerates filters beyond the 11-register MMIE weight
    generator (e.g. hubert's 128-tap positional conv): such layers still get
    the derived (N_eff, p_eff) schedule instead of a hard error."""
    if w_f > 11:
        return modes.derived_mode(w_f, s)
    return modes.paper_mode(w_f, s)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def plan_conv2d(x_shape: Shape, w_shape: Shape, stride: int, pad: int,
                groups: int, backend: str) -> EnginePlan:
    """x: (B, H, W, C_in) NHWC; w: (H_f, W_f, C_in/g, C_out) HWIO."""
    h_f, w_f, _, c_out = (int(v) for v in w_shape)
    b, h_in, w_in, c_in = (int(v) for v in x_shape)
    spec = analytics.ConvLayerSpec("conv2d", h_in, w_in, c_in, c_out,
                                   h_f, w_f, stride, pad, groups)
    cost = analytics.conv_cost(spec)
    note = ""
    if w_f <= stride:
        note = "W_f<=S: strided-out pixels decimated, booked at S=1"
    return EnginePlan(
        kind="conv2d", backend=backend, mode=cost.mode,
        tiling=modes.mxu_tiling_for_mode(cost.mode, c_in // groups, c_out),
        cycles=cost.cycles * b, ma_words=cost.ma_total_words * b,
        macs=cost.macs * b, note=note)


# ---------------------------------------------------------------------------
# depthwise conv1d (SSM / positional short convs)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def plan_conv1d_depthwise(x_shape: Shape, w_shape: Shape,
                          backend: str) -> EnginePlan:
    """x: (B, L, D); w: (W_f, D). Each channel is an independent GFID row."""
    w_f = int(w_shape[0])
    b, l, d = (int(v) for v in x_shape)
    mode = _mode_for(w_f, 1)
    spec = analytics.ConvLayerSpec("conv1d_dw", 1, l, 1, 1, 1, w_f, 1,
                                   pad=w_f - 1)
    cost = analytics.conv_cost(spec, mode)
    return EnginePlan(
        kind="conv1d_dw", backend=backend, mode=mode,
        tiling=modes.mxu_tiling_for_mode(mode, 1, d),
        cycles=cost.cycles * d * b, ma_words=cost.ma_total_words * d * b,
        macs=cost.macs * d * b)


# ---------------------------------------------------------------------------
# paged-KV block gather (serving memory move)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def plan_gather(x_shape: Shape, w_shape: Shape, backend: str) -> EnginePlan:
    """x: (num_blocks, block_size, *feature) paged KV pool; w: (B,
    blocks_per_req) int32 block table. A pure memory move — zero MACs —
    priced at the words gathered (one read + one write per element, moved
    through the array at one word per PE per cycle), so a serving plan that
    includes paged-KV reconstruction stays honest about where its cycles go
    instead of booking the gather as free."""
    block_size = int(x_shape[1])
    feature = math.prod(int(v) for v in x_shape[2:])
    b, blocks_per_req = (int(v) for v in w_shape)
    words = b * blocks_per_req * block_size * feature
    mode = modes.fc_mode()
    return EnginePlan(
        kind="gather", backend=backend, mode=mode,
        tiling=modes.mxu_tiling_for_mode(mode, 1, 1),
        cycles=-(-words // modes.MMIE_NUM_PES),
        ma_words=2 * words, macs=0,
        note="paged-KV block gather (pure memory move)")


# ---------------------------------------------------------------------------
# dense contractions (FC mode)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EinsumStructure:
    """Parsed two-operand einsum: per-axis roles, in operand order."""

    x_labels: Tuple[str, ...]
    w_labels: Tuple[str, ...]
    out_labels: Tuple[str, ...]
    batch: Tuple[str, ...]          # in x, w and out
    contract: Tuple[str, ...]       # in x and w, not out
    x_free: Tuple[str, ...]         # in x and out only
    w_free: Tuple[str, ...]         # in w and out only


def canonical_gemm(structure: EinsumStructure, w_ndim: int) -> bool:
    """True when a dense contraction lowers to ONE (M, K) @ (K, N) blocked
    GEMM: single contract label, plain 2-D weights, no batched dims, output
    laid out x-free rows then w-free cols. The single source of truth for
    both the Pallas dispatch path (dispatch._pallas_einsum runs the kernel
    exactly when this holds, else falls back to the XLA lowering) and the
    autotuner's key space (engine/tune.py only tunes ops the kernel will
    actually execute)."""
    return (w_ndim == 2 and len(structure.contract) == 1
            and not structure.batch
            and structure.out_labels == structure.x_free + structure.w_free)


PRECISIONS = ("fp32", "int8")


def supports_int8(op: OpSpec) -> bool:
    """True when the int8 quantized contract is defined for `op`: conv2d
    and canonical-GEMM dense ops. Everything else (non-canonical einsums,
    depthwise conv1d, gather) stays fp32 even under
    `EngineConfig(precision="int8")` — a shape-only predicate, so every
    backend agrees on which ops quantize."""
    if op.kind == "conv2d":
        return True
    if op.kind == "dense":
        st = parse_einsum(op.spec, len(op.x_shape), len(op.w_shape))
        return canonical_gemm(st, len(op.w_shape))
    return False


def with_precision(plan: EnginePlan, op: OpSpec,
                   precision: str) -> EnginePlan:
    """Pin `precision` onto a plan, downgrading to fp32 for ops outside
    the int8 contract. The replace-not-mutate shape keeps the lru-cached
    planners precision-agnostic (same pattern as tile_config / shard)."""
    p = "int8" if precision == "int8" and supports_int8(op) else "fp32"
    if p == plan.precision:
        return plan
    return dataclasses.replace(plan, precision=p)


@functools.lru_cache(maxsize=1024)
def parse_einsum(spec: str, x_ndim: int, w_ndim: int) -> EinsumStructure:
    """Parse `spec` for operands of the given ranks. Ellipses in the spec are
    expanded to reserved per-position labels ("…0", "…1", ...)."""
    if "->" not in spec:
        raise ValueError(f"engine.einsum requires an explicit output: {spec!r}")
    lhs, rhs = spec.split("->")
    ops = lhs.split(",")
    if len(ops) != 2:
        raise ValueError(f"engine.einsum takes exactly two operands: {spec!r}")

    def _splice(sub: str, ell: Tuple[str, ...]) -> Tuple[str, ...]:
        head, tail = sub.split("...")
        return tuple(head) + ell + tuple(tail)

    def expand(sub: str, ndim: int) -> Tuple[str, ...]:
        sub = sub.replace(" ", "")
        if "..." in sub:
            n_ell = ndim - len(sub.replace("...", ""))
            if n_ell < 0:
                raise ValueError(f"operand rank {ndim} too small for {sub!r}")
            return _splice(sub, tuple(f"…{i}" for i in range(n_ell)))
        if len(sub) != ndim:
            raise ValueError(f"{sub!r} does not match operand rank {ndim}")
        return tuple(sub)

    x_labels = expand(ops[0], x_ndim)
    w_labels = expand(ops[1], w_ndim)
    for labels, side in ((x_labels, "operand 0"), (w_labels, "operand 1")):
        if len(set(labels)) != len(labels):
            raise ValueError(
                f"repeated label within {side} of {spec!r} (a diagonal, "
                "not a dense contraction the engine can plan)")
    rhs = rhs.replace(" ", "")
    if "..." in rhs:
        # output ellipsis carries the x-side ellipsis labels (numpy rule:
        # broadcast dims lead; here w never carries an ellipsis).
        n_ell = sum(1 for l in x_labels if l.startswith("…"))
        out_labels = _splice(rhs, tuple(f"…{i}" for i in range(n_ell)))
    else:
        out_labels = tuple(rhs)

    xs, ws, os_ = set(x_labels), set(w_labels), set(out_labels)
    for lab in os_:
        if lab not in xs | ws:
            raise ValueError(f"output label {lab!r} missing from inputs: {spec!r}")
    for lab in xs | ws:
        if lab not in os_ and not (lab in xs and lab in ws):
            raise ValueError(
                f"label {lab!r} is summed within one operand — not a dense "
                f"contraction the engine can plan: {spec!r}")
    batch = tuple(l for l in x_labels if l in ws and l in os_)
    contract = tuple(l for l in x_labels if l in ws and l not in os_)
    x_free = tuple(l for l in x_labels if l not in ws)
    w_free = tuple(l for l in w_labels if l not in xs)
    return EinsumStructure(x_labels, w_labels, out_labels,
                           batch, contract, x_free, w_free)


@functools.lru_cache(maxsize=4096)
def plan_einsum(spec: str, x_shape: Shape, w_shape: Shape,
                backend: str) -> EnginePlan:
    """FC-mode plan for a dense contraction `einsum(spec, x, w)`."""
    st = parse_einsum(spec, len(x_shape), len(w_shape))
    dims: Dict[str, int] = {}
    for labels, shape in ((st.x_labels, x_shape), (st.w_labels, w_shape)):
        for lab, size in zip(labels, shape):
            if dims.setdefault(lab, int(size)) != int(size):
                raise ValueError(
                    f"size mismatch for {lab!r} in {spec!r}: "
                    f"{dims[lab]} vs {size}")
    # math.prod of an empty tuple is 1 (no contract labels = outer product,
    # one MAC per output element); a genuine zero-size dim propagates a
    # zero-work plan (0 MACs, 0 cycles, 0 memory words) instead of being
    # rounded up.
    n = math.prod(dims[l] for l in st.contract)
    m = math.prod(dims[l] for l in st.w_free)
    reps = math.prod(dims[l] for l in st.batch + st.x_free)
    if n == 0 or m == 0:
        reps = 0
    fc = analytics.fc_cost(analytics.FCLayerSpec("fc", n, m))
    mode = modes.fc_mode()
    return EnginePlan(
        kind="dense", backend=backend, mode=mode,
        tiling=modes.mxu_tiling_for_mode(mode, n, m),
        cycles=fc.cycles * reps, ma_words=fc.ma_total_words * reps,
        macs=fc.macs * reps,
        note="" if not st.batch else
        f"batched weights over {len(st.batch)} dim(s)")


def dense_spec(x_ndim: int) -> str:
    """Canonical `(…, n) @ (n, m)` spec for `engine.dense`."""
    return "...n,nm->...m"
