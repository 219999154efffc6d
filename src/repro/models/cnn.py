"""The paper's evaluation networks — AlexNet, VGGNet-16, ResNet-50 — in JAX,
with every conv and FC layer routed through the multi-mode engine.

Layer tables double as the input to `core.analytics` (paper Eqs. 15-18), so
the same definition yields (a) a runnable functional model and (b) the
MMIE-projected latency / memory-access / performance-efficiency numbers of
the paper's Table 4 and Fig. 5.

Note on ResNet-50 (DESIGN.md §Arch-applicability): the paper's Table 2
counts the 49 main-path convolutions (1x 7x7, 16x 3x3, 32x 1x1) and models
all 3x3/1x1 at S=1; the functional model below additionally contains the 4
projection shortcuts and the stride-2 downsampling convs required for
correctness. `analytics_layers(main_path_only=True)` reproduces the paper's
counting; the functional path uses the real geometry.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import engine as E
from repro.core.analytics import ConvLayerSpec, FCLayerSpec


@dataclasses.dataclass(frozen=True)
class ConvDef:
    name: str
    c_in: int
    c_out: int
    k: int
    stride: int = 1
    pad: int = 0
    groups: int = 1
    pool: int = 1          # max-pool (k=stride=pool) applied after ReLU
    relu: bool = True


@dataclasses.dataclass(frozen=True)
class FCDef:
    name: str
    n: int
    m: int
    relu: bool = True


# ---------------------------------------------------------------------------
# AlexNet (227x227x3 input; grouped conv2/4/5 as in Krizhevsky 2012)
# ---------------------------------------------------------------------------

ALEXNET_CONVS: Tuple[ConvDef, ...] = (
    ConvDef("conv1", 3, 96, 11, stride=4, pad=0, pool=2),
    ConvDef("conv2", 96, 256, 5, stride=1, pad=2, groups=2, pool=2),
    ConvDef("conv3", 256, 384, 3, stride=1, pad=1),
    ConvDef("conv4", 384, 384, 3, stride=1, pad=1, groups=2),
    ConvDef("conv5", 384, 256, 3, stride=1, pad=1, groups=2, pool=2),
)
ALEXNET_FCS: Tuple[FCDef, ...] = (
    FCDef("fc6", 9216, 4096),
    FCDef("fc7", 4096, 4096),
    FCDef("fc8", 4096, 1000, relu=False),
)
ALEXNET_INPUT = (227, 227, 3)

# ---------------------------------------------------------------------------
# VGGNet-16 (224x224x3; all 3x3 s1 p1)
# ---------------------------------------------------------------------------

def _vgg_block(name: str, c_in: int, c_out: int, n: int,
               pool_last: bool = True) -> List[ConvDef]:
    defs = []
    for i in range(n):
        defs.append(ConvDef(f"{name}_{i+1}", c_in if i == 0 else c_out, c_out,
                            3, 1, 1, pool=2 if (pool_last and i == n - 1) else 1))
    return defs


VGG16_CONVS: Tuple[ConvDef, ...] = tuple(
    _vgg_block("conv1", 3, 64, 2) + _vgg_block("conv2", 64, 128, 2)
    + _vgg_block("conv3", 128, 256, 3) + _vgg_block("conv4", 256, 512, 3)
    + _vgg_block("conv5", 512, 512, 3))
VGG16_FCS: Tuple[FCDef, ...] = (
    FCDef("fc6", 25088, 4096),
    FCDef("fc7", 4096, 4096),
    FCDef("fc8", 4096, 1000, relu=False),
)
VGG16_INPUT = (224, 224, 3)

# ---------------------------------------------------------------------------
# ResNet-50 (v1: stride-2 in the first 1x1 of downsampling bottlenecks)
# ---------------------------------------------------------------------------

RESNET50_STAGES = (  # (n_blocks, c_mid, c_out, first_stride)
    (3, 64, 256, 1),
    (4, 128, 512, 2),
    (6, 256, 1024, 2),
    (3, 512, 2048, 2),
)
RESNET50_FCS: Tuple[FCDef, ...] = (FCDef("fc", 2048, 1000, relu=False),)
RESNET50_INPUT = (224, 224, 3)


@dataclasses.dataclass(frozen=True)
class CNNDef:
    name: str
    input_hw_c: Tuple[int, int, int]
    convs: Tuple[ConvDef, ...]      # empty for resnet (built structurally)
    fcs: Tuple[FCDef, ...]
    kind: str                       # "plain" | "resnet"


CNNS: Dict[str, CNNDef] = {
    "alexnet": CNNDef("alexnet", ALEXNET_INPUT, ALEXNET_CONVS, ALEXNET_FCS, "plain"),
    "vgg16": CNNDef("vgg16", VGG16_INPUT, VGG16_CONVS, VGG16_FCS, "plain"),
    "resnet50": CNNDef("resnet50", RESNET50_INPUT, (), RESNET50_FCS, "resnet"),
}


# ---------------------------------------------------------------------------
# Analytic layer tables (drive core.analytics / benchmarks.paper_tables)
# ---------------------------------------------------------------------------

def analytics_layers(name: str, main_path_only: bool = True,
                     ) -> Tuple[List[ConvLayerSpec], List[FCLayerSpec]]:
    """Conv/FC layer geometry tables for the paper's cost model."""
    net = CNNS[name]
    h, w, _ = net.input_hw_c
    convs: List[ConvLayerSpec] = []
    if net.kind == "plain":
        for cd in net.convs:
            spec = ConvLayerSpec(cd.name, h, w, cd.c_in, cd.c_out, cd.k, cd.k,
                                 cd.stride, cd.pad, cd.groups)
            convs.append(spec)
            h, w = spec.h_out // cd.pool, spec.w_out // cd.pool
    else:
        # conv1 7x7/2 + maxpool/2
        spec = ConvLayerSpec("conv1", h, w, 3, 64, 7, 7, 2, 3)
        convs.append(spec)
        h = w = spec.h_out // 2
        c_in = 64
        for si, (n_blocks, c_mid, c_out, first_stride) in enumerate(RESNET50_STAGES):
            for b in range(n_blocks):
                s = first_stride if b == 0 else 1
                pre = f"s{si+2}b{b+1}"
                h2, w2 = (h + s - 1) // s, (w + s - 1) // s
                # Paper Table-2 counting books every 1x1/3x3 bottleneck conv
                # as an S=1 mode: the strided-out pixels of a W_f<=S conv
                # never reach any output, so the engine streams the
                # decimated map (h2 x w2) at S=1 — same MACs and cycles as
                # the real stride-2 geometry, but the spec now *says* S=1,
                # matching the (1,1)/(3,1) modes the paper lists. The real
                # geometry keeps the stride for the functional model.
                if main_path_only:
                    convs.append(ConvLayerSpec(f"{pre}_1x1a", h2, w2, c_in,
                                               c_mid, 1, 1, 1))
                else:
                    convs.append(ConvLayerSpec(f"{pre}_1x1a", h, w, c_in,
                                               c_mid, 1, 1, s))
                convs.append(ConvLayerSpec(f"{pre}_3x3", h2, w2, c_mid, c_mid,
                                           3, 3, 1, 1))
                convs.append(ConvLayerSpec(f"{pre}_1x1b", h2, w2, c_mid, c_out,
                                           1, 1, 1))
                if b == 0 and not main_path_only:
                    convs.append(ConvLayerSpec(f"{pre}_proj", h, w, c_in,
                                               c_out, 1, 1, s))
                h, w, c_in = h2, w2, c_out
    fcs = [FCLayerSpec(f.name, f.n, f.m) for f in net.fcs]
    return convs, fcs


# ---------------------------------------------------------------------------
# Functional models (init + apply through the multi-mode engine)
# ---------------------------------------------------------------------------

def _conv_init(key, cd: ConvDef, dtype) -> Dict[str, jax.Array]:
    fan_in = cd.k * cd.k * cd.c_in // cd.groups
    w = jax.random.normal(key, (cd.k, cd.k, cd.c_in // cd.groups, cd.c_out),
                          dtype) * (2.0 / fan_in) ** 0.5
    return {"w": w, "b": jnp.zeros((cd.c_out,), dtype)}


def _fc_init(key, fd: FCDef, dtype) -> Dict[str, jax.Array]:
    w = jax.random.normal(key, (fd.n, fd.m), dtype) * (2.0 / fd.n) ** 0.5
    return {"w": w, "b": jnp.zeros((fd.m,), dtype)}


def init_cnn(name: str, key: jax.Array, dtype=jnp.float32) -> Dict:
    net = CNNS[name]
    params: Dict = {"conv": {}, "fc": {}}
    if net.kind == "plain":
        for cd in net.convs:
            key, sub = jax.random.split(key)
            params["conv"][cd.name] = _conv_init(sub, cd, dtype)
    else:
        convs, _ = analytics_layers(name, main_path_only=False)
        for spec in convs:
            key, sub = jax.random.split(key)
            cd = ConvDef(spec.name, spec.c_in, spec.c_out, spec.w_f,
                         spec.s, spec.pad)
            params["conv"][spec.name] = _conv_init(sub, cd, dtype)
    for fd in net.fcs:
        key, sub = jax.random.split(key)
        params["fc"][fd.name] = _fc_init(sub, fd, dtype)
    return params


def _maxpool(x: jax.Array, k: int) -> jax.Array:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, k, k, 1), (1, k, k, 1), "VALID")


def _layer_names(net: CNNDef) -> set:
    if net.kind == "plain":
        names = {cd.name for cd in net.convs}
    else:
        convs, _ = analytics_layers(net.name, main_path_only=False)
        names = {c.name for c in convs}
    return names | {fd.name for fd in net.fcs}


def _check_precisions(net: CNNDef,
                      precisions: Optional[Dict[str, str]]) -> None:
    if precisions is None:
        return
    unknown = set(precisions) - _layer_names(net)
    if unknown:
        raise ValueError(
            f"unknown layer name(s) {sorted(unknown)} in precisions for "
            f"{net.name!r}")


def _prec(precisions: Optional[Dict[str, str]], name: str) -> Optional[str]:
    return None if precisions is None else precisions.get(name)


def _forward(net: CNNDef, params: Dict, x: jax.Array,
             precisions: Optional[Dict[str, str]] = None) -> jax.Array:
    """The functional forward pass, engine-routed, context-free — shared by
    eager `apply_cnn` and the compiled `program(...)` path.

    Bias and ReLU ride each conv/FC op as the engine's fused epilogue: a
    conv+bias+relu layer is ONE kernel launch on the Pallas backend
    (epilogue applied in the accumulator — fp32, or int32-with-fused-
    dequant on the int8 path) instead of three ops. `precisions` maps
    layer names to explicit per-layer precision overrides ("fp32" |
    "int8"); an entry wins over the ambient config AND over a compiled
    plan's pinned precision, the same way an explicit `backend=` argument
    wins at the engine API."""
    if net.kind == "plain":
        for cd in net.convs:
            p = params["conv"][cd.name]
            x = E.conv2d(x, p["w"], stride=cd.stride, pad=cd.pad,
                         groups=cd.groups, bias=p["b"],
                         act="relu" if cd.relu else None,
                         precision=_prec(precisions, cd.name), name=cd.name)
            if cd.pool > 1:
                x = _maxpool(x, cd.pool)
        x = x.reshape(x.shape[0], -1)
    else:
        x = _resnet50_body(params, x, precisions)
        x = x.mean(axis=(1, 2))         # global average pool
    for fd in net.fcs:
        p = params["fc"][fd.name]
        x = E.matmul(x, p["w"], bias=p["b"],
                     act="relu" if fd.relu else None,
                     precision=_prec(precisions, fd.name), name=fd.name)
    return x


def apply_cnn(name: str, params: Dict, x: jax.Array,
              engine=None, *, backend: Optional[str] = None,
              config: Optional[E.EngineConfig] = None,
              precisions: Optional[Dict[str, str]] = None) -> jax.Array:
    """Eager forward pass through the multi-mode engine. x: (B, H, W, 3) ->
    logits (B, 1000).

    `config` threads a full `engine.EngineConfig`; `backend` is the compat
    shim selecting just the engine backend ("pallas" | "xla" | "ref");
    `precisions` maps layer names to per-layer precision overrides (e.g.
    ``{"fc6": "int8"}`` — wins over the config's `precision`); wrap
    the call in `E.tracking()` to collect the MMIE analytics ledger. The
    `engine` argument still accepts a legacy `core.MultiModeEngine` (its
    backend and ledger are honored) but is deprecated. For the jitted,
    whole-network-planned path use `engine.compile(program(name), cfg)`.
    """
    if engine is not None:          # legacy shim path
        backend = engine.config.backend
        track = (E.tracking(engine.ledger) if engine.config.track_analytics
                 else contextlib.nullcontext())
    else:
        track = contextlib.nullcontext()
    if config is not None and backend is not None:
        raise ValueError("pass config or backend (or a legacy engine), "
                         "not both")
    net = CNNS[name]
    _check_precisions(net, precisions)
    ctx = E.using_config(config) if config is not None \
        else E.using_backend(backend)
    with track, ctx:
        return _forward(net, params, x, precisions)


def program(name: str, *, batch: int = 1, dtype=jnp.float32,
            main_path_only: bool = True,
            precisions: Optional[Dict[str, str]] = None) -> E.Program:
    """The network as an `engine.Program`: an ordered, shape-complete op
    graph derived from the `CNNDef` layer tables, plus the executable
    functional forward.

    With `main_path_only=True` (default) the op graph follows the paper's
    Table-2/Table-4 counting — `engine.compile(program(net)).plan`
    reproduces `analytics.network_cost` exactly (ResNet-50 books the 49
    main-path convs, S=1 modes, no projection shortcuts). The *execution*
    side always runs the real geometry: `compile()` captures the functional
    forward's own op sequence, so `.apply` matches `apply_cnn` bitwise.
    `main_path_only=False` makes the op graph itself follow the real
    geometry (what a `tracking()` ledger of one forward would record).

    The program carries batch metadata, so the batched apply path is
    `engine.compile(program(net).with_batch(B), cfg).apply(params, xB)` —
    re-planned, never re-traced; the `serve.scheduler` uses exactly this to
    pack requests into batch buckets.

    `precisions` bakes per-layer precision overrides into the program's
    forward: the named layers issue an explicit `precision=` at every
    execution, which wins over the compile config's `precision` the same
    way an explicit backend pin wins over the planned backend.
    """
    net = CNNS[name]
    _check_precisions(net, precisions)
    h, w, c = net.input_hw_c
    conv_specs, fc_specs = analytics_layers(name, main_path_only)
    ops: List[E.OpSpec] = []
    for cs in conv_specs:
        ops.append(E.OpSpec(
            "conv2d",
            (batch, cs.h_in, cs.w_in, cs.c_in),
            (cs.h_f, cs.w_f, cs.c_in // cs.groups, cs.c_out),
            stride=cs.s, pad=cs.pad, groups=cs.groups, name=cs.name))
    for fs in fc_specs:
        ops.append(E.OpSpec(
            "dense", (batch, fs.n), (fs.n, fs.m),
            spec=E.dense_spec(2), name=fs.name))
    params_avals = jax.eval_shape(
        lambda key: init_cnn(name, key, dtype), jax.random.PRNGKey(0))
    x_aval = jax.ShapeDtypeStruct((batch, h, w, c), dtype)
    fn = (functools.partial(_forward, net) if precisions is None
          else functools.partial(_forward, net, precisions=dict(precisions)))
    batch_axes = E.infer_batch_axes(
        (params_avals, x_aval),
        (params_avals, jax.ShapeDtypeStruct((batch + 1, h, w, c), dtype)))
    return E.Program(name=name, ops=tuple(ops), fn=fn,
                     in_avals=(params_avals, x_aval),
                     batch_size=batch, batch_axes=batch_axes)


def _resnet50_body(params: Dict, x: jax.Array,
                   precisions: Optional[Dict[str, str]] = None) -> jax.Array:
    pc = params["conv"]

    def conv(nm, x, stride, pad, act=None):
        # bias (and relu where it directly follows) fused into the engine op
        p = pc[nm]
        return E.conv2d(x, p["w"], stride=stride, pad=pad, bias=p["b"],
                        act=act, precision=_prec(precisions, nm), name=nm)

    x = conv("conv1", x, 2, 3, act="relu")
    x = _maxpool(jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)),
                         constant_values=-jnp.inf), 2)
    for si, (n_blocks, c_mid, c_out, first_stride) in enumerate(RESNET50_STAGES):
        for b in range(n_blocks):
            s = first_stride if b == 0 else 1
            pre = f"s{si+2}b{b+1}"
            res = x
            y = conv(f"{pre}_1x1a", x, s, 0, act="relu")
            y = conv(f"{pre}_3x3", y, 1, 1, act="relu")
            y = conv(f"{pre}_1x1b", y, 1, 0)
            if b == 0:
                res = conv(f"{pre}_proj", x, s, 0)
            x = jax.nn.relu(y + res)
    return x


def total_macs(name: str) -> Tuple[int, int]:
    """(conv MACs, FC MACs) — cross-check against the paper's §1 numbers."""
    convs, fcs = analytics_layers(name)
    return sum(c.macs for c in convs), sum(f.macs for f in fcs)
