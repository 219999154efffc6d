"""Plain reference of the CNN configurations, in jax.numpy and lax alone.

It imports nothing of the program. `init` makes the weights the benchmark
serves, in the program's parameter layout ({"conv": {name: {"w", "b"}},
"fc": {name: {"w", "b"}}}), in one jitted call from a key. `forward` is
the network written out plainly from the configuration file: NHWC convs
with bias and ReLU, max-pools, ResNet v1 bottlenecks, the FC stack.

`precision` is how each conv and matmul multiplies float32 operands:

  "highest"  full float32 products (lax.Precision.HIGHEST). The reference.
  "high"     three bfloat16 passes (lax.Precision.HIGH): the next precision
             down, and the control. On a TPU the chip's own HIGH; where the
             platform ignores the flag (the CPU), each operand is split
             into a bfloat16 high and low part and hi*hi + hi*lo + lo*hi is
             summed in float32, which is what HIGH computes.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from bench import counts

BIAS_STD = 0.05
PRECISIONS = ("highest", "high")


def init(cfg: Dict, key: jax.Array) -> Dict:
    """He-normal weights and small normal biases for every op of `cfg`, cut
    from one standard-normal draw in one jitted call."""
    ops = counts.ops(cfg)
    total = sum(op.weight_words for op in ops)

    def make(key):
        flat = jax.random.normal(key, (total,), jnp.float32)
        params: Dict = {"conv": {}, "fc": {}}
        at = 0
        for op in ops:
            fan_in = op.c_in if op.kind == "dense" \
                else op.k * op.k * op.c_in // op.groups
            n = op.weight_words - op.c_out
            w = flat[at:at + n].reshape(op.w_shape) * (2.0 / fan_in) ** 0.5
            b = flat[at + n:at + n + op.c_out] * BIAS_STD
            at += op.weight_words
            group = "fc" if op.kind == "dense" else "conv"
            params[group][op.name] = {"w": w, "b": b}
        return params

    return jax.jit(make)(key)


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _multiplied(op, x, w, precision: str):
    """op(x, w) at `precision` (op is bilinear in x and w)."""
    if precision == "highest":
        return op(x, w, lax.Precision.HIGHEST)
    if precision == "high":
        if jax.default_backend() == "tpu":
            return op(x, w, lax.Precision.HIGH)
        (xh, xl), (wh, wl) = _split(x), _split(w)
        hp = lax.Precision.HIGHEST      # products of bf16 values are exact
        return op(xh, wh, hp) + op(xh, wl, hp) + op(xl, wh, hp)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def _conv(x, p, stride, pad, groups, precision):
    def op(a, w, prec):
        return lax.conv_general_dilated(
            a, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=prec)
    return _multiplied(op, x, p["w"], precision) + p["b"]


def _dense(x, p, precision):
    def op(a, w, prec):
        return jnp.matmul(a, w, precision=prec)
    return _multiplied(op, x, p["w"], precision) + p["b"]


def _maxpool(x, k: int):
    if k == 1:
        return x
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, k, k, 1), "VALID")


def _plain(cfg, params, x, precision):
    for cd in cfg["convs"]:
        x = _conv(x, params["conv"][cd["name"]], cd["stride"], cd["pad"],
                  cd["groups"], precision)
        if cd["relu"]:
            x = jax.nn.relu(x)
        x = _maxpool(x, cd["pool"])
    return x.reshape(x.shape[0], -1)


def _resnet(cfg, params, x, precision):
    pc = params["conv"]
    st = cfg["stem"]
    x = jax.nn.relu(_conv(x, pc[st["name"]], st["stride"], st["pad"], 1,
                          precision))
    x = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)),
                constant_values=-jnp.inf)
    x = _maxpool(x, st["pool"])
    for si, sg in enumerate(cfg["stages"]):
        for b in range(sg["blocks"]):
            s = sg["stride"] if b == 0 else 1
            pre = f"s{si + 2}b{b + 1}"
            y = jax.nn.relu(_conv(x, pc[f"{pre}_1x1a"], s, 0, 1, precision))
            y = jax.nn.relu(_conv(y, pc[f"{pre}_3x3"], 1, 1, 1, precision))
            y = _conv(y, pc[f"{pre}_1x1b"], 1, 0, 1, precision)
            res = x if b else _conv(x, pc[f"{pre}_proj"], s, 0, 1, precision)
            x = jax.nn.relu(y + res)
    return x.mean(axis=(1, 2))


_BODIES = {"plain": _plain, "resnet_v1_bottleneck": _resnet}


def forward(cfg: Dict, params: Dict, x: jax.Array,
            precision: str = "highest") -> jax.Array:
    """Logits of images `x` (B, H, W, C): (B, classes)."""
    x = _BODIES[cfg["arch"]](cfg, params, x, precision)
    for f in cfg["fcs"]:
        x = _dense(x, params["fc"][f["name"]], precision)
        if f["relu"]:
            x = jax.nn.relu(x)
    return x
