"""jit'd dispatch wrappers for the Pallas kernels.

On a TPU every kernel compiles to Mosaic. Where JAX's default backend is
the CPU they run in interpret mode (the kernel body executes in Python,
checking the BlockSpec tiling and accumulation logic); `interpret=None`,
the default, picks between the two (`resolve_interpret`). The wrappers add
padding, grouping, batching and dtype plumbing so callers see the same
contract as the pure-jnp references in ref.py / core.gfid.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gfid, quant
from repro.kernels import conv1d as _conv1d
from repro.kernels import flash_attention as _flash
from repro.kernels import gfid_conv as _conv
from repro.kernels import gfid_matmul as _matmul
from repro.kernels import paged as _paged


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """`interpret` when given, else True exactly where JAX's default backend
    is the CPU: Pallas kernels can only be interpreted there, and must never
    be on a TPU."""
    return jax.default_backend() == "cpu" if interpret is None else interpret


def gfid_conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1, pad: int = 0,
                groups: int = 1, tile: Optional[Tuple[int, int]] = None,
                bias: Optional[jax.Array] = None, act: Optional[str] = None,
                interpret: Optional[bool] = None,
                precision: str = "fp32") -> jax.Array:
    """NHWC x HWIO conv through the multi-mode engine's conv mode.

    `tile` is the (c_in_block, c_out_block) channel tiling (None keeps the
    kernel default; `engine.tune` passes per-layer winners). `bias` (C_out,)
    and `act` ("relu" | "gelu") run as a fused in-kernel epilogue.

    In fp32, a conv with fewer than `gfid.FOLD_MAX_C_IN` input channels per
    group (`gfid.folds_taps`, AlexNet's conv1) runs the kernel at stride 1
    over `gfid.fold_operands`, the relayout `gfid.conv2d_gfid` folds on
    XLA: stride phases and width taps in the contraction, its whole K one
    C_in block. Only the order of the sums changes.

    `precision="int8"` quantizes both operands symmetrically (per-example
    activation scales, per-channel weight scales), runs the int8 kernel
    with an exact int32 VMEM accumulator, and fuses dequant+bias+act into
    the same epilogue writeback — still one kernel launch.

    Grouped convolution (AlexNet's historical 2-group layers) runs as ONE
    batched kernel call: the group axis is stacked in front of x and w and
    `vmap`'s pallas_call batching rule folds it into the grid, instead of
    the old eager Python loop that emitted `groups` separate kernel launches
    plus a concatenate.
    """
    interpret = resolve_interpret(interpret)
    if precision == "int8":
        return _gfid_conv2d_int8(x, w, stride=stride, pad=pad, groups=groups,
                                 tile=tile, bias=bias, act=act,
                                 interpret=interpret)
    cib, cob = tile if tile is not None else _conv.DEFAULT_CONV_TILE
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    conv = partial(_conv_fp32, stride=stride, cib=cib, cob=cob, act=act,
                   interpret=interpret)
    if groups == 1:
        return conv(x, w, bias).astype(x.dtype)
    b, h_in, w_in, c_in = x.shape
    h_f, w_f, cg, c_out = w.shape
    og = c_out // groups
    # (B,H,W,G*cg) -> (G,B,H,W,cg); (Hf,Wf,cg,G*og) -> (G,Hf,Wf,cg,og).
    xg = jnp.moveaxis(x.reshape(b, h_in, w_in, groups, cg), 3, 0)
    wg = jnp.moveaxis(w.reshape(h_f, w_f, cg, groups, og), 3, 0)
    if bias is None and act is None:
        outs = jax.vmap(lambda xv, wv: conv(xv, wv, None))(xg, wg)
    else:
        bg = (jnp.zeros((c_out,), jnp.float32) if bias is None
              else bias.astype(jnp.float32)).reshape(groups, og)
        outs = jax.vmap(conv)(xg, wg, bg)
    # (G,B,Ho,Wo,og) -> (B,Ho,Wo,G*og) with groups major in C_out.
    return jnp.moveaxis(outs, 0, 3).reshape(
        b, outs.shape[2], outs.shape[3], c_out).astype(x.dtype)


def _conv_fp32(x: jax.Array, w: jax.Array, bias: Optional[jax.Array], *,
               stride: int, cib: int, cob: int, act: Optional[str],
               interpret: bool) -> jax.Array:
    """One group's valid fp32 conv on the GFID kernel. Where
    `gfid.folds_taps`, the kernel runs at stride 1 over the relayout
    `gfid.fold_operands`, one height tap a grid step and its whole K one
    C_in block: for AlexNet's conv1 one (55, 144) x (144, 96) dot a step,
    not eleven with K = 3 on a stride-phase copy padded from 3 lanes to
    128."""
    if gfid.folds_taps(w.shape):
        h_out = (x.shape[1] - w.shape[0]) // stride + 1
        w_out = (x.shape[2] - w.shape[1]) // stride + 1
        x, w_f = gfid.fold_operands(x, w, stride, h_out, w_out)
        w, stride, cib = w_f[:, None], 1, w_f.shape[1]
    return _conv.gfid_conv2d_nhwc(x, w, stride=stride, c_in_block=cib,
                                  c_out_block=cob, bias=bias, act=act,
                                  interpret=interpret)


def _gfid_conv2d_int8(x: jax.Array, w: jax.Array, *, stride: int, pad: int,
                      groups: int, tile: Optional[Tuple[int, int]],
                      bias: Optional[jax.Array], act: Optional[str],
                      interpret: bool) -> jax.Array:
    """int8 conv mode: quantize (before padding — scales must not see the
    zero pad), pad in int8 (exact zeros), run the int32-accumulator kernel
    with the fused dequant epilogue."""
    cib, cob = tile if tile is not None else _conv.DEFAULT_CONV_TILE
    xq, wq, sx, sw = quant.quantize_conv_operands(x, w)
    if pad:
        xq = jnp.pad(xq, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    b = x.shape[0]
    sx2 = sx.reshape(b, 1)                       # (B, 1) for the kernel
    h_f, w_f, cg, c_out = w.shape
    if groups == 1:
        out = _conv.gfid_conv2d_nhwc_int8(
            xq, wq, sx2, sw.reshape(1, c_out), stride=stride,
            c_in_block=cib, c_out_block=cob, bias=bias, act=act,
            interpret=interpret)
        return out.astype(x.dtype)
    og = c_out // groups
    h_in, w_in = xq.shape[1], xq.shape[2]
    xg = jnp.moveaxis(xq.reshape(b, h_in, w_in, groups, cg), 3, 0)
    wg = jnp.moveaxis(wq.reshape(h_f, w_f, cg, groups, og), 3, 0)
    swg = sw.reshape(groups, 1, og)              # (G, 1, og) per-group rows
    bg = None if bias is None else bias.astype(jnp.float32).reshape(
        groups, og)
    if bg is None:
        outs = jax.vmap(
            lambda xv, wv, sv: _conv.gfid_conv2d_nhwc_int8(
                xv, wv, sx2, sv, stride=stride, c_in_block=cib,
                c_out_block=cob, act=act, interpret=interpret))(xg, wg, swg)
    else:
        outs = jax.vmap(
            lambda xv, wv, sv, bv: _conv.gfid_conv2d_nhwc_int8(
                xv, wv, sx2, sv, stride=stride, c_in_block=cib,
                c_out_block=cob, bias=bv, act=act,
                interpret=interpret))(xg, wg, swg, bg)
    return jnp.moveaxis(outs, 0, 3).reshape(
        b, outs.shape[2], outs.shape[3], c_out).astype(x.dtype)


def gfid_matmul(x: jax.Array, w: jax.Array, *,
                tile: Optional[Tuple[int, int, int]] = None,
                bias: Optional[jax.Array] = None, act: Optional[str] = None,
                interpret: Optional[bool] = None,
                precision: str = "fp32") -> jax.Array:
    """(..., K) @ (K, N) through the FC mode.

    `tile` is the (bm, bk, bn) GEMM blocking (None keeps the kernel
    default); `bias` (N,) and `act` run as a fused in-kernel epilogue.
    `precision="int8"` quantizes per-row (x) / per-column (w) and runs the
    exact-int32-accumulator kernel with the fused dequant epilogue."""
    interpret = resolve_interpret(interpret)
    bm, bk, bn = tile if tile is not None else _matmul.DEFAULT_TILE
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if precision == "int8":
        xq, wq, sx, sw = quant.quantize_matmul_operands(x2, w)
        out = _matmul.gfid_matmul_int8(xq, wq, sx, sw, bm=bm, bk=bk, bn=bn,
                                       bias=bias, act=act,
                                       interpret=interpret)
    else:
        out = _matmul.gfid_matmul(x2, w, bm=bm, bk=bk, bn=bn, bias=bias,
                                  act=act, interpret=interpret)
    return out.reshape(*lead, w.shape[-1]).astype(x.dtype)


def gfid_conv1d_depthwise(x: jax.Array, w: jax.Array, *, causal: bool = True,
                          interpret: Optional[bool] = None) -> jax.Array:
    return _conv1d.gfid_conv1d_depthwise(
        x, w, causal=causal,
        interpret=resolve_interpret(interpret)).astype(x.dtype)


def paged_gather(pool: jax.Array, table: jax.Array, *,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Paged-KV block gather: pool (num_blocks, block_size, *feature) indexed
    by table (B, blocks_per_req) int32 -> (B, blocks_per_req * block_size,
    *feature). Bitwise identical to the XLA `jnp.take` reference — a gather
    is a copy, so there is no accumulation-order caveat."""
    return _paged.paged_gather(pool, table,
                               interpret=resolve_interpret(interpret))


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D) — GQA broadcast inside.
    Returns (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    n_kv = k.shape[2]
    g = h // n_kv
    qt = q.transpose(0, 2, 1, 3)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    out = _flash.flash_attention(qt, kt, vt, causal=causal, scale=scale,
                                 interpret=resolve_interpret(interpret))
    return out.transpose(0, 2, 1, 3)
