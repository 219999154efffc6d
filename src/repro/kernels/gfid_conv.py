"""GFID convolution as a Pallas TPU kernel.

The TPU-native lowering of the paper's dataflow (DESIGN.md §2): one output
row per grid step (the paper's 1-D tile sweep, N_eff = W_out), the input
row resident in VMEM and read from HBM exactly once per (C_out tile), the
filter taps looping from VMEM registers (the weight-generator analogue),
and the W_f shifted GEMM accumulations hitting the MXU with fp32
accumulation (the 24-bit partial-sum scratchpad analogue).

Grid: (B, H_out, n_cout, H_f, n_cin) — the two innermost dims revisit the
same output block consecutively, accumulating in place, exactly like the
paper's PEs accumulate C_in x H_f partial products per output pixel
(§4: "this procedure is repeated H_f x C_in times").

Stride S > 1: the wrapper splits each input row into its S stride phases,
(B, H, W, C) -> (B, H, S, ceil(W/S), C), so filter tap i reads phase i % S
at offset i // S as one contiguous slice. Mosaic refuses a strided slice
of a loaded vector; the phase split keeps the tap order, and so the
accumulation order, of the plain strided loop. A conv with fewer than
`gfid.FOLD_MAX_C_IN` input channels per group (AlexNet's conv1) never
reaches this kernel strided: `ops.gfid_conv2d` passes it the relayout
`gfid.fold_operands`, a stride-1 conv with one width tap over
K = ceil(W_f/S)*S*S*C, since a C-lane minor dim would be padded to 128
lanes here.

Channel tiling `(c_in_block, c_out_block)` is an explicit knob (the
per-layer resource adaptation of `engine.tune`): ragged channel counts fall
back to whole-channel blocks. Optional fused epilogue: `bias` (C_out,)
and/or `act` ("relu" | "gelu") applied to the fp32 accumulator on the last
(H_f, C_in-tile) grid step, before the single writeback.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import INT8_EXACT_K
from repro.kernels.epilogue import ACTS, dequant_epilogue

DEFAULT_CONV_TILE = (512, 256)      # (c_in_block, c_out_block)


def _phase_split(x: jax.Array, stride: int) -> jax.Array:
    """(B, H, W, C) -> (B, H, S, ceil(W/S), C): phase p holds the columns
    p, p + S, p + 2S, ... of every row. The zero columns padded on to make
    W divisible by S are never read by a tap of a valid output column."""
    b, h, w, c = x.shape
    if stride == 1:
        return x.reshape(b, h, 1, w, c)
    wp = -(-w // stride)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, wp * stride - w), (0, 0)))
    return x.reshape(b, h, wp, stride, c).transpose(0, 1, 3, 2, 4)


def _tap(x_ref, i: int, stride: int, w_out: int) -> jax.Array:
    """The (W_out, C_in_blk) input slice filter tap `i` multiplies: output
    column o reads input column o * S + i, i.e. phase i % S, row o + i // S."""
    q, p = divmod(i, stride)
    return x_ref[0, 0, p, q:q + w_out, :]


def _x_spec(xp: jax.Array, stride: int, cib: int) -> pl.BlockSpec:
    """One phase-split input row, all S phases, one C_in block."""
    return pl.BlockSpec((1, 1, stride, xp.shape[3], cib),
                        lambda bi, z, co, j, k: (bi, z * stride + j, 0, 0, k))


def _accumulate(x_ref, w_ref, o_ref, *, w_f: int, stride: int, w_out: int):
    acc = jnp.zeros((w_out, o_ref.shape[-1]), jnp.float32)
    for i in range(w_f):                      # the W_f weight-register loop
        acc += jnp.dot(_tap(x_ref, i, stride, w_out), w_ref[0, i],
                       preferred_element_type=jnp.float32)
    o_ref[0, 0] += acc


def _kernel(x_ref, w_ref, o_ref, *, w_f: int, stride: int, w_out: int):
    j = pl.program_id(3)
    k = pl.program_id(4)

    @pl.when((j == 0) & (k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _accumulate(x_ref, w_ref, o_ref, w_f=w_f, stride=stride, w_out=w_out)


def _kernel_epilogue(x_ref, w_ref, b_ref, o_ref, *, w_f: int, stride: int,
                     w_out: int, last_j: int, last_k: int,
                     act: Optional[str]):
    j = pl.program_id(3)
    k = pl.program_id(4)

    @pl.when((j == 0) & (k == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _accumulate(x_ref, w_ref, o_ref, w_f=w_f, stride=stride, w_out=w_out)

    @pl.when((j == last_j) & (k == last_k))
    def _epilogue():
        y = o_ref[0, 0] + b_ref[...]          # (W_out, cob) + (1, cob)
        o_ref[0, 0] = ACTS[act](y) if act is not None else y


def gfid_conv2d_nhwc(x: jax.Array, w: jax.Array, *, stride: int = 1,
                     c_in_block: int = DEFAULT_CONV_TILE[0],
                     c_out_block: int = DEFAULT_CONV_TILE[1],
                     bias: Optional[jax.Array] = None,
                     act: Optional[str] = None,
                     interpret: bool = False) -> jax.Array:
    """Valid conv (pad outside). x: (B, H_in, W_in, C_in) already padded;
    w: (H_f, W_f, C_in, C_out). Returns (B, H_out, W_out, C_out) fp32.

    `bias` (C_out,) and `act` ("relu" | "gelu") run as a fused epilogue in
    the fp32 accumulator before writeback.
    """
    if act is not None and act not in ACTS:
        raise ValueError(f"unknown epilogue activation {act!r}; "
                         f"expected one of {sorted(ACTS)}")
    b, h_in, w_in, c_in = x.shape
    h_f, w_f, _, c_out = w.shape
    h_out = (h_in - h_f) // stride + 1
    w_out = (w_in - w_f) // stride + 1

    cib = min(c_in_block, c_in)
    cob = min(c_out_block, c_out)
    if c_in % cib or c_out % cob:
        # fall back to whole-channel blocks for ragged channel counts
        cib, cob = c_in, c_out
    n_ci, n_co = c_in // cib, c_out // cob

    grid = (b, h_out, n_co, h_f, n_ci)
    xp = _phase_split(x, stride)
    x_spec = _x_spec(xp, stride, cib)
    w_spec = pl.BlockSpec((1, w_f, cib, cob),
                          lambda bi, z, co, j, k: (j, 0, k, co))
    o_spec = pl.BlockSpec((1, 1, w_out, cob),
                          lambda bi, z, co, j, k: (bi, z, 0, co))
    out_shape = jax.ShapeDtypeStruct((b, h_out, w_out, c_out), jnp.float32)
    if bias is None and act is None:
        return pl.pallas_call(
            functools.partial(_kernel, w_f=w_f, stride=stride, w_out=w_out),
            grid=grid, in_specs=[x_spec, w_spec], out_specs=o_spec,
            out_shape=out_shape, interpret=interpret)(xp, w)
    bv = (jnp.zeros((c_out,), jnp.float32) if bias is None
          else bias.astype(jnp.float32)).reshape(1, c_out)
    b_spec = pl.BlockSpec((1, cob), lambda bi, z, co, j, k: (0, co))
    return pl.pallas_call(
        functools.partial(_kernel_epilogue, w_f=w_f, stride=stride,
                          w_out=w_out, last_j=h_f - 1, last_k=n_ci - 1,
                          act=act),
        grid=grid, in_specs=[x_spec, w_spec, b_spec], out_specs=o_spec,
        out_shape=out_shape, interpret=interpret)(xp, w, bv)


def _accumulate_int8(x_ref, w_ref, acc_ref, *, w_f: int, stride: int,
                     w_out: int):
    """Exact int32 accumulation of one (H_f tap, C_in block) contribution.

    Mirrors `_accumulate`, but the per-tap dots run on int8 values cast to
    fp32, chunked along C_in at INT8_EXACT_K so every fp32 partial is an
    exactly-represented integer (< 2²⁴) — the in-kernel twin of
    `core.quant.int8_matmul_i32`. Order-independent integer math keeps the
    Pallas result bitwise identical to the xla/ref quantized paths."""
    cib = x_ref.shape[-1]
    acc = jnp.zeros(acc_ref.shape, jnp.int32)
    for i in range(w_f):
        xs = _tap(x_ref, i, stride, w_out)    # (W_out, C_in_blk) int8
        wv = w_ref[0, i]                      # (C_in_blk, cob) int8
        for c0 in range(0, max(cib, 1), INT8_EXACT_K):
            acc += jnp.dot(
                xs[:, c0:c0 + INT8_EXACT_K].astype(jnp.float32),
                wv[c0:c0 + INT8_EXACT_K, :].astype(jnp.float32),
                preferred_element_type=jnp.float32).astype(jnp.int32)
    acc_ref[...] += acc


def _kernel_int8(x_ref, w_ref, sx_ref, sw_ref, b_ref, o_ref, acc_ref, *,
                 w_f: int, stride: int, w_out: int, last_j: int,
                 last_k: int, has_bias: bool, act: Optional[str]):
    j = pl.program_id(3)
    k = pl.program_id(4)

    @pl.when((j == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_int8(x_ref, w_ref, acc_ref, w_f=w_f, stride=stride,
                     w_out=w_out)

    @pl.when((j == last_j) & (k == last_k))
    def _epilogue():
        scale = sx_ref[0] * sw_ref[...]       # (1, 1) * (1, cob)
        o_ref[0, 0] = dequant_epilogue(
            acc_ref[...], scale, b_ref[...] if has_bias else None, act)


def gfid_conv2d_nhwc_int8(xq: jax.Array, wq: jax.Array, sx: jax.Array,
                          sw: jax.Array, *, stride: int = 1,
                          c_in_block: int = DEFAULT_CONV_TILE[0],
                          c_out_block: int = DEFAULT_CONV_TILE[1],
                          bias: Optional[jax.Array] = None,
                          act: Optional[str] = None,
                          interpret: bool = False) -> jax.Array:
    """int8 valid conv (pad outside). xq: (B, H_in, W_in, C_in) int8,
    already padded (int8 zero pads are exact); wq: (H_f, W_f, C_in, C_out)
    int8. `sx`: (B, 1) per-example activation scales; `sw`: (1, C_out)
    per-channel weight scales. Returns (B, H_out, W_out, C_out) fp32.

    Accumulates exactly in an int32 VMEM scratch across the (H_f, C_in
    tile) grid steps and applies the fused dequant+bias+act epilogue on
    the last step — quantized conv+bias+relu stays one kernel launch.
    """
    if act is not None and act not in ACTS:
        raise ValueError(f"unknown epilogue activation {act!r}; "
                         f"expected one of {sorted(ACTS)}")
    b, h_in, w_in, c_in = xq.shape
    h_f, w_f, _, c_out = wq.shape
    h_out = (h_in - h_f) // stride + 1
    w_out = (w_in - w_f) // stride + 1

    cib = min(c_in_block, c_in)
    cob = min(c_out_block, c_out)
    if c_in % cib or c_out % cob:
        cib, cob = c_in, c_out
    n_ci, n_co = c_in // cib, c_out // cob

    grid = (b, h_out, n_co, h_f, n_ci)
    xp = _phase_split(xq, stride)
    x_spec = _x_spec(xp, stride, cib)
    w_spec = pl.BlockSpec((1, w_f, cib, cob),
                          lambda bi, z, co, j, k: (j, 0, k, co))
    # (B, 1, 1): a (1, 1) block of a (B, 1) array breaks the TPU's (8, 128)
    # block rule for any B > 1; here the block's last two dims are whole
    sx_spec = pl.BlockSpec((1, 1, 1), lambda bi, z, co, j, k: (bi, 0, 0))
    sw_spec = pl.BlockSpec((1, cob), lambda bi, z, co, j, k: (0, co))
    b_spec = pl.BlockSpec((1, cob), lambda bi, z, co, j, k: (0, co))
    o_spec = pl.BlockSpec((1, 1, w_out, cob),
                          lambda bi, z, co, j, k: (bi, z, 0, co))
    has_bias = bias is not None
    bv = (jnp.zeros((c_out,), jnp.float32) if bias is None
          else bias.astype(jnp.float32)).reshape(1, c_out)
    return pl.pallas_call(
        functools.partial(_kernel_int8, w_f=w_f, stride=stride,
                          w_out=w_out, last_j=h_f - 1, last_k=n_ci - 1,
                          has_bias=has_bias, act=act),
        grid=grid,
        in_specs=[x_spec, w_spec, sx_spec, sw_spec, b_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, h_out, w_out, c_out),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((w_out, cob), jnp.int32)],
        interpret=interpret)(xp, wq, sx.astype(jnp.float32).reshape(b, 1, 1),
                             sw.astype(jnp.float32), bv)
