"""GFID — Generalized Fully-connected Inspired Dataflow (paper §2.1, §3).

Two artefacts live here:

1. `gfid_matrix` — the literal banded matrix M of Eq. (3): expressing a 1-D
   convolution row as an FC-style vector-matrix product. Used by tests to
   verify the dataflow algebra (Tables 1, Eq. 4-7) and by `analytics` to
   count active neurons per cycle.

2. `conv2d_gfid` / `conv1d_depthwise_gfid` — the TPU-native realization:
   convolution computed as `H_f * W_f` *shifted GEMM accumulations* over the
   input, never materializing the im2col expansion. Each input element is
   loaded once and reused W_f x C_out times — the paper's "input pixels are
   read once per clock cycle while weights loop on-chip", re-expressed for a
   memory hierarchy (HBM -> VMEM -> MXU) instead of shift registers.
   Where a conv has fewer than `FOLD_MAX_C_IN` input channels per group
   (the RGB stems), `conv2d_gfid` folds the stride phases and the filter
   taps into the contraction instead (`folds_taps`): a K = C_in product
   fills only C_in of a vector register's 128 lanes, so H_f * W_f of them
   would move and multiply ~40x the bytes the conv needs. `fold_operands` is
   that relayout, for both lowerings: this module contracts it with one
   einsum per height tap, and the Pallas wrapper
   (`repro.kernels.ops.gfid_conv2d`, fp32) passes it to the conv kernel
   at stride 1.

These are the pure-JAX reference semantics; `repro.kernels.gfid_conv` is the
Pallas TPU kernel with explicit BlockSpec VMEM tiling implementing the same
contract.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def gfid_matrix(weights: np.ndarray, n_out: int, stride: int = 1) -> np.ndarray:
    """Build the banded GFID matrix M of Eq. (3).

    Args:
      weights: 1-D filter row, shape (W_f,).
      n_out:  N — number of output pixels in the output-activation-map row.
      stride: S.

    Returns:
      M of shape (S*N + W_f - S, N): column j holds the filter (top-to-bottom
      W_1..W_Wf) starting at row j*S; y = x @ M computes the valid conv row.
    """
    w_f = int(weights.shape[0])
    rows = stride * n_out + w_f - stride
    mat = np.zeros((rows, n_out), dtype=weights.dtype)
    for j in range(n_out):
        mat[j * stride:j * stride + w_f, j] = weights
    return mat


def active_neurons_per_cycle(w_f: int, stride: int, n_out: int) -> int:
    """Max number of non-zero entries in any row of M — the paper's T."""
    mat = gfid_matrix(np.ones((w_f,)), n_out, stride)
    return int((mat != 0).sum(axis=1).max())


# ---------------------------------------------------------------------------
# Shifted-GEMM convolution (the TPU-native GFID lowering)
# ---------------------------------------------------------------------------

# Below this many input channels per group a per-tap GEMM fills too few
# lanes: fold the stride phases and taps into the contraction instead.
FOLD_MAX_C_IN = 16


def folds_taps(w_shape) -> bool:
    """True where `conv2d_gfid` and the fp32 Pallas wrapper lower a conv
    with filters of shape `w_shape` (HWIO, C_in per group) over
    `fold_operands`, its stride phases and width taps in the contraction;
    False where they run the band loop and the strided kernel."""
    return w_shape[2] < FOLD_MAX_C_IN


def fold_operands(x: jax.Array, w: jax.Array, stride: int, h_out: int,
                  w_out: int) -> Tuple[jax.Array, jax.Array]:
    """The operands of a valid conv of padded x (B, H, W, C) with w
    (H_f, W_f, C, O), the stride phases and width taps folded into the
    contraction: x_f (B, H_q, W_out, K) and w_f (T_h, K, O), with
    K = ceil(W_f/S)*S*S*C and T_h = ceil(H_f/S). Output row z is
    sum over p < T_h of x_f[:, z + p] @ w_f[p]: a stride-1 conv of x_f
    with a (T_h, 1) filter.

    Space to depth by S makes the conv stride-1 over ceil(H_f/S) x
    ceil(W_f/S) taps of S*S*C channels each; the filter is zero-padded to
    whole taps (exact zeros change no sum). The width taps are then
    concatenated on channels. `_conv_folded` (XLA) and the Pallas conv
    wrapper (`kernels.ops.gfid_conv2d`) both contract this relayout."""
    s = stride
    h_f, w_f, c, o = w.shape
    th, tw = -(-h_f // s), -(-w_f // s)
    hq, wq = h_out - 1 + th, w_out - 1 + tw
    b = x.shape[0]
    # Rows and columns past H and W meet only the zero taps.
    x = x[:, :hq * s, :wq * s]
    x = jnp.pad(x, ((0, 0), (0, hq * s - x.shape[1]),
                    (0, wq * s - x.shape[2]), (0, 0)))
    x = x.reshape(b, hq, s, wq, s, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, hq, wq, s * s * c)
    x = jnp.concatenate([x[:, :, q:q + w_out] for q in range(tw)], axis=-1)
    w = jnp.pad(w, ((0, th * s - h_f), (0, tw * s - w_f), (0, 0), (0, 0)))
    w = w.reshape(th, s, tw, s, c, o).transpose(0, 2, 1, 3, 4, 5)
    return x, w.reshape(th, tw * s * s * c, o)


def _conv_folded(x: jax.Array, w: jax.Array, stride: int, h_out: int,
                 w_out: int, accum_dtype: jnp.dtype) -> jax.Array:
    """Valid conv of padded x (B, H, W, C) with w (H_f, W_f, C, O) over
    `fold_operands`' relayout: one einsum per height tap."""
    x, w = fold_operands(x, w, stride, h_out, w_out)
    acc = jnp.zeros((x.shape[0], h_out, w_out, w.shape[-1]),
                    dtype=accum_dtype)
    for p in range(w.shape[0]):
        acc = acc + jnp.einsum("bhwc,cd->bhwd", x[:, p:p + h_out], w[p],
                               preferred_element_type=accum_dtype)
    return acc


def conv2d_gfid(x: jax.Array, w: jax.Array, stride: int = 1, pad: int = 0,
                groups: int = 1,
                accum_dtype: jnp.dtype = jnp.float32) -> jax.Array:
    """2-D convolution as H_f*W_f shifted GEMM accumulations (valid conv).

    Args:
      x: input activation maps, (B, H_in, W_in, C_in)   [NHWC].
      w: filters, (H_f, W_f, C_in // groups, C_out)     [HWIO].
      stride: S (same in both spatial dims, as in the paper's networks).
      pad: symmetric zero padding.
      groups: grouped convolution (AlexNet's historical 2-group layers).

    Returns:
      (B, H_out, W_out, C_out) in x.dtype.

    The inner loop is a Python loop over the (H_f, W_f) filter offsets —
    `H_f*W_f` is a small static constant (<= 121) — with each step a strided
    slice + GEMM over C_in. This is exactly the GFID banded-matrix product
    evaluated band-by-band: band (j, i) of M contributes
    X[:, zS+j, tS+i, :] @ W[j, i] to every output pixel (z, t).

    With fewer than `FOLD_MAX_C_IN` channels per group (`folds_taps`) that
    GEMM would fill C_in of 128 lanes, so each group instead runs
    `_conv_folded`: the same products, the stride phases and width taps
    folded into K, one einsum per ceil(H_f/S) height tap. Only the order
    of the sums changes.
    """
    if x.ndim != 4 or w.ndim != 4:
        raise ValueError(f"expected NHWC x and HWIO w, got {x.shape} {w.shape}")
    h_f, w_f, c_in_g, c_out = w.shape
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    b, h_in, w_in, c_in = x.shape
    if c_in // groups != c_in_g:
        raise ValueError(f"groups mismatch: {c_in}/{groups} != {c_in_g}")
    h_out = (h_in - h_f) // stride + 1
    w_out = (w_in - w_f) // stride + 1

    out_shards = []
    cg = c_in // groups
    og = c_out // groups
    fold = folds_taps(w.shape)
    for g in range(groups):
        xg = x[..., g * cg:(g + 1) * cg]
        if fold:
            out_shards.append(_conv_folded(
                xg, w[..., g * og:(g + 1) * og], stride, h_out, w_out,
                accum_dtype))
            continue
        acc = jnp.zeros((b, h_out, w_out, og), dtype=accum_dtype)
        for j in range(h_f):
            for i in range(w_f):
                # Shifted, strided view of the input: one band of M.
                xs = jax.lax.slice(
                    xg,
                    (0, j, i, 0),
                    (b, j + (h_out - 1) * stride + 1,
                     i + (w_out - 1) * stride + 1, cg),
                    (1, stride, stride, 1))
                wg = w[j, i, :, g * og:(g + 1) * og]
                acc = acc + jnp.einsum(
                    "bhwc,cd->bhwd", xs, wg,
                    preferred_element_type=accum_dtype)
        out_shards.append(acc)
    out = jnp.concatenate(out_shards, axis=-1) if groups > 1 else out_shards[0]
    return out.astype(x.dtype)


def conv2d_gfid_int8(xq: jax.Array, wq: jax.Array, stride: int = 1,
                     pad: int = 0, groups: int = 1) -> jax.Array:
    """int8 shifted-GEMM convolution with exact int32 accumulation.

    Same band-by-band GFID lowering as `conv2d_gfid`, but each per-tap
    contraction over C_in runs through `quant.int8_matmul_i32` (K-chunked
    fp32 dots, exact below 2²⁴, summed in int32). Exact integer
    accumulation is order-independent, so this matches the Pallas int8
    kernel and `conv2d_reference_int8` bitwise. Returns int32 accumulators
    (B, H_out, W_out, C_out); the caller applies the dequant epilogue.
    """
    from repro.core import quant
    if xq.ndim != 4 or wq.ndim != 4:
        raise ValueError(
            f"expected NHWC x and HWIO w, got {xq.shape} {wq.shape}")
    h_f, w_f, c_in_g, c_out = wq.shape
    if pad:
        xq = jnp.pad(xq, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    b, h_in, w_in, c_in = xq.shape
    if c_in // groups != c_in_g:
        raise ValueError(f"groups mismatch: {c_in}/{groups} != {c_in_g}")
    h_out = (h_in - h_f) // stride + 1
    w_out = (w_in - w_f) // stride + 1

    out_shards = []
    cg = c_in // groups
    og = c_out // groups
    for g in range(groups):
        xg = xq[..., g * cg:(g + 1) * cg]
        acc = jnp.zeros((b, h_out, w_out, og), dtype=jnp.int32)
        for j in range(h_f):
            for i in range(w_f):
                xs = jax.lax.slice(
                    xg,
                    (0, j, i, 0),
                    (b, j + (h_out - 1) * stride + 1,
                     i + (w_out - 1) * stride + 1, cg),
                    (1, stride, stride, 1))
                wg = wq[j, i, :, g * og:(g + 1) * og]
                acc = acc + quant.int8_matmul_i32(xs, wg)
        out_shards.append(acc)
    return jnp.concatenate(out_shards, axis=-1) if groups > 1 else \
        out_shards[0]


def conv2d_reference_int8(xq: jax.Array, wq: jax.Array, stride: int = 1,
                          pad: int = 0, groups: int = 1) -> jax.Array:
    """XLA's native int8 conv with int32 accumulation (exact, hence
    bitwise identical to `conv2d_gfid_int8` under any op ordering).
    Returns int32 accumulators; the caller applies the dequant epilogue."""
    return jax.lax.conv_general_dilated(
        xq, wq,
        window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.int32)


def conv1d_depthwise_xla(x: jax.Array, w: jax.Array, *,
                         causal: bool = True) -> jax.Array:
    """Depthwise 1-D conv as a single XLA conv op (feature_group_count=D).

    Functionally identical to `conv1d_depthwise_gfid`; used for large W_f
    (hubert's 128-tap positional conv) where the W_f-step shifted-add
    lowering explodes GSPMD compile time. On TPU both lower to
    `kernels.conv1d`.
    """
    b, l, d = x.shape
    w_f = w.shape[0]
    if causal:
        pad = (w_f - 1, 0)
    else:
        lpad = (w_f - 1) // 2
        pad = (lpad, w_f - 1 - lpad)
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w[:, None, :].astype(jnp.float32),
        window_strides=(1,), padding=(pad,),
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=d)
    return out.astype(x.dtype)


def conv1d_depthwise_gfid(x: jax.Array, w: jax.Array, *,
                          causal: bool = True) -> jax.Array:
    """Depthwise causal 1-D convolution via GFID shifted accumulation.

    The 1-D mode of the engine (paper Table 1 with C_in = 1 per channel):
    used by Mamba / xLSTM short convolutions (W_f = 4, S = 1, T = 4).

    Args:
      x: (B, L, D).
      w: (W_f, D) depthwise taps.
      causal: left-pad with W_f - 1 zeros (decode-consistent).
    Returns:
      (B, L, D).
    """
    w_f, d = w.shape
    if w_f > 8:
        return conv1d_depthwise_xla(x, w, causal=causal)
    if causal:
        xp = jnp.pad(x, ((0, 0), (w_f - 1, 0), (0, 0)))
    else:
        lpad = (w_f - 1) // 2
        xp = jnp.pad(x, ((0, 0), (lpad, w_f - 1 - lpad), (0, 0)))
    l = x.shape[1]
    acc = jnp.zeros(x.shape, dtype=jnp.float32)
    for i in range(w_f):
        acc = acc + xp[:, i:i + l, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return acc.astype(x.dtype)


def fc_gfid(x: jax.Array, w: jax.Array,
            accum_dtype: jnp.dtype = jnp.float32) -> jax.Array:
    """FC mode of the engine (paper §4.1.6): plain GEMM, UF = 100%.

    x: (..., n); w: (n, m). The degenerate W_f = 1, S = 1 mode — on TPU this
    and `conv2d_gfid` share one Pallas kernel (`repro.kernels`).
    """
    return jnp.einsum("...n,nm->...m", x, w,
                      preferred_element_type=accum_dtype).astype(x.dtype)


def conv2d_reference(x: jax.Array, w: jax.Array, stride: int = 1,
                     pad: int = 0, groups: int = 1) -> jax.Array:
    """XLA's own conv (the 'direct' baseline the GFID lowering must match)."""
    return jax.lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.float32).astype(x.dtype)
