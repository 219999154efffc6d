"""Plan-guided kernel autotuner for the Pallas backend.

The paper's whole argument (§4, Table 4) is that a fixed PE array only
sustains high utilization when the *schedule* adapts per layer. The Pallas
kernels used to do the opposite — one module-level (bm, bk, bn) = (256,
512, 256) GEMM blocking and one (512, 256) conv channel blocking for every
layer shape. This module closes the loop:

  * per op (keyed by a *stable* hash of the canonicalized `OpSpec` plus
    backend and accumulation dtype), generate a small grid of MXU-aligned
    candidate tile configs,
  * prune it analytically (padding waste + grid-step launch overhead +
    VMEM footprint, the software analogue of the plan's occupancy model) to
    ~6-10 candidates,
  * benchmark the survivors min-of-N wallclock on the real kernel, and
  * persist the winner to a versioned JSON cache,
    ``.tuning/<device_kind>.json`` — committable, so CI and fresh clones
    run on cached winners and never pay the tuning cost.

`EngineConfig.tuning` selects the behavior: "off" (kernel defaults),
"cached" (use the cache, fall back silently on a miss) or "autotune"
(benchmark misses at `engine.compile` time and persist them). Resolution
happens *outside* jit: `engine.compile` pins each op's `tile_config` into
its `exec_pairs`; the eager API performs cached lookups only.

Batch invariance: dense keys drop the row (M) dim and conv keys the batch
dim, so a batch-8 bucket and a batch-1 call always resolve to the same
tile config. Since row/column tiling never changes accumulation order
(only the K blocking does, and it is shared), batched execution stays
bitwise identical per row to batch-1 execution — the `serve.scheduler`
parity contract survives tuning.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core import modes
from repro.core.quant import INT8_EXACT_K
from repro.engine import plan as planlib
from repro.engine.config import EngineConfig

Tile = Tuple[int, ...]

# v2: tile keys and candidate grids gained a precision dimension (int8
# tiles align the M block to the 32-row int8 sublane); a v1 cache no
# longer matches and degrades cleanly to the kernel defaults.
CACHE_VERSION = 2
CACHE_DIR_ENV = "REPRO_TUNING_DIR"
MAX_CANDIDATES = 10         # benchmarked per op after analytic pruning
TIMING_REPEATS = 3          # min-of-N wallclock per candidate

# Analytic pruning weights: one grid step is priced like LAUNCH_MACS
# MAC-equivalents (kernel launch / revisit overhead), so the score
# `padded_macs + LAUNCH_MACS * steps` trades tile-quantization waste
# against launch count — the same tension the plan's occupancy model
# (mxu_occupancy) captures for the MMIE array.
LAUNCH_MACS = 1 << 20

# The scoped-VMEM limit the Pallas kernels compile under. They pass no
# `vmem_limit_bytes`, so Mosaic's default for TPU v5e holds: 16 MiB. A
# (512, 1792, 512) fp32 GEMM tile, 17 MiB with its buffers, is refused with
# RESOURCE_EXHAUSTED; (512, 1536, 512), 15 MiB, compiles. Pallas double-
# buffers every pipelined block, so footprints below count inputs and
# outputs twice. tests/test_tpu_compile.py compiles the largest kept tile.
SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def repo_root() -> Optional[Path]:
    """The checkout this module lives in (the nearest parent holding a
    pyproject.toml or .git), or None for an installed package."""
    for parent in Path(__file__).resolve().parents:
        if (parent / "pyproject.toml").exists() or (parent / ".git").exists():
            return parent
    return None


def _default_dir() -> Path:
    """`.tuning/` anchored at the repo root when one is detectable, else
    CWD-relative. Anchoring means `tuning="cached"` finds the committed
    cache — and `tuning="autotune"` extends it — no matter which directory
    the process was launched from; the CWD fallback covers
    installed-package layouts."""
    root = repo_root()
    return root / ".tuning" if root is not None else Path(".tuning")


_dir_override: Optional[Path] = None  # analyze: allow[mutable-global] test-only cache-dir override
_MEMO: Dict[str, dict] = {}  # device_kind -> cache # analyze: allow[mutable-global] read-through memo


# ---------------------------------------------------------------------------
# Cache location / persistence
# ---------------------------------------------------------------------------

def cache_dir() -> Path:
    """Directory holding `<device_kind>.json` tile caches. Resolution:
    `set_cache_dir()` override, then $REPRO_TUNING_DIR, then `.tuning/` at
    the detected repo root (CWD-relative if no root is detectable)."""
    if _dir_override is not None:
        return _dir_override
    env = os.environ.get(CACHE_DIR_ENV)
    return Path(env) if env else _default_dir()


def set_cache_dir(path: Optional[os.PathLike]) -> None:
    """Override the cache directory (None restores the default resolution).
    Drops the in-memory cache memo so the next lookup re-reads from disk."""
    global _dir_override
    _dir_override = Path(path) if path is not None else None
    _MEMO.clear()


def device_kind() -> str:
    """The accelerator identity the cache is keyed by, filename-safe
    (e.g. "cpu", "tpu_v5_lite")."""
    import jax
    kind = jax.devices()[0].device_kind
    return "".join(c if c.isalnum() else "_" for c in kind.lower())


def cache_path(kind: Optional[str] = None) -> Path:
    return cache_dir() / f"{kind or device_kind()}.json"


def load_cache(kind: Optional[str] = None) -> dict:
    """The (memoized) cache for `kind`. A missing, unreadable, corrupted or
    stale-versioned file degrades to an empty cache — tuning then falls
    back to the kernel defaults instead of failing the run."""
    kind = kind or device_kind()
    if kind in _MEMO:
        return _MEMO[kind]
    cache = {"version": CACHE_VERSION, "device_kind": kind, "entries": {}}
    path = cache_path(kind)
    try:
        raw = json.loads(path.read_text())
        if (isinstance(raw, dict) and raw.get("version") == CACHE_VERSION
                and isinstance(raw.get("entries"), dict)):
            cache = raw
    except (OSError, ValueError):
        pass
    _MEMO[kind] = cache
    return cache


def save_cache(kind: Optional[str] = None) -> Path:
    """Write the in-memory cache for `kind` to disk, crash-safely.

    The payload lands in a uniquely-named temp file in the cache
    directory (same filesystem, so the final `os.replace` is atomic),
    fsync'd before the rename — a crash mid-write leaves either the old
    cache or the new one, never a truncated JSON, and two concurrent
    savers never interleave into one file. The temp file is unlinked on
    any failure."""
    import tempfile
    kind = kind or device_kind()
    cache = load_cache(kind)
    path = cache_path(kind)
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(json.dumps(cache, indent=2, sort_keys=True) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


# ---------------------------------------------------------------------------
# Stable op keys
# ---------------------------------------------------------------------------

def _canonical_dense(op: planlib.OpSpec) -> Optional[Tuple[int, int, int]]:
    """(M, K, N) of a dense op the blocked-GEMM kernel can run (the same
    `plan.canonical_gemm` test dispatch._pallas_einsum uses), else None."""
    st = planlib.parse_einsum(op.spec, len(op.x_shape), len(op.w_shape))
    if not planlib.canonical_gemm(st, len(op.w_shape)):
        return None
    dims = dict(zip(st.x_labels, op.x_shape))
    dims.update(zip(st.w_labels, op.w_shape))
    k = dims[st.contract[0]]
    n = math.prod(dims[l] for l in st.w_free)
    m = math.prod(dims[l] for l in st.x_free)
    return int(m), int(k), int(n)


def tile_key(op: planlib.OpSpec, backend: str, accum: Optional[str],
             precision: str = "fp32") -> Optional[str]:
    """Stable (process-independent) cache key for one tunable op, or None
    when the op has no tile knob on `backend`.

    Dense keys are (K, N) only — the row count M is execution detail (it
    never changes accumulation order, and dropping it lets every batch
    bucket share one config). Conv keys drop the batch dim for the same
    reason. `precision` is a key dimension: the int8 kernels have their
    own sublane alignment and arithmetic cost, so fp32 winners must not
    leak onto the quantized path (or vice versa). The hash is sha1 over
    the canonical JSON, so keys survive process restarts and hash
    randomization (unlike `hash(op)`).
    """
    if backend != "pallas":
        return None
    if op.kind == "dense":
        mkn = _canonical_dense(op)
        if mkn is None:
            return None
        ident = ["dense", mkn[1], mkn[2]]
    elif op.kind == "conv2d":
        b, h_in, w_in, c_in = op.x_shape
        ident = ["conv2d", h_in, w_in, c_in, list(op.w_shape),
                 op.stride, op.pad, op.groups]
    else:
        return None
    ident += [backend, accum or "default", precision]
    blob = json.dumps(ident, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:16]


def _accum_label(cfg: EngineConfig) -> Optional[str]:
    return cfg.accum


# ---------------------------------------------------------------------------
# Candidate generation (analytically pruned)
# ---------------------------------------------------------------------------

_round_up = modes.round_up


def _buf(rows: int, cols: int, elt: int) -> int:
    """VMEM bytes of a (rows, cols) buffer laid out in (sublane, 128) tiles:
    the sublane packs 32 bytes per lane column (8 rows fp32, 32 int8)."""
    return (_round_up(max(rows, 1), 32 // elt) * _round_up(max(cols, 1), 128)
            * elt)


def dense_vmem_bytes(tile: Sequence[int], precision: str = "fp32") -> int:
    """VMEM footprint of one (bm, bk, bn) GEMM grid step: double-buffered
    operand, bias and output blocks plus the dot's (bm, bn) fp32 result.
    int8 adds the int32 accumulator scratch, the lane-padded (bm, 1) row
    scales, and the fp32 casts of one INT8_EXACT_K contraction chunk."""
    bm, bk, bn = (int(v) for v in tile)
    out = 2 * _buf(bm, bn, 4) + _buf(bm, bn, 4)
    if precision != "int8":
        return 2 * (_buf(bm, bk, 4) + _buf(bk, bn, 4) + _buf(1, bn, 4)) + out
    kc = min(bk, INT8_EXACT_K)
    return (2 * (_buf(bm, bk, 1) + _buf(bk, bn, 1) + _buf(bm, 1, 4)
                 + 2 * _buf(1, bn, 4))
            + out + _buf(bm, bn, 4) + _buf(bm, kc, 4) + _buf(kc, bn, 4))


def conv_vmem_bytes(op: planlib.OpSpec, tile: Sequence[int]) -> int:
    """VMEM footprint of one GFID conv grid step at channel tile (cib, cob):
    the double-buffered phase-split input row, W_f filter taps, bias row
    and output row, the fp32 accumulator, and one more output-row buffer
    for the int8 kernel's int32 scratch. Counted at 4 bytes per element,
    which bounds the int8 kernel's blocks and their fp32 casts too."""
    cib, cob = (int(v) for v in tile)
    w_in, w_f, s = op.x_shape[2] + 2 * op.pad, op.w_shape[1], op.stride
    w_out = (w_in - w_f) // s + 1
    x_row = s * _buf(-(-w_in // s), cib, 4)
    taps = w_f * _buf(cib, cob, 4)
    out_row = _buf(w_out, cob, 4)
    return 2 * (x_row + taps + _buf(1, cob, 4) + out_row) + 2 * out_row


@dataclasses.dataclass(frozen=True)
class Candidate:
    tile: Tile
    score: float        # analytic cost, lower is better (pruning only)


def _dense_candidates(m: int, k: int, n: int,
                      precision: str = "fp32") -> List[Candidate]:
    """MXU-aligned (bm, bk, bn) grid for an (M, K) @ (K, N) GEMM, scored by
    padded MACs + launch overhead, guarded by `SCOPED_VMEM_BYTES`. int8
    candidates align the M block to the 32-row int8 sublane (fp32 packs 8
    rows per sublane, int8 packs 32)."""
    sub = 32 if precision == "int8" else 8
    mp8, kp, np_ = _round_up(m, sub), _round_up(k, 128), _round_up(n, 128)
    bms = sorted({v for v in (sub, 64, 128, 256, mp8)
                  if v <= mp8 and v % sub == 0})
    bks = sorted({v for v in (128, 256, 512, 1024, kp) if v <= kp})
    bns = sorted({v for v in (128, 256, 512, 1024, np_) if v <= np_})
    out: List[Candidate] = []
    for bm in bms:
        for bk in bks:
            for bn in bns:
                if dense_vmem_bytes((bm, bk, bn),
                                    precision) > SCOPED_VMEM_BYTES:
                    continue
                mp = _round_up(m, bm)
                kpp = _round_up(k, bk)
                npp = _round_up(n, bn)
                steps = (mp // bm) * (kpp // bk) * (npp // bn)
                out.append(Candidate((bm, bk, bn),
                                     mp * kpp * npp + LAUNCH_MACS * steps))
    return out


def _divisor_tiles(c: int) -> List[int]:
    """Channel-block candidates for a conv side: 128-multiples dividing
    `c`, plus `c` itself (the kernel's whole-channel fallback)."""
    opts = {c}
    for v in (128, 256, 512):
        if v < c and c % v == 0:
            opts.add(v)
    return sorted(opts)


def _conv_candidates(op: planlib.OpSpec) -> List[Candidate]:
    b, h_in, w_in, c_in = op.x_shape
    h_f, w_f, cg, c_out = op.w_shape
    og = c_out // op.groups
    h_out = (h_in + 2 * op.pad - h_f) // op.stride + 1
    w_out = (w_in + 2 * op.pad - w_f) // op.stride + 1
    out: List[Candidate] = []
    for cib in _divisor_tiles(cg):
        for cob in _divisor_tiles(og):
            if conv_vmem_bytes(op, (cib, cob)) > SCOPED_VMEM_BYTES:
                continue
            steps = (op.groups * b * h_out * (og // cob) * h_f * (cg // cib))
            # x rows are re-read once per C_out tile; w once per step
            traffic = (steps * (w_in + 2 * op.pad) * cib
                       + steps * w_f * cib * cob)
            out.append(Candidate((cib, cob),
                                 traffic + LAUNCH_MACS * steps))
    return out


def candidates_for(op: planlib.OpSpec, limit: int = MAX_CANDIDATES,
                   precision: str = "fp32") -> List[Tile]:
    """The analytically-pruned candidate tiles for `op`, best-scored first
    (what `autotune_op` actually benchmarks). Conv channel tilings are
    precision-independent (the lane dim is 128 either way); dense M blocks
    follow the precision's sublane."""
    if op.kind == "dense":
        mkn = _canonical_dense(op)
        if mkn is None:
            return []
        cands = _dense_candidates(*mkn, precision=precision)
    elif op.kind == "conv2d":
        cands = _conv_candidates(op)
    else:
        return []
    cands.sort(key=lambda c: (c.score, c.tile))
    return [c.tile for c in cands[:limit]]


# ---------------------------------------------------------------------------
# Wallclock benchmarking
# ---------------------------------------------------------------------------

def _bench_once(fn, args, repeats: int) -> float:
    import jax
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))        # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def benchmark_tile(op: planlib.OpSpec, tile: Tile, cfg: EngineConfig,
                   repeats: int = TIMING_REPEATS,
                   precision: str = "fp32") -> float:
    """Min-of-N wallclock of the real Pallas kernel for `op` at `tile`,
    on the precision's actual path (quantize + int8 kernel when int8)."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    if op.kind == "dense":
        m, k, n = _canonical_dense(op)
        x = jnp.ones((m, k), jnp.float32)
        w = jnp.ones((k, n), jnp.float32)
        fn = lambda x, w: kops.gfid_matmul(     # noqa: E731
            x, w, tile=tile, interpret=cfg.interpret, precision=precision)
        return _bench_once(fn, (x, w), repeats)
    if op.kind == "conv2d":
        x = jnp.ones(op.x_shape, jnp.float32)
        w = jnp.ones(op.w_shape, jnp.float32)
        fn = lambda x, w: kops.gfid_conv2d(     # noqa: E731
            x, w, stride=op.stride, pad=op.pad, groups=op.groups,
            tile=tile, interpret=cfg.interpret, precision=precision)
        return _bench_once(fn, (x, w), repeats)
    raise ValueError(f"op kind {op.kind!r} has no tile knob")


def _op_desc(op: planlib.OpSpec) -> str:
    if op.kind == "dense":
        m, k, n = _canonical_dense(op)
        return f"dense {k}x{n}"
    return (f"conv2d {op.x_shape[1]}x{op.x_shape[2]}x{op.x_shape[3]}"
            f" w{op.w_shape[0]}x{op.w_shape[1]}->{op.w_shape[3]}"
            f" s{op.stride} p{op.pad} g{op.groups}")


# ---------------------------------------------------------------------------
# Resolution: lookup / autotune / attach
# ---------------------------------------------------------------------------

def lookup(op: planlib.OpSpec, cfg: EngineConfig,
           precision: str = "fp32") -> Optional[Tile]:
    """Cache-only tile resolution (never benchmarks; safe at trace time)."""
    key = tile_key(op, "pallas", _accum_label(cfg), precision)
    if key is None:
        return None
    entry = load_cache().get("entries", {}).get(key)
    if not isinstance(entry, dict):
        return None
    tile = entry.get("tile")
    want = 3 if op.kind == "dense" else 2
    if (isinstance(tile, (list, tuple)) and len(tile) == want
            and all(isinstance(v, int) and v > 0 for v in tile)):
        return tuple(tile)
    return None


def autotune_op(op: planlib.OpSpec, cfg: EngineConfig,
                repeats: int = TIMING_REPEATS,
                precision: str = "fp32") -> Optional[Tile]:
    """Benchmark the pruned candidate grid for `op`, persist and return the
    winner (None when the op has no tile knob). Cached winners are reused —
    re-tuning an already-tuned op is a dict hit, not a re-benchmark."""
    key = tile_key(op, "pallas", _accum_label(cfg), precision)
    if key is None:
        return None
    cached = lookup(op, cfg, precision)
    if cached is not None:
        return cached
    cands = candidates_for(op, precision=precision)
    if not cands:
        return None
    timed = [(benchmark_tile(op, t, cfg, repeats, precision), t)
             for t in cands]
    best_wall, best = min(timed, key=lambda p: (p[0], p[1]))
    kind = device_kind()
    load_cache(kind)["entries"][key] = {
        "kind": op.kind,
        "tile": list(best),
        "wall_us": round(best_wall * 1e6, 1),
        "candidates": len(timed),
        "precision": precision,
        "desc": _op_desc(op),
    }
    save_cache(kind)
    return best


def attach(op: planlib.OpSpec, plan: planlib.EnginePlan, cfg: EngineConfig,
           *, allow_autotune: bool = False) -> planlib.EnginePlan:
    """The plan with its tuned tile pinned, per `cfg.tuning`.

    "off" (or a non-Pallas backend, or an untunable op) returns the plan
    unchanged; "cached" pins a cache hit; "autotune" additionally
    benchmarks misses — but only when `allow_autotune` is set, i.e. from
    `engine.compile`, never from the eager per-op path (benchmarking from
    inside a traced function would be meaningless).
    """
    if (cfg.tuning == "off" or plan.backend != "pallas"
            or plan.tile_config is not None):
        return plan
    prec = plan.precision           # pinned before tile resolution (api /
    tile = lookup(op, cfg, prec)    # engine.compile), so the key sees it
    if tile is None and allow_autotune and cfg.tuning == "autotune":
        tile = autotune_op(op, cfg, precision=prec)
    if tile is None:
        return plan
    return dataclasses.replace(plan, tile_config=tile)


def tune_program(ops: Sequence[planlib.OpSpec], cfg: EngineConfig) -> int:
    """Autotune every tunable Pallas op in `ops`; returns the number of
    ops that now have a cache entry (convenience for warm-up scripts;
    `engine.compile` under `EngineConfig(tuning="autotune")` does the same
    for the ops it compiles)."""
    tuned = 0
    for op in ops:
        backend = (planlib.auto_backend(op, cfg.backend)
                   if cfg.policy == "auto" else cfg.backend)
        prec = ("int8" if cfg.precision == "int8"
                and planlib.supports_int8(op) else "fp32")
        if tile_key(op, backend, _accum_label(cfg), prec) is None:
            continue
        if autotune_op(op, cfg, precision=prec) is not None:
            tuned += 1
    return tuned
