"""Tile / tuning-cache contract rules (layer 1) + the `.tuning/` doctor.

The PR-4/PR-8 autotuner contracts, all decidable without benchmarking:

  * a pinned dense tile must stay MXU-aligned for its precision — the M
    block on the precision's sublane (8 rows fp32, 32 rows int8), the K/N
    blocks on the 128 lane — or the kernel pays pad/repack on every step;
  * a pinned tile's VMEM footprint (`tune.dense_vmem_bytes`: double-
    buffered blocks plus accumulators) must fit `tune.SCOPED_VMEM_BYTES`,
    the limit the kernels compile under and the candidate generator's
    guard — a hand-edited or stale cache entry can violate it;
  * a cache entry's recorded precision must agree with the precision the
    key was derived for (fp32 winners must not leak onto the int8 path).

`doctor_cache` audits a whole `.tuning/<device_kind>.json` file entry by
entry (structure, alignment, VMEM, precision) and classifies keys that no
registered program derives as info-level "unreferenced" (benchmark
workloads legitimately create such entries, so they are never errors).
With `repair=True` it drops error-class entries and returns the cleaned
cache dict for the caller to persist.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from repro.engine import tune as tunelib
from repro.engine.config import EngineConfig
from repro.engine.plan import EnginePlan, OpSpec

from repro.analyze.diagnostics import Diagnostic, Rule, finding, register_rule

register_rule(Rule(
    id="tile-misaligned", severity="error", layer="tile",
    contract="a pinned dense tile must be MXU-aligned for its precision: "
             "bm a multiple of the sublane (8 fp32 / 32 int8), bk and bn "
             "multiples of the 128 lane"))
register_rule(Rule(
    id="tile-vmem-overflow", severity="error", layer="tile",
    contract="a pinned tile's VMEM footprint (double-buffered blocks + "
             "accumulators) must fit tune.SCOPED_VMEM_BYTES, the scoped "
             "limit the kernels compile under"))
register_rule(Rule(
    id="tile-precision-mismatch", severity="error", layer="tile",
    contract="a cache entry's recorded precision must match the precision "
             "its key was derived for — fp32 winners must not resolve onto "
             "the int8 kernel path or vice versa"))
register_rule(Rule(
    id="cache-malformed-entry", severity="error", layer="tile",
    contract="every .tuning/ cache entry must carry a well-formed tile "
             "(positive-int tuple of the kind's arity) and a known kind "
             "and precision"))
register_rule(Rule(
    id="cache-unreferenced-key", severity="info", layer="tile",
    contract="cache keys no registered program derives are reported (not "
             "gated): benchmark workloads legitimately create them, but "
             "orphans from key-format drift show up here first"))


def sublane_rows(precision: str) -> int:
    return 32 if precision == "int8" else 8


def check_dense_tile(tile: Sequence[int], precision: str,
                     site: str) -> List[Diagnostic]:
    """Alignment + VMEM findings for one pinned dense tile."""
    out: List[Diagnostic] = []
    bm, bk, bn = (int(v) for v in tile)
    sub = sublane_rows(precision)
    bad = []
    if bm % sub:
        bad.append(f"bm={bm} not a multiple of the {precision} "
                   f"sublane ({sub})")
    if bk % 128:
        bad.append(f"bk={bk} not a multiple of the 128 lane")
    if bn % 128:
        bad.append(f"bn={bn} not a multiple of the 128 lane")
    if bad:
        out.append(finding(
            "tile-misaligned", site, "; ".join(bad),
            fix="re-tune the op (python -m benchmarks.run --retune) or "
                "drop the entry so the kernel default applies"))
    vmem = tunelib.dense_vmem_bytes((bm, bk, bn), precision)
    if vmem > tunelib.SCOPED_VMEM_BYTES:
        out.append(finding(
            "tile-vmem-overflow", site,
            f"tile ({bm}, {bk}, {bn}) needs {vmem} VMEM bytes > "
            f"{tunelib.SCOPED_VMEM_BYTES} scoped limit",
            fix="re-tune the op; the candidate generator never emits "
                "over-budget tiles"))
    return out


def check_op_tile(op: OpSpec, plan: EnginePlan, cfg: EngineConfig,
                  site: str) -> List[Diagnostic]:
    """Tile-contract findings for one planned op: resolve the cache entry
    the op would pin under `cfg` and audit it (no benchmarking)."""
    out: List[Diagnostic] = []
    if cfg.tuning == "off" or plan.backend != "pallas":
        return out
    key = tunelib.tile_key(op, "pallas", cfg.accum, plan.precision)
    if key is None:
        return out
    entry = tunelib.load_cache().get("entries", {}).get(key)
    if not isinstance(entry, dict):
        return out                  # miss: kernel default, nothing to audit
    recorded = entry.get("precision", "fp32")
    if recorded != plan.precision:
        out.append(finding(
            "tile-precision-mismatch", site,
            f"cache entry {key} records precision {recorded!r} but the "
            f"op resolves it at precision {plan.precision!r}",
            fix="drop the entry and re-tune; the key derivation embeds "
                "the precision, so this only happens to edited caches"))
    tile = entry.get("tile")
    want = 3 if op.kind == "dense" else 2
    if not (isinstance(tile, (list, tuple)) and len(tile) == want
            and all(isinstance(v, int) and v > 0 for v in tile)):
        out.append(finding(
            "cache-malformed-entry", site,
            f"cache entry {key} carries malformed tile {tile!r} for "
            f"kind {op.kind!r} (want {want} positive ints)",
            fix="drop the entry (python -m repro.analyze --tuning --fix)"))
        return out
    if op.kind == "dense":
        out.extend(check_dense_tile(tile, plan.precision,
                                    f"{site} cache[{key}]"))
    return out


# ---------------------------------------------------------------------------
# The .tuning/ cache doctor
# ---------------------------------------------------------------------------

def derivable_keys(ops: Sequence[OpSpec],
                   accums: Sequence[Optional[str]] = (None,),
                   ) -> Set[str]:
    """Every tile-cache key any of `ops` can resolve to, across both
    precisions and the given accum labels — the reference set for
    unreferenced-key reporting."""
    keys: Set[str] = set()
    for op in ops:
        for accum in accums:
            for prec in ("fp32", "int8"):
                key = tunelib.tile_key(op, "pallas", accum, prec)
                if key is not None:
                    keys.add(key)
    return keys


def doctor_cache(path: Path, known_keys: Optional[Set[str]] = None,
                 repair: bool = False,
                 ) -> Tuple[List[Diagnostic], Optional[Dict[str, Any]]]:
    """Audit one `.tuning/<device_kind>.json` file.

    Returns (diagnostics, repaired_cache): `repaired_cache` is None unless
    `repair=True` and at least one error-class entry was dropped — the
    caller persists it (atomically, via `tune.save_cache` semantics).
    """
    out: List[Diagnostic] = []
    site = str(path)
    try:
        raw = json.loads(path.read_text())
    except FileNotFoundError:
        return out, None
    except (OSError, ValueError) as e:
        out.append(finding("cache-malformed-entry", site,
                           f"cache file unreadable: {e}",
                           fix="delete the file; tuning degrades cleanly "
                               "to kernel defaults"))
        return out, None
    if not isinstance(raw, dict) or not isinstance(raw.get("entries"), dict):
        out.append(finding("cache-malformed-entry", site,
                           "cache file is not a {version, entries} object",
                           fix="delete the file and re-tune"))
        return out, None
    if raw.get("version") != tunelib.CACHE_VERSION:
        out.append(finding(
            "cache-malformed-entry", site,
            f"cache version {raw.get('version')!r} != current "
            f"{tunelib.CACHE_VERSION} (stale caches load as empty)",
            severity="warn",
            fix="regenerate with `python -m benchmarks.run --retune`"))
    bad_keys: List[str] = []
    for key, entry in sorted(raw["entries"].items()):
        esite = f"{site}#{key}"
        errs_before = len([d for d in out if d.severity == "error"])
        if not isinstance(entry, dict):
            out.append(finding("cache-malformed-entry", esite,
                               f"entry is {type(entry).__name__}, not an "
                               "object", fix="drop the entry"))
            bad_keys.append(key)
            continue
        kind = entry.get("kind")
        prec = entry.get("precision", "fp32")
        tile = entry.get("tile")
        if kind not in ("dense", "conv2d"):
            out.append(finding("cache-malformed-entry", esite,
                               f"unknown kind {kind!r}",
                               fix="drop the entry"))
        if prec not in ("fp32", "int8"):
            out.append(finding("cache-malformed-entry", esite,
                               f"unknown precision {prec!r} (stale "
                               "pre-precision-axis entry)",
                               fix="drop the entry and re-tune"))
        want = 3 if kind == "dense" else 2
        well_formed = (isinstance(tile, (list, tuple)) and len(tile) == want
                       and all(isinstance(v, int) and v > 0 for v in tile))
        if not well_formed:
            out.append(finding("cache-malformed-entry", esite,
                               f"malformed tile {tile!r} for kind {kind!r}",
                               fix="drop the entry"))
        elif kind == "dense" and prec in ("fp32", "int8"):
            out.extend(check_dense_tile(tile, prec, esite))
        if len([d for d in out if d.severity == "error"]) > errs_before:
            bad_keys.append(key)
        elif known_keys is not None and key not in known_keys:
            out.append(finding(
                "cache-unreferenced-key", esite,
                f"no registered program derives this key "
                f"({entry.get('desc', 'no desc')!r}) — benchmark-produced "
                "or orphaned by key-format drift"))
    repaired = None
    if repair and bad_keys:
        repaired = {**raw,
                    "entries": {k: v for k, v in raw["entries"].items()
                                if k not in bad_keys}}
    return out, repaired
