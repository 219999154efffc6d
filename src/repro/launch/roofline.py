"""Roofline analysis (EXPERIMENTS.md §Roofline).

Reads the dry-run artifacts (experiments/dryrun/*.json) and merges them with
the analytic FLOP/byte model (core/flops.py) into the three-term roofline
per (arch x shape) cell on the single-pod mesh, against the published
peaks of the named device kind (`core.modes.DEVICE_PEAKS`; a kind missing
from the table is an error):

  compute term    = FLOPs / (chips x peak bf16 FLOP/s)
  memory term     = HBM bytes / (chips x peak HBM bytes/s)
  collective term = collective bytes per chip / ICI bytes/s per link

FLOPs source: analytic (exact matmul census from the config — XLA's
HloCostAnalysis counts while bodies once, see launch/hloparse.py docstring);
the compiled number and the MODEL_FLOPS = 6*N_active*D ratio are reported
alongside. Collective bytes: trip-count-corrected HLO parse (per-device).

  PYTHONPATH=src python -m repro.launch.roofline --device-kind "TPU v5 lite" \
      [--mesh 16x16] [--md]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from repro.configs.base import ARCH_NAMES, SHAPES, get_config, valid_cells
from repro.core import flops as F
from repro.core.modes import peaks_for

DRYRUN_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def cell_roofline(arch: str, cell: str, mesh_tag: str = "16x16", *,
                  device_kind: str):
    peaks = peaks_for(device_kind)
    cfg = get_config(arch)
    n_dev = 512 if mesh_tag == "2x16x16" else 256
    path = DRYRUN_DIR / f"{arch}__{cell}__{mesh_tag}.json"
    dj = json.loads(path.read_text()) if path.exists() else None

    cf = F.cell_flops(cfg, cell)
    per_dev_flops = cf.cell_total / n_dev
    compute_term = per_dev_flops / peaks.flops_bf16

    bytes_dev = F.cell_bytes_per_device(cfg, cell, n_dev)
    hbm_bytes = sum(bytes_dev.values())
    memory_term = hbm_bytes / peaks.hbm_bw

    coll_bytes = 0
    coll_detail = {}
    hlo_flops = hlo_bytes = peak_gib = None
    if dj:
        coll_detail = dj.get("collective_bytes", {})
        coll_bytes = sum(coll_detail.values())
        hlo_flops = dj["cost"]["flops"]
        hlo_bytes = dj["cost"]["bytes_accessed"]
        peak_gib = (dj["memory"]["peak_bytes"] or 0) / 2 ** 30
    collective_term = coll_bytes / peaks.ici_bw

    terms = {"compute": compute_term, "memory": memory_term,
             "collective": collective_term}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    roofline_fraction = compute_term / bound if bound else 0.0
    return {
        "arch": arch, "cell": cell, "mesh": mesh_tag,
        "device_kind": device_kind,
        "compute_s": compute_term, "memory_s": memory_term,
        "collective_s": collective_term, "dominant": dominant,
        "roofline_fraction": roofline_fraction,
        "model_flops": cf.model_flops,
        "analytic_flops": cf.cell_total,
        "useful_ratio": cf.model_flops / cf.cell_total,
        "hlo_flops_reported": hlo_flops,
        "hlo_bytes_reported": hlo_bytes,
        "peak_gib": peak_gib,
        "collective_detail": coll_detail,
        "bytes_detail": bytes_dev,
    }


def all_rows(mesh_tag: str = "16x16", *, device_kind: str):
    rows = []
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for cell in valid_cells(cfg):
            rows.append(cell_roofline(arch, cell, mesh_tag,
                                      device_kind=device_kind))
    return rows


def fmt_s(x):
    if x >= 1.0:
        return f"{x:7.2f}s "
    if x >= 1e-3:
        return f"{x*1e3:6.2f}ms"
    return f"{x*1e6:6.1f}us"


def print_table(rows, md=False):
    hdr = ("arch", "cell", "compute", "memory", "collective", "dominant",
           "roofline%", "useful%", "peakGiB")
    sep = "|" if md else "  "
    if md:
        print("| " + " | ".join(hdr) + " |")
        print("|" + "---|" * len(hdr))
    else:
        print(f"{hdr[0]:22s} {hdr[1]:12s} {hdr[2]:>9s} {hdr[3]:>9s} "
              f"{hdr[4]:>10s} {hdr[5]:>10s} {hdr[6]:>9s} {hdr[7]:>7s} "
              f"{hdr[8]:>8s}")
    for r in rows:
        vals = (r["arch"], r["cell"], fmt_s(r["compute_s"]),
                fmt_s(r["memory_s"]), fmt_s(r["collective_s"]),
                r["dominant"], f"{100*r['roofline_fraction']:.0f}%",
                f"{100*r['useful_ratio']:.0f}%",
                f"{r['peak_gib']:.1f}" if r["peak_gib"] else "-")
        if md:
            print("| " + " | ".join(str(v) for v in vals) + " |")
        else:
            print(f"{vals[0]:22s} {vals[1]:12s} {vals[2]:>9s} {vals[3]:>9s} "
                  f"{vals[4]:>10s} {vals[5]:>10s} {vals[6]:>9s} "
                  f"{vals[7]:>7s} {vals[8]:>8s}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--device-kind", required=True,
                    help="jax device_kind whose peaks price the terms")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--json", default="")
    args = ap.parse_args()
    rows = all_rows(args.mesh, device_kind=args.device_kind)
    print_table(rows, md=args.md)
    if args.json:
        Path(args.json).write_text(json.dumps(rows, indent=1))


if __name__ == "__main__":
    main()
