"""Shared fixtures of the benchmark's own tests.

  JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They run on the CPU: the harness is driven through `harness.run_cell(...,
allow_cpu=True)`, which skips only its look for a chip, on throwaway cells
in a temporary root that hold the real configurations with the engine on
XLA (Pallas would run interpreted) and small batches.
"""
from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CPU_PEAKS = {"cpu": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}}
CLOSED = {"loop": "closed", "batch": 4, "input_batches": 2}
OPEN = {"loop": "open", "rate_per_s": 8, "pool": 16,
        "scheduler": {"max_batch": 4, "buckets": [1, 2, 4]}}


def _dump(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def make_root(tmp: Path, config: str = "alexnet_fp32_pallas") -> Path:
    """A root with the repo's benchmark code data, one configuration moved to
    XLA, and two cells: t.offline (closed loop, batch 4) and t.server (open
    loop, 8 requests/s). Every metric of BENCHMARK.json is listed for the
    cell of its kind."""
    shutil.copytree(REPO / "bench" / "metrics", tmp / "bench" / "metrics")
    shutil.copytree(REPO / "bench" / "references",
                    tmp / "bench" / "references")
    cfg = json.loads((REPO / "bench" / "configs" / f"{config}.json")
                     .read_text())
    cfg["engine"] = dict(cfg["engine"], backend="xla", policy="fixed")
    cfg["served_engine"] = {"row_align": 8}
    _dump(tmp / "bench" / "configs" / "t.json", cfg)
    _dump(tmp / "bench" / "traffic" / "t_closed.json", CLOSED)
    _dump(tmp / "bench" / "traffic" / "t_open.json", OPEN)
    _dump(tmp / "bench" / "peaks.json", CPU_PEAKS)
    bm = copy.deepcopy(json.loads((REPO / "BENCHMARK.json").read_text()))
    open_cells = {
        c["name"] for c in bm["workloads"]
        if json.loads((REPO / "bench" / "traffic" / f"{c['traffic']}.json")
                      .read_text())["loop"] == "open"}
    bm["configs"] = [{"name": "t", "file": "bench/configs/t.json"}]
    bm["workloads"] = [
        {"name": "t.offline", "config": "t", "traffic": "t_closed",
         "chips": 1},
        {"name": "t.server", "config": "t", "traffic": "t_open",
         "chips": 1}]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            served = bool(open_cells & set(m["workloads"]))
            m["workloads"] = ["t.server" if served else "t.offline"]
    _dump(tmp / "BENCHMARK.json", bm)
    return tmp


@pytest.fixture
def root(tmp_path):
    return make_root(tmp_path)
