"""Everything the harness knows about a cell comes from files, found by name.

Under a root directory (the checkout, or a test's temporary copy):

  BENCHMARK.json                 cells, configurations, metrics
  bench/configs/<config>.json    sizes, engine settings, reference, limits
  bench/traffic/<traffic>.json   loop, batch or arrivals, scheduler
  bench/references/<ref>.py      the configuration's plain reference
  bench/metrics/<metric>.py      one reader per metric: read(ctx) -> number
                                 or None when it finds nothing to read
  bench/peaks.json               published chip peaks, keyed by device kind

A new configuration, traffic mix or metric is a new file and a new entry in
BENCHMARK.json; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List


class SpecError(RuntimeError):
    """A cell, file or device kind the benchmark does not describe."""


def _json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def _module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path) -> Dict:
    return _json(root / "BENCHMARK.json")


def cell(bm: Dict, workload: str) -> Dict:
    for c in bm["workloads"]:
        if c["name"] == workload:
            return c
    raise SpecError(f"no workload {workload!r}; have "
                    f"{[c['name'] for c in bm['workloads']]}")


def config(root: Path, bm: Dict, name: str) -> Dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return _json(root / c["file"])
    raise SpecError(f"no configuration {name!r}")


def traffic(root: Path, name: str) -> Dict:
    return _json(root / "bench" / "traffic" / f"{name}.json")


def reference(root: Path, cfg: Dict):
    name = cfg["reference"]
    return _module(root / "bench" / "references" / f"{name}.py",
                   f"bench_reference_{name}")


def peaks(root: Path, device_kind: str) -> Dict:
    table = _json(root / "bench" / "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise SpecError(f"no published peaks for device kind "
                        f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


def metrics_of(bm: Dict, workload: str, group: str) -> List[Dict]:
    """The `group` ("end_to_end" or "per_layer") metrics a cell reports: those
    that list it, and those without a list whose `moves` (or, for an
    end-to-end metric, itself) the cell reports."""
    e2e = [m["name"] for m in bm["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    out = []
    for m in bm[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def reader(root: Path, metric: str) -> Callable:
    mod = _module(root / "bench" / "metrics" / f"{metric}.py",
                  "bench_metric_" + metric.replace(".", "_").replace("-", "_"))
    return mod.read
