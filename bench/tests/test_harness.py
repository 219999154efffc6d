"""The harness end to end on the CPU: a cell added by files alone, the
check catching a broken timed path, and the refusals."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from conftest import REPO, _dump

from bench import harness


def _run(root, cell, trace=False, seed=2**33 + 11, seconds=1.0):
    return harness.run_cell(root, cell, seed, seconds, trace,
                            time.perf_counter(), allow_cpu=True,
                            log=lambda m: None)


def test_a_cell_config_mix_and_metric_added_by_files_only(root):
    """Nothing under bench/ names a cell: new files and entries are found
    by name, and the new metric is reported in the new cell."""
    cfg = json.loads((root / "bench" / "configs" / "t.json").read_text())
    _dump(root / "bench" / "configs" / "t2.json", cfg)
    _dump(root / "bench" / "traffic" / "t_closed2.json",
          {"loop": "closed", "batch": 2, "input_batches": 1})
    (root / "bench" / "metrics" / "batches_in_window.t2.py").write_text(
        "def read(run):\n    return len(run.batches)\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "t2", "file": "bench/configs/t2.json"})
    bm["workloads"].append({"name": "t2.offline.b2", "config": "t2",
                            "traffic": "t_closed2", "chips": 1})
    bm["end_to_end"][0]["workloads"].append("t2.offline.b2")
    bm["per_layer"].append({"name": "batches_in_window.t2", "unit": "count",
                            "better": "higher", "source": "host_clock",
                            "layer": "whole step", "moves": "images_per_s",
                            "workloads": ["t2.offline.b2"]})
    _dump(root / "BENCHMARK.json", bm)
    out = _run(root, "t2.offline.b2")
    assert out["correct"]
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    traced = _run(root, "t2.offline.b2", trace=True)
    assert traced["correct"]
    assert traced["metrics"]["batches_in_window.t2"]["value"] >= 1
    assert list(traced)[-1] == "checks"


@pytest.mark.parametrize("cell", ["t.offline", "t.server"])
def test_a_sound_run_is_correct_and_reports_its_metrics(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = ({"images_per_s", "setup_s"} if cell == "t.offline"
            else {"latency_p50_ms", "setup_s"})
    assert set(out["metrics"]) == want
    assert out["checks"]["max_rel_err"]["value"] \
        <= out["checks"]["max_rel_err"]["limit"]


def test_a_traced_server_run_reports_its_per_layer_metrics(root):
    """The p95 tail and the whole step's share are per-layer readings of
    the served cells; a share never passes 100%."""
    out = _run(root, "t.server", trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert {"latency_p95_ms.server", "mfu.server", "queue_wait_ms.server",
            "batch_occupancy.server"} <= set(m)
    assert m["latency_p95_ms.server"]["value"] > 0
    assert 0 < m["mfu.server"]["value"] <= 100


def _one_answer_altered(y):
    return y.at[0, 0].add(1e-3 * jnp.abs(y[0]).max())


def _half_the_batch_left_out(y):
    half = (y.shape[0] + 1) // 2
    return y.at[half:].set(y[:y.shape[0] - half])


@pytest.mark.parametrize("fault", [_one_answer_altered,
                                   _half_the_batch_left_out])
@pytest.mark.parametrize("cell", ["t.offline", "t.server"])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, cell, fault):
    from repro.engine.program import CompiledNet
    apply = CompiledNet.apply

    def broken(self, *args):
        return fault(apply(self, *args))

    monkeypatch.setattr(CompiledNet, "apply", broken)
    if cell == "t.server":      # enough load that batches hold several rows
        mix = json.loads((root / "bench" / "traffic" / "t_open.json")
                         .read_text())
        _dump(root / "bench" / "traffic" / "t_open.json",
              dict(mix, rate_per_s=64))
    out = _run(root, cell)
    assert not out["correct"]
    assert out["checks"]["max_rel_err"]["value"] \
        > out["checks"]["max_rel_err"]["limit"]


def _bench(cwd, env_extra, *args):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "alexnet.offline.b128", "--seed", "1", "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_3_and_prints_nothing():
    p = _bench(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3
    assert p.stdout == ""


def test_only_the_benchmark_files_exit_nonzero_and_print_nothing(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""


def test_an_unknown_device_kind_has_no_peaks(root):
    from bench import spec
    with pytest.raises(spec.SpecError):
        spec.peaks(root, "TPU v9 imaginary")
    assert spec.peaks(REPO, "TPU v5 lite")["flops_bf16"] == 197e12
