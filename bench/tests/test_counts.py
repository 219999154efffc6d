"""bench/counts.py against the program's own real-geometry plans."""
import json

import pytest

from conftest import REPO

from bench import counts


def _cfg(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


# VGG-16 has no cell yet; its table is written out here as a configuration
# would hold it, so the counts are checked on the paper's third net too.
VGG16 = {
    "arch": "plain", "input": [224, 224, 3],
    "convs": [{"name": f"c{i}", "c_in": a, "c_out": b, "k": 3, "stride": 1,
               "pad": 1, "groups": 1, "pool": p, "relu": True}
              for i, (a, b, p) in enumerate(
                  [(3, 64, 1), (64, 64, 2), (64, 128, 1), (128, 128, 2),
                   (128, 256, 1), (256, 256, 1), (256, 256, 2),
                   (256, 512, 1), (512, 512, 1), (512, 512, 2),
                   (512, 512, 1), (512, 512, 1), (512, 512, 2)])],
    "fcs": [{"name": "fc6", "n": 25088, "m": 4096, "relu": True},
            {"name": "fc7", "n": 4096, "m": 4096, "relu": True},
            {"name": "fc8", "n": 4096, "m": 1000, "relu": False}],
}


def _program_macs(net):
    from repro import engine as E
    from repro.models import cnn
    prog = cnn.program(net, main_path_only=False)
    return E.plan_network(prog, E.EngineConfig()).total_macs


@pytest.mark.parametrize("net,cfg,macs_m", [
    ("alexnet", _cfg("alexnet_fp32_pallas"), 724.4),
    ("resnet50", _cfg("resnet50_fp32_auto"), 3858.0),
    ("vgg16", VGG16, 15470.3),
])
def test_macs_match_the_programs_real_geometry(net, cfg, macs_m):
    assert counts.macs(cfg) == _program_macs(net)
    assert counts.macs(cfg) / 1e6 == pytest.approx(macs_m, abs=0.05)


def test_ops_match_the_programs_execution_order():
    from repro.models import cnn
    for net, name in [("alexnet", "alexnet_fp32_pallas"),
                      ("resnet50", "resnet50_fp32_auto")]:
        prog = cnn.program(net, main_path_only=False)
        ours = counts.ops(_cfg(name))
        assert [op.name for op in ours] == [op.name for op in prog.ops]
        for mine, theirs in zip(ours, prog.ops):
            assert mine.w_shape == tuple(theirs.w_shape)


def test_alexnet_parameter_count():
    cfg = _cfg("alexnet_fp32_pallas")
    assert counts.params(cfg) == cfg["params"] == 60_965_224


def test_least_time_is_the_larger_bound():
    op = counts.Op("fc", "dense", 1, 1, 4096, 4096)
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # batch 1: weight bytes dominate; batch 4096: FLOPs do
    assert counts.least_seconds(op, 1, peaks) == pytest.approx(
        counts.bytes_moved(op, 1) / 819e9)
    assert counts.least_seconds(op, 4096, peaks) == pytest.approx(
        2 * 4096 * 4096 * 4096 / 197e12)
