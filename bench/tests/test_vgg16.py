"""VGG-16 (`vgg16_fp32_auto`) at its published widths, on the CPU: the
configuration file against the program, the program against the plain
reference on seeded weights, and the control failing the limit."""
import json
import time

import pytest

from conftest import REPO, _dump, make_root

from bench import control, counts, harness


def _cfg(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("net,name", [
    ("alexnet", "alexnet_fp32_pallas"),
    ("resnet50", "resnet50_fp32_auto"),
    ("vgg16", "vgg16_fp32_auto"),
])
def test_ops_match_the_programs_real_geometry(net, name):
    """Each op of the configuration, in execution order, has the name,
    filter shape, input size, stride and padding of the program's op."""
    from repro.models import cnn
    prog = cnn.program(net, main_path_only=False)
    ours = counts.ops(_cfg(name))
    assert [op.name for op in ours] == [op.name for op in prog.ops]
    for mine, theirs in zip(ours, prog.ops):
        assert mine.w_shape == tuple(theirs.w_shape)
        if mine.kind == "conv":
            assert (mine.h_in, mine.w_in, mine.stride, mine.pad) == (
                theirs.x_shape[1], theirs.x_shape[2], theirs.stride,
                theirs.pad)


def test_vgg16_counts_are_the_published_ones():
    cfg = _cfg("vgg16_fp32_auto")
    assert counts.params(cfg) == cfg["params"] == 138_357_544
    assert counts.macs(cfg) == 15_470_264_320


def _two_image_root(tmp_path):
    root = make_root(tmp_path, "vgg16_fp32_auto")
    _dump(root / "bench" / "traffic" / "t_closed.json",
          {"loop": "closed", "batch": 2, "input_batches": 1})
    return root


def test_vgg16_at_published_widths_matches_the_reference(tmp_path):
    """`engine.compile(...).apply` at batch 2 on seeded weights, through
    the run's own check: correct, nothing missing."""
    out = harness.run_cell(_two_image_root(tmp_path), "t.offline",
                           2**33 + 11, 0.1, False, time.perf_counter(),
                           allow_cpu=True, log=lambda m: None)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2 * harness.AHEAD
    assert out["checks"]["max_rel_err"]["value"] \
        <= out["checks"]["max_rel_err"]["limit"]


def test_the_control_fails_the_vgg16_limit(tmp_path):
    """The reference at "high" (three bfloat16 passes) is not correct by
    the configuration's limit."""
    rows = list(control.readings(_two_image_root(tmp_path), "t.offline",
                                 [5, 2**33 + 3], allow_cpu=True))
    for row in rows:
        assert not row["correct"], row
        assert not harness.passed(row["checks"])
