"""The control (the reference at "high", three bfloat16 passes) must fail
each configuration's limit, at published widths.

On the chip the control is read at the cell's own size by
`python3 -m bench.control`; here it runs over two images on the CPU, where
the reference writes the three passes out (the CPU ignores
lax.Precision.HIGH). Either way its answers go through the run's own
check, `harness.checks_for` and `harness.passed`, which must say not
correct.
"""
import json

import pytest

from conftest import _dump

from bench import control, harness


@pytest.mark.parametrize("config", ["alexnet_fp32_pallas",
                                    "resnet50_fp32_auto"])
def test_the_control_fails_the_limit(tmp_path, config):
    from conftest import make_root
    root = make_root(tmp_path, config)
    _dump(root / "bench" / "traffic" / "t_two.json",
          {"loop": "closed", "batch": 2, "input_batches": 1})
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "t.two", "config": "t",
                            "traffic": "t_two", "chips": 1})
    _dump(root / "BENCHMARK.json", bm)
    rows = list(control.readings(root, "t.two", [5, 2**33 + 3],
                                 allow_cpu=True))
    for row in rows:
        assert not row["correct"], row
        assert not harness.passed(row["checks"])
