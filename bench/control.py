"""The control: the plain reference at the next precision down, in the
program's place, read by the same number as a run's check.

  python3 -m bench.control --workload <name> --seeds 1,2,3

For each seed it makes the cell's weights and input images exactly as a
run does, computes the reference at "highest" and the control at
"high" (three bfloat16 passes, lax.Precision.HIGH; bench/references), and
judges the control's answers by the run's own check (`harness.checks_for`
and `harness.passed`): one JSON line per seed with `correct`, which must
be false, and the checks, each number beside its limit. Requires the
chip, like a run.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench.run import ROOT, log, use_checkout


def readings(root, workload: str, seeds, allow_cpu: bool = False):
    import jax
    from bench import harness, spec
    bm = spec.benchmark(root)
    cell = spec.cell(bm, workload)
    cfg = spec.config(root, bm, cell["config"])
    mix = spec.traffic(root, cell["traffic"])
    if jax.devices()[0].platform != "tpu" and not allow_cpu:
        raise harness.NoChip("the control reads on the chip")
    ref = spec.reference(root, cfg)
    for seed in seeds:
        k_weights, k_inputs = harness.seed_keys(seed)
        params = ref.init(cfg, k_weights)
        pool = harness.input_pool(cfg, mix, k_inputs)
        want = harness.reference_logits(ref, cfg, params, pool, "highest")
        got = harness.reference_logits(ref, cfg, params, pool, "high")
        errs = harness.row_rel_err(got, want)
        checks = harness.checks_for(cfg, errs, 0)
        yield {"workload": workload, "seed": seed, "images": len(errs),
               "correct": harness.passed(checks),
               "median_rel_err": float(np.median(errs)), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if not use_checkout():
        return 1
    from bench import harness
    try:
        for row in readings(ROOT, args.workload,
                            [int(s) for s in args.seeds.split(",")]):
            print(json.dumps(row), flush=True)
    except harness.NoChip as e:
        log(str(e))
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
