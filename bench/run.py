"""Run one benchmark cell once.

  python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cells, their configurations, traffic and
metrics are in BENCHMARK.json and the files under bench/ (see
bench/spec.py). The last line of standard output is one JSON object:
correct, attempted, failed, metrics (the cell's end-to-end metrics, or its
per-layer ones with --trace 1), device, with --trace 1 a breakdown of the
trace, and last the checks: each number compared with its limit. The
checks are also the last lines of standard error.

Exit codes: 0 a result was printed (correct or not); 3 JAX found no TPU or
fewer chips than the cell asks for; 1 anything else, among them a checkout
without the program under src/. JAX's persistent compilation cache is kept
in <checkout>/.jax_cache, whatever the environment says, so that only the
first run in a checkout compiles, and the TPU runtime's logs in
<checkout>/.tpu_logs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def use_checkout() -> bool:
    """Point JAX's compilation cache and the TPU runtime's logs into the
    checkout and put its program on the path; False when it has none."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["TPU_LOG_DIR"] = str(ROOT / ".tpu_logs")
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"no program under {src}: nothing to measure")
        return False
    sys.path.insert(0, str(src))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return True


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not use_checkout():
        return 1
    from bench import harness
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START,
                                  log=log)
    except harness.NoChip as e:
        log(str(e))
        return 3
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
