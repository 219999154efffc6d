"""End-to-end training driver example: train an LM with checkpoints, kill it
mid-run, resume, and verify the loss curve continues — the fault-tolerance
path a cluster scheduler would exercise.

  PYTHONPATH=src python examples/train_lm.py            # ~2 min on CPU
  PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --full
"""
import argparse
import tempfile

from repro.launch import compile_cache, train as train_mod


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--full", action="store_true",
                    help="full config instead of the reduced variant")
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args(argv)
    compile_cache.enable()

    with tempfile.TemporaryDirectory() as ckpt:
        base = ["--arch", args.arch, "--seq", "128", "--batch", "8",
                "--ckpt-dir", ckpt, "--ckpt-every", "20",
                "--log-every", "10"]
        if not args.full:
            base += ["--reduced"]

        half = args.steps // 2
        print(f"=== phase 1: train to step {half}, then 'crash' ===")
        h1 = train_mod.main(base + ["--steps", str(half)])

        print("=== phase 2: resume from the checkpoint (elastic restart) ===")
        h2 = train_mod.main(base + ["--steps", str(args.steps), "--resume"])

        first = h1[0]["loss"]
        last = h2[-1]["loss"]
        print(f"loss {first:.4f} -> {last:.4f} across a crash/resume")
        assert last < first, "training did not make progress across resume"
        print("train_lm example done.")


if __name__ == "__main__":
    main()
