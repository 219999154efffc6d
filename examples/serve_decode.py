"""Batched serving example: prefill a batch of prompts and stream greedy
tokens with the O(1)-state decode path (recurrent archs) or the KV cache
(attention archs).

  PYTHONPATH=src python examples/serve_decode.py --arch xlstm-125m
  PYTHONPATH=src python examples/serve_decode.py --arch gemma2-27b  # reduced
"""
import argparse

from repro.launch import compile_cache, serve as serve_mod


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    args = ap.parse_args(argv)
    compile_cache.enable()
    serve_mod.main(["--arch", args.arch, "--reduced", "--batch", "4",
                    "--prompt-len", "48", "--gen", "24"])


if __name__ == "__main__":
    main()
