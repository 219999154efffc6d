"""Continuous-batching serving demo over the paged KV block pool.

  PYTHONPATH=src python examples/serve_continuous.py [--requests 8]
      [--max-batch 4] [--num-blocks 48] [--block-size 8] [--seed 0]

A mixed-length greedy-generation workload runs three ways:

  * continuous — per-step admission: finished rows leave the decode batch
    and queued requests join it the same step, each request's KV cache
    living in pool blocks allocated on demand (`serve.kv_pool`);
  * static    — the same `ContinuousScheduler` in `admission="drain"`
    mode: a batch is admitted together and drained to empty before the
    next one forms (the PR-3 bucketed behaviour, short rows stranded);
  * sequential — `max_batch=1`, one request at a time.

All three produce bitwise-identical tokens per request (the golden-parity
contract: batch-1 prefill at the exact prompt length + `row_align=8`
decode GEMMs + exact masking of recycled-block garbage), so the demo
checks parity while it measures throughput, then prints the pool / fill
stats that explain the continuous win.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import reduced
from repro.launch import compile_cache
from repro.models import transformer as T
from repro.serve.scheduler import ContinuousScheduler, latency_percentiles

MAX_LEN = 64


def build_workload(n, seed):
    rng = jax.random.PRNGKey(seed)
    work = []
    for i in range(n):
        rng, k1, k2, k3 = jax.random.split(rng, 4)
        plen = int(jax.random.randint(k1, (), 3, 17))
        steps = int(jax.random.choice(k2, jnp.asarray([4, 8, 16, 24])))
        prompt = jax.random.randint(k3, (plen,), 1, 200, dtype=jnp.int32)
        work.append(([int(t) for t in prompt], steps))
    return work


def serve(cfg, params, work, *, admission, max_batch, num_blocks,
          block_size, timeout_s=None):
    sched = ContinuousScheduler(cfg, params, max_len=MAX_LEN,
                                num_blocks=num_blocks,
                                block_size=block_size,
                                max_batch=max_batch, admission=admission)
    tickets = [sched.submit(p, n, timeout_s=timeout_s) for p, n in work]
    t0 = time.perf_counter()
    sched.run()
    wall = time.perf_counter() - t0
    return tickets, sched, wall


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--num-blocks", type=int, default=48)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    work = build_workload(args.requests, args.seed)
    print(f"workload: {len(work)} requests, prompt lens "
          f"{[len(p) for p, _ in work]}, steps {[n for _, n in work]}")

    runs = {}
    for mode, admission, mb in (("continuous", "continuous", args.max_batch),
                                ("static", "drain", args.max_batch),
                                ("sequential", "continuous", 1)):
        tickets, sched, wall = serve(
            cfg, params, work, admission=admission, max_batch=mb,
            num_blocks=args.num_blocks, block_size=args.block_size)
        runs[mode] = [t.tokens for t in tickets]
        st = sched.stats()
        pct = latency_percentiles(tickets)
        print(f"{mode:10s} wall={wall:6.2f}s "
              f"tok/s={st['tokens_out'] / wall:7.1f} "
              f"fill={st['decode_fill']:.3f} steps={st['steps']:3d} "
              f"p50={pct['p50_ms']:7.1f}ms p95={pct['p95_ms']:7.1f}ms")
        if mode == "continuous":
            pool = st["pool"]
            print(f"{'':10s} pool: {pool['num_blocks']} blocks x "
                  f"{pool['block_size']} slots, low-water "
                  f"{pool['free_low_water']}, admitted/step "
                  f"{st['admitted_per_step'][:8]}")

    assert runs["continuous"] == runs["static"] == runs["sequential"], \
        "parity violation: modes disagree on generated tokens"
    print("parity: tokens bitwise identical across all three modes")


if __name__ == "__main__":
    main()
