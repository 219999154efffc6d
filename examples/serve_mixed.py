"""Mixed-workload serving demo: AlexNet image forwards and transformer
decode steps share one plan-driven batched scheduler — the paper's
conv-and-FC-on-the-same-engine claim at serving granularity.

  PYTHONPATH=src python examples/serve_mixed.py [--policy spf|fifo]
                                                [--cnn 3] [--decode 8]

Requests interleave in one queue; the scheduler packs same-program
requests into shape buckets and orders batches by each program's analytic
`NetworkPlan.total_latency_s` ("spf") or arrival ("fifo"). Every ticket
carries an `engine.Ledger` of its own plan ops, so the demo prints true
per-request MMIE-projected cost next to the measured wall clock.
"""
import argparse

import jax
import jax.numpy as jnp

from repro import engine as E
from repro.configs.base import reduced
from repro.launch import compile_cache
from repro.models import cnn, transformer as T
from repro.serve import engine as SE
from repro.serve.scheduler import Scheduler, latency_percentiles


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="spf", choices=("spf", "fifo"))
    ap.add_argument("--cnn", type=int, default=3, help="# AlexNet requests")
    ap.add_argument("--decode", type=int, default=8,
                    help="# decode-step requests")
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cnn_params = cnn.init_cnn("alexnet", jax.random.PRNGKey(1))

    sched = Scheduler(policy=args.policy, max_batch=args.max_batch)
    entries = {
        "decode": sched.register(
            "decode", SE.decode_program(cfg, batch=1, max_len=32),
            shared_args=(params, jnp.int32(3))),
        "alexnet": sched.register("alexnet", cnn.program("alexnet"),
                                  shared_args=(cnn_params,)),
    }
    for name, entry in entries.items():
        print(f"registered {name:8s} plan_latency="
              f"{entry.unit_plan.total_latency_s * 1e3:8.3f}ms "
              f"ops={len(entry.unit_plan.plans)} "
              f"eff={entry.unit_plan.performance_efficiency:.3f}")

    tickets = []
    for i in range(max(args.cnn, args.decode)):
        if i < args.cnn:
            x = jax.random.normal(jax.random.PRNGKey(i),
                                  (1, 227, 227, 3), jnp.float32) * 0.1
            tickets.append(sched.submit("alexnet", x))
        if i < args.decode:
            st = T.init_decode_state(cfg, 1, 32)
            tickets.append(sched.submit(
                "decode", st, jnp.full((1, 1), i, jnp.int32)))
    print(f"\nqueued {sched.pending()} requests, plan cost "
          f"{sched.queue_cost_s() * 1e3:.3f}ms ({args.policy})")

    done = sched.drain()
    print("\nrid  model     bucket fill  latency_ms  plan_macs")
    for t in done:
        print(f"{t.rid:3d}  {t.model:8s} {t.batch_bucket:5d} "
              f"{t.batch_fill:4d}  {t.latency_s * 1e3:9.2f}  "
              f"{t.ledger.total_macs:10d}")

    stats = sched.stats()
    pct = latency_percentiles(done)
    print(f"\nserved {stats['served']} in {stats['batches']} batches, "
          f"{stats['throughput_rps']:.1f} req/s; "
          f"p50={pct['p50_ms']:.1f}ms p95={pct['p95_ms']:.1f}ms")
    print(f"plan-projected work served: {stats['plan_macs_served']:,} MACs, "
          f"{stats['plan_cycles_served']:,} MMIE cycles")


if __name__ == "__main__":
    main()
