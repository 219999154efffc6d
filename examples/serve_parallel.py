"""Multi-device serving demo: plan-driven sharding + replica spreading.

  PYTHONPATH=src python examples/serve_parallel.py [--data 2] [--model 4]
      [--requests 8] [--seed 0]

On a CPU host, JAX fakes the data*model devices: the script adds
`--xla_force_host_platform_device_count=<data*model>` to XLA_FLAGS before
JAX starts. The flag shapes only the CPU backend, so on a host with chips
the mesh is built from the real devices (`--data 1 --model 4` or
`--data 4 --model 1` on a four-chip host).

Three layers of the parallel subsystem, smallest to largest:

  1. per-op placement — `engine.compile` under
     `EngineConfig(parallel=ParallelConfig(model=M))` gives every GEMM of
     the plan its own strategy (replicate / shard-K all-reduce / shard-N
     all-gather), priced by the same analytic MMIE cost model that picks
     pallas-vs-xla per layer; `CompiledNet.shards()` shows the choices and
     `plan.collective_words` the priced ring-collective traffic;
  2. tensor-parallel serving — a `ContinuousScheduler(mesh=...)` compiles
     its prefill/decode steps shard_mapped over one (1, model) group;
  3. replica spreading — `ReplicaSpread` splits a (data, model) mesh into
     `data` independent tensor-parallel groups, each with its own paged KV
     pool, and routes requests least-loaded.

The golden-parity contract survives every layer: the demo generates the
same workload single-device and spread-sharded and asserts the token
streams are bitwise identical (shard-N only concatenates column blocks;
shard-K, the one inexact strategy, is never auto-selected).
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", type=int, default=2,
                    help="data-parallel replicas (independent KV pools)")
    ap.add_argument("--model", type=int, default=4,
                    help="tensor-parallel ways per replica")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # read when JAX first starts a backend, i.e. below; CPU backend only
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                                 f"={args.data * args.model}")

    import jax
    import jax.numpy as jnp

    from repro import engine as E
    from repro.configs.base import reduced
    from repro.engine.parallel import ParallelConfig, make_mesh
    from repro.launch import compile_cache
    from repro.models import transformer as T
    from repro.serve.scheduler import ContinuousScheduler, ReplicaSpread

    compile_cache.enable()
    print(f"devices: {jax.device_count()} "
          f"(mesh {args.data} data x {args.model} model)")
    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed), jnp.float32)

    rng = jax.random.PRNGKey(args.seed + 1)
    work = []
    for _ in range(args.requests):
        rng, k1, k2 = jax.random.split(rng, 3)
        plen = int(jax.random.randint(k1, (), 2, 9))
        prompt = jax.random.randint(k2, (plen,), 0, cfg.vocab_size)
        steps = int(jax.random.randint(k1, (), 3, 8))
        work.append(([int(t) for t in prompt], steps))
    kw = dict(max_len=24, num_blocks=48, max_batch=4)

    # single-device baseline (replicas see the same analytic plans)
    base = ContinuousScheduler(cfg, params, **kw)
    bt = [base.submit(p, s) for p, s in work]
    base.run()

    pcfg = ParallelConfig(data=args.data, model=args.model)
    mesh = make_mesh(pcfg)
    spread = ReplicaSpread(cfg, params, mesh=mesh,
                           config=E.EngineConfig(row_align=8, parallel=pcfg),
                           **kw)
    rt = [spread.submit(p, s) for p, s in work]
    spread.run()

    dec = spread.replicas[0].decode_compiled(kw["max_batch"])
    strategies = dec.shards()
    print(f"decode-step placements ({len(strategies)} dense ops): "
          + ", ".join(sorted({f'{s}x{strategies.count(s)}'
                              for s in set(strategies)})))
    print(f"priced collective traffic: {dec.plan.collective_words} words "
          f"/ decode step")

    ok = all(b.tokens == r.tokens for b, r in zip(bt, rt))
    print(f"bitwise token parity (single vs spread-sharded): {ok}")
    assert ok
    st = spread.stats()
    for i, rep in enumerate(st["per_replica"]):
        print(f"replica {i}: served {rep['admitted']} requests, "
              f"{rep['tokens_out']} decode tokens, "
              f"fill {rep['decode_fill']:.2f}")
    print(f"placements: {[t.replica for t in rt]}")


if __name__ == "__main__":
    main()
