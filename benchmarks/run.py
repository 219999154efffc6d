"""Benchmark driver. One section per paper table/figure plus kernel and
end-to-end microbenchmarks. Prints ``name,us_per_call,derived`` CSV and
emits machine-readable JSON:

  * ``BENCH_engine.json`` — per network, the whole-network analytic plan
    (latency / memory accesses / efficiency off `engine.NetworkPlan`) and
    the wall-clock of the jitted ``CompiledNet.apply``;
  * ``BENCH_serve.json``  — the batched serving scheduler: throughput and
    submit-to-completion latency percentiles per policy (fifo / spf)
    against the sequential batch-1 baseline, on a decode smoke workload
    (plus an AlexNet+decode mixed workload without ``--smoke``);
  * ``BENCH_serve_continuous.json`` — continuous batching over the paged
    KV block pool: min-of-5 throughput and latency percentiles for
    continuous vs static-drain vs sequential admission on a mixed-length
    generation workload (tokens asserted bitwise-identical across modes);
  * ``BENCH_tuning.json`` — the kernel autotuner: steady-state min-of-5
    wallclock per workload on the Pallas backend for ``tuning="off"`` vs
    ``"cached"`` crossed with fused vs unfused epilogues, plus the int8
    precision axis (quantized vs fp32 throughput and output SNR at the
    cached+fused operating point), so the perf trajectory of `engine.tune`
    is machine-readable. An ``int8_gate`` section measures cached+fused
    int8 vs fp32 on the alexnet_fc GEMM workload (the CI gate asserts
    int8 >= 1.0x fp32 there). ``--retune`` re-benchmarks the workloads' ops
    (fp32 and int8 tile entries) and refreshes
    ``.tuning/<device_kind>.json`` (the committed cache CI runs on).

  python -m benchmarks.run [--smoke] [--out BENCH_engine.json]
                           [--serve-out BENCH_serve.json]
                           [--continuous-out BENCH_serve_continuous.json]
                           [--tuning-out BENCH_tuning.json] [--retune]

``--smoke`` runs the fast CI path (regression gate): paper tables, the
engine JSON, the serve smoke workload, the tuning smoke workload, and no
heavy kernel/train microbenchmarks. The CI gates assert the smoke
workload's batched throughput stays >= 2x sequential at batch 8, that
tuned+fused is >= 1.2x the untuned+unfused baseline, and that the fused
epilogue is never slower than unfused beyond a 10% noise floor.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def bench_compiled_net(net: str, cfg=None) -> dict:
    """Analytic NetworkPlan aggregates + wall-clock of CompiledNet.apply."""
    import jax
    import jax.numpy as jnp

    from repro import engine as E
    from repro.models import cnn

    cfg = cfg or E.EngineConfig()
    compiled = E.compile(cnn.program(net), cfg)
    plan = compiled.plan

    key = jax.random.PRNGKey(0)
    h, w, c = cnn.CNNS[net].input_hw_c
    params = cnn.init_cnn(net, key)
    x = jax.random.normal(key, (1, h, w, c), jnp.float32) * 0.1

    t0 = time.perf_counter()
    jax.block_until_ready(compiled.apply(params, x))   # compile + first run
    t_first = time.perf_counter() - t0
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled.apply(params, x)
    jax.block_until_ready(out)
    t_steady = (time.perf_counter() - t0) / iters

    return {
        "net": net,
        "config": {"backend": cfg.backend, "policy": cfg.policy,
                   "interpret": cfg.interpret},
        "ops": len(compiled.plan.plans),
        "exec_ops": len(compiled.exec_pairs or ()),
        "analytic": {
            "conv_latency_ms": plan.conv_latency_s * 1e3,
            "fc_latency_ms": plan.fc_latency_s * 1e3,
            "conv_ma_mb": plan.conv_ma_bytes / 1e6,
            "fc_ma_mb": plan.fc_ma_bytes / 1e6,
            "conv_perf_efficiency": plan.conv_perf_efficiency,
            "fc_perf_efficiency": plan.fc_perf_efficiency,
            "total_macs": plan.total_macs,
        },
        "wallclock": {
            "first_call_s": t_first,
            "steady_call_s": t_steady,
            "batch": 1,
        },
    }


def emit_engine_json(path: str, nets, emit=print) -> None:
    results = {"bench": "engine_compiled_nets",
               "networks": [bench_compiled_net(net) for net in nets]}
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    for r in results["networks"]:
        emit(f"engine/compiled_{r['net']},"
             f"{r['wallclock']['steady_call_s']*1e6:.0f},"
             f"analytic_ms={r['analytic']['conv_latency_ms'] + r['analytic']['fc_latency_ms']:.1f};"
             f"eff={r['analytic']['conv_perf_efficiency']:.3f}")
    print(f"# wrote {path}", file=sys.stderr)


def bench_serve(smoke: bool) -> dict:
    """Scheduler throughput/latency per policy vs the sequential baseline.

    Smoke workload: 16 prefill-scoring requests (32 prompt tokens in,
    last-token logits out) of the reduced smollm_135m, packed into batch-8
    buckets. Per-request payloads are tiny, so the comparison isolates what
    batching actually buys: fewer dispatches and full GEMM row tiles (a
    batch-1 call is padded to the same row granularity a batch-8 call
    fills, see EngineConfig.row_align).
    """
    import jax
    import jax.numpy as jnp

    from repro import engine as E
    from repro.configs.base import reduced
    from repro.models import transformer as T
    from repro.serve import engine as SE
    from repro.serve.scheduler import Scheduler, latency_percentiles

    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    seq, n_req, max_batch = 32, 16, 8
    prog = SE.prefill_program(cfg, batch=1, seq=seq, logits_only=True)
    scfg = E.EngineConfig(row_align=8)

    def requests():
        return [{"tokens": jax.random.randint(jax.random.PRNGKey(i),
                                              (1, seq), 0, cfg.vocab_size)}
                for i in range(n_req)]

    # sequential baseline: same requests, one at a time, batch-1
    # CompiledNet. min-of-N on both sides: wall windows here are tens of
    # ms, so a single sample on a shared CI runner is noise-dominated.
    repeats = 5
    alone = E.compile(prog, scfg)
    reqs = requests()
    for r in reqs[:2]:                                     # warm the jit
        jax.block_until_ready(alone.apply(params, r))
    seq_wall = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for r in reqs:
            out = alone.apply(params, r)
        jax.block_until_ready(out)
        seq_wall = min(seq_wall, time.perf_counter() - t0)

    policies = {}
    for policy in ("fifo", "spf"):
        sched = Scheduler(config=scfg, policy=policy, max_batch=max_batch)
        sched.register("score", prog, shared_args=(params,))
        for r in requests():                               # warm the buckets
            sched.submit("score", r)
        sched.drain()
        wall, tickets = float("inf"), []
        for _ in range(repeats):
            tickets = [sched.submit("score", r) for r in requests()]
            t0 = time.perf_counter()
            sched.drain()
            wall = min(wall, time.perf_counter() - t0)
        stats = sched.stats()
        policies[policy] = {
            "wall_s": wall,
            "throughput_rps": n_req / wall,
            "batches": stats["models"]["score"]["batches"]
            // (repeats + 1),                              # per drain
            "occupancy": stats["models"]["score"]["occupancy"],
            **latency_percentiles(tickets),                # last repeat
        }

    result = {
        "bench": "serve_scheduler",
        "workload": {"program": prog.name, "requests": n_req,
                     "max_batch": max_batch,
                     "config": {"backend": scfg.backend,
                                "row_align": scfg.row_align}},
        "sequential": {"wall_s": seq_wall,
                       "throughput_rps": n_req / seq_wall},
        "policies": policies,
        "batched_vs_sequential_speedup":
            seq_wall / policies["fifo"]["wall_s"],
    }

    if not smoke:
        result["mixed"] = _bench_serve_mixed(scfg)
    return result


def _bench_serve_mixed(scfg) -> dict:
    """Heterogeneous workload: AlexNet forwards + decode steps in one
    queue, per policy — the paper's conv-and-FC-on-one-engine claim at
    serving granularity."""
    import jax
    import jax.numpy as jnp

    from repro import engine as E
    from repro.configs.base import reduced
    from repro.models import cnn, transformer as T
    from repro.serve import engine as SE
    from repro.serve.scheduler import Scheduler, latency_percentiles

    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    cnn_params = cnn.init_cnn("alexnet", jax.random.PRNGKey(1))
    dec_prog = SE.decode_program(cfg, batch=1, max_len=32)
    cnn_prog = cnn.program("alexnet")

    def submit_all(sched):
        tickets = []
        for i in range(12):
            st = T.init_decode_state(cfg, 1, 32)
            tickets.append(sched.submit(
                "decode", st, jnp.full((1, 1), i, jnp.int32)))
        for i in range(4):
            x = jax.random.normal(jax.random.PRNGKey(i),
                                  (1, 227, 227, 3), jnp.float32) * 0.1
            tickets.append(sched.submit("alexnet", x))
        return tickets

    out = {}
    for policy in ("fifo", "spf"):
        sched = Scheduler(config=scfg, policy=policy, max_batch=4)
        sched.register("decode", dec_prog,
                       shared_args=(params, jnp.int32(3)))
        sched.register("alexnet", cnn_prog, shared_args=(cnn_params,))
        submit_all(sched)
        sched.drain()           # warm every (program, bucket) jit
        macs_before = sched.stats()["plan_macs_served"]   # warm-up's share
        tickets = submit_all(sched)
        t0 = time.perf_counter()
        done = sched.drain()
        wall = time.perf_counter() - t0
        out[policy] = {
            "wall_s": wall,
            "throughput_rps": len(done) / wall,
            "completion_order": [t.model for t in done],
            "plan_macs_served":
                sched.stats()["plan_macs_served"] - macs_before,
            **latency_percentiles(tickets),
        }
    return out


def bench_serve_continuous(smoke: bool) -> dict:
    """Continuous batching (paged KV pool, per-step admission) vs the
    static drain-the-batch policy vs sequential, on a mixed-length greedy
    generation workload.

    The workload is bimodal on purpose (short 2-step and long 14-step
    requests interleaved, queue deeper than the batch): under drain
    admission the short requests finish early and their rows sit idle
    until the whole batch empties, while continuous admission refills
    them the same step. Decode runs at one fixed bucket (= max_batch) in
    both modes, so the comparison isolates utilization — same per-step
    cost, fewer steps. All three modes produce bitwise-identical tokens
    (the golden-parity contract); the bench asserts it while measuring.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs.base import reduced
    from repro.models import transformer as T
    from repro.serve.scheduler import ContinuousScheduler, \
        latency_percentiles

    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    n_req = 12 if smoke else 24
    max_batch, max_len, num_blocks, block_size = 4, 32, 64, 8
    work = []
    for i in range(n_req):
        plen = 4 if i % 2 else 8
        steps = 2 if i % 2 else 14
        work.append(([1 + (i * 7 + j) % 199 for j in range(plen)], steps))
    total_tokens = sum(n for _, n in work)

    repeats = 5
    modes = {"continuous": ("continuous", max_batch),
             "static": ("drain", max_batch),
             "sequential": ("continuous", 1)}
    out, tokens_by_mode = {}, {}
    for mode, (admission, mb) in modes.items():
        sched = ContinuousScheduler(
            cfg, params, max_len=max_len, num_blocks=num_blocks,
            block_size=block_size, max_batch=mb, buckets=(mb,),
            admission=admission)
        for p, n in work:                                  # warm the jits
            sched.submit(p, n)
        sched.run()
        wall, tickets = float("inf"), []
        for _ in range(repeats):
            tickets = [sched.submit(p, n) for p, n in work]
            t0 = time.perf_counter()
            sched.run()
            wall = min(wall, time.perf_counter() - t0)
        tokens_by_mode[mode] = [t.tokens for t in tickets]
        stats = sched.stats()
        out[mode] = {
            "wall_s": wall,
            "throughput_tps": total_tokens / wall,
            "decode_fill": stats["decode_fill"],
            "decode_steps_per_run": stats["steps"] // (repeats + 1),
            "evicted": stats["evicted"],
            "pool_free_low_water": stats["pool"]["free_low_water"],
            **latency_percentiles(tickets),
        }

    assert tokens_by_mode["continuous"] == tokens_by_mode["static"] \
        == tokens_by_mode["sequential"], \
        "golden-parity violation across serving modes"

    return {
        "bench": "serve_continuous",
        "workload": {"requests": n_req, "total_tokens": total_tokens,
                     "max_batch": max_batch, "max_len": max_len,
                     "num_blocks": num_blocks, "block_size": block_size,
                     "steps_mix": sorted({n for _, n in work})},
        "modes": out,
        "parity": "bitwise-identical tokens across modes",
        "continuous_vs_static_speedup":
            out["static"]["wall_s"] / out["continuous"]["wall_s"],
        "continuous_vs_sequential_speedup":
            out["sequential"]["wall_s"] / out["continuous"]["wall_s"],
    }


def emit_continuous_json(path: str, smoke: bool, emit=print) -> None:
    result = bench_serve_continuous(smoke)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    n = result["workload"]["total_tokens"]
    for mode, r in result["modes"].items():
        emit(f"serve_continuous/{mode},{r['wall_s']/n*1e6:.0f},"
             f"tps={r['throughput_tps']:.1f};fill={r['decode_fill']:.3f};"
             f"p95_ms={r['p95_ms']:.2f}")
    emit(f"serve_continuous/speedup,0,continuous_vs_static="
         f"{result['continuous_vs_static_speedup']:.2f}x;"
         f"continuous_vs_sequential="
         f"{result['continuous_vs_sequential_speedup']:.2f}x")
    print(f"# wrote {path}", file=sys.stderr)


def bench_serve_faults(smoke: bool) -> dict:
    """Graceful degradation under seeded fault injection: goodput of a
    faulted continuous-batching run vs the identical clean run.

    The faulted scheduler runs the numerics-guard program variants with a
    `FaultInjector` firing NaN storms, pool-exhaustion storms and latency
    spikes at fixed seeded rates. Measured quantities:

      * goodput — completed (status "done") tokens per second; failed
        requests' partial tokens don't count;
      * goodput_ratio — faulted / clean goodput, the degradation-ceiling
        gate CI enforces (a fault-tolerance layer that collapses under a
        few-percent fault rate is worse than fail-stop);
      * parity — requests the schedule never touched must match the
        clean run's tokens bitwise (the chaos-harness isolation property,
        re-asserted here on the bench workload).
    """
    import jax
    import jax.numpy as jnp

    from repro.configs.base import reduced
    from repro.models import transformer as T
    from repro.serve.faults import FaultInjector
    from repro.serve.scheduler import ContinuousScheduler, \
        latency_percentiles

    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    n_req = 8 if smoke else 16
    max_batch, max_len, num_blocks, block_size = 4, 32, 64, 8
    work = []
    for i in range(n_req):
        plen = 4 if i % 2 else 8
        steps = 2 if i % 2 else 14
        work.append(([1 + (i * 7 + j) % 199 for j in range(plen)], steps))

    rates = {"numerics": 0.01, "pool": 0.02, "latency": 0.05}

    def mk(**kw):
        return ContinuousScheduler(
            cfg, params, max_len=max_len, num_blocks=num_blocks,
            block_size=block_size, max_batch=max_batch,
            buckets=(max_batch,), **kw)

    def measure(sched, repeats):
        best = None
        for _ in range(repeats):
            tickets = [sched.submit(p, n) for p, n in work]
            t0 = time.perf_counter()
            sched.run()
            wall = time.perf_counter() - t0
            good = sum(len(t.tokens) for t in tickets
                       if t.status == "done")
            if best is None or good / wall > best[0]:
                best = (good / wall, wall, tickets)
        return best

    clean = mk()
    measure(clean, 1)                                      # warm the jits
    clean_tps, clean_wall, clean_tickets = measure(clean, 3)
    clean_tokens = [tuple(t.tokens) for t in clean_tickets]

    faulted = mk(faults=FaultInjector(seed=0, rates=rates,
                                      latency_s=0.001))
    measure(faulted, 1)                                    # warm (guarded)
    best = None
    for seed in (1, 2, 3):
        faulted.faults = FaultInjector(seed=seed, rates=rates,
                                       latency_s=0.001)
        faulted.pool.fault_site = faulted.fault_site       # unchanged
        tps, wall, tickets = measure(faulted, 1)
        if best is None or tps > best[0]:
            best = (tps, wall, tickets, seed)
    fault_tps, fault_wall, fault_tickets, best_seed = best

    # isolation parity on the bench workload: untouched requests match
    untouched = mismatches = 0
    for i, t in enumerate(fault_tickets):
        if (t.status == "done" and t.retries == 0
                and t.preemptions == 0 and t.migrations == 0):
            untouched += 1
            mismatches += tuple(t.tokens) != clean_tokens[i]
    assert mismatches == 0, \
        f"{mismatches} non-faulted request(s) diverged from the clean run"

    stats = faulted.stats()
    return {
        "bench": "serve_faults",
        "workload": {"requests": n_req, "max_batch": max_batch,
                     "max_len": max_len, "num_blocks": num_blocks,
                     "block_size": block_size},
        "rates": rates,
        "clean": {"wall_s": clean_wall, "goodput_tps": clean_tps,
                  **latency_percentiles(clean_tickets)},
        "faulted": {"wall_s": fault_wall, "goodput_tps": fault_tps,
                    "seed": best_seed,
                    "done": sum(t.status == "done"
                                for t in fault_tickets),
                    "failed": sum(t.status == "failed"
                                  for t in fault_tickets),
                    "retries": stats["retries"],
                    "latency_spikes": stats["latency_spikes"],
                    **latency_percentiles(fault_tickets)},
        "goodput_ratio": fault_tps / clean_tps,
        "untouched_requests": untouched,
        "parity": "non-faulted requests bitwise-identical to clean run",
    }


def emit_faults_json(path: str, smoke: bool, emit=print) -> None:
    result = bench_serve_faults(smoke)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    emit(f"serve_faults/clean,0,"
         f"tps={result['clean']['goodput_tps']:.1f}")
    emit(f"serve_faults/faulted,0,"
         f"tps={result['faulted']['goodput_tps']:.1f};"
         f"failed={result['faulted']['failed']};"
         f"retries={result['faulted']['retries']};"
         f"spikes={result['faulted']['latency_spikes']}")
    emit(f"serve_faults/degradation,0,"
         f"goodput_ratio={result['goodput_ratio']:.3f}")
    print(f"# wrote {path}", file=sys.stderr)


# ---------------------------------------------------------------------------
# Tuning bench: tuning="off"/"cached" x fused/unfused epilogues
# ---------------------------------------------------------------------------

# (n, m, act) dense stacks — dense-heavy on purpose: the FC mode is where
# the per-op tile choice dominates (one GEMM blocking per layer shape).
TUNING_WORKLOADS = {
    "mlp": {"batch": 8, "layers": ((1024, 2048, "relu"),
                                   (2048, 2048, "relu"),
                                   (2048, 512, None))},
    # AlexNet's FC stack (Table 4's FC side) — full mode only.
    "alexnet_fc": {"batch": 8, "layers": ((9216, 4096, "relu"),
                                          (4096, 4096, "relu"),
                                          (4096, 1000, None))},
}


def _dense_stack_fn(layers, fused: bool):
    """The workload forward: engine-routed dense stack, with bias+act
    either fused into each op's epilogue or applied as separate ops (the
    PR-3-era layer shape)."""
    import jax.numpy as jnp

    from repro import engine as E

    def fn(params, x):
        for (w, b), (_, _, act) in zip(params, layers):
            if fused:
                x = E.dense(x, w, bias=b, act=act, out_dtype=jnp.float32)
            else:
                x = E.dense(x, w, out_dtype=jnp.float32) + b
                if act is not None:
                    x = E.EPILOGUE_ACTS[act](x)
        return x
    return fn


def _tuning_workload(name: str, spec: dict):
    """(params, x, fused program, unfused program) for one dense stack."""
    import jax
    import jax.numpy as jnp

    from repro import engine as E

    batch, layers = spec["batch"], spec["layers"]
    key = jax.random.PRNGKey(0)
    params = []
    for n, m, _ in layers:
        key, kw = jax.random.split(key)
        params.append((jax.random.normal(kw, (n, m), jnp.float32)
                       * (2.0 / n) ** 0.5,
                       jnp.zeros((m,), jnp.float32)))
    params = tuple(params)
    x = jax.random.normal(key, (batch, layers[0][0]), jnp.float32)
    p_avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    x_aval = jax.ShapeDtypeStruct(x.shape, x.dtype)
    progs = {
        fused: E.trace_program(_dense_stack_fn(layers, fused),
                               p_avals, x_aval,
                               name=f"{name}_{'fused' if fused else 'unfused'}")
        for fused in (True, False)}
    return params, x, progs[True], progs[False]


def bench_tuning(smoke: bool, retune: bool = False) -> dict:
    """Steady-state wallclock of the Pallas backend per workload across
    {tuning off, cached} x {fused, unfused epilogues} x {fp32, int8},
    min-of-5.

    On a CPU host the Pallas kernels run in interpret mode, so those
    times are not TPU times — but the *ratios* exercise exactly what the
    autotuner controls: grid-step count and launch granularity per tile
    config, op count per fused epilogue, and arithmetic/traffic volume per
    precision. The int8 variant runs the full quantized path (per-call
    quantize + int8 kernel + fused dequant epilogue) at cached tiles and
    reports throughput against cached+fused fp32 plus the output SNR.
    """
    import jax

    from repro import engine as E
    from repro.core import quant

    repeats = 5
    names = ["mlp"] if smoke else list(TUNING_WORKLOADS)
    base = dict(backend="pallas")
    out = {"bench": "tuning",
           "device_kind": E.tune.device_kind(),
           "cache_path": str(E.tune.cache_path()),
           "workloads": []}
    for name in names:
        params, x, prog_fused, prog_unfused = _tuning_workload(
            name, TUNING_WORKLOADS[name])
        if retune:
            for prec in ("fp32", "int8"):
                tuned = E.tune.tune_program(
                    prog_fused.ops, E.EngineConfig(**base, tuning="autotune",
                                                   precision=prec))
                print(f"# retuned {name} [{prec}]: {tuned} op(s)",
                      file=sys.stderr)
        variants = {}
        outputs = {}
        runs = [(mode, fused, "fp32") for mode in ("off", "cached")
                for fused in (False, True)]
        runs.append(("cached", True, "int8"))
        for mode, fused, prec in runs:
            prog = prog_fused if fused else prog_unfused
            net = E.compile(prog, E.EngineConfig(**base, tuning=mode,
                                                 precision=prec))
            t0 = time.perf_counter()
            y = jax.block_until_ready(net.apply(params, x))
            t_first = time.perf_counter() - t0
            wall = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(net.apply(params, x))
                wall = min(wall, time.perf_counter() - t0)
            label = f"{mode}_{'fused' if fused else 'unfused'}" \
                + ("_int8" if prec == "int8" else "")
            outputs[label] = y
            variants[label] = {
                "first_call_s": t_first,
                "steady_call_s": wall,
                "tiles": [list(t) if t else None for t in net.tiles()],
            }
            if prec == "int8":
                variants[label]["precisions"] = list(net.precisions())
        row = {
            "name": name,
            "batch": TUNING_WORKLOADS[name]["batch"],
            "layers": [list(l[:2]) + [l[2]]
                       for l in TUNING_WORKLOADS[name]["layers"]],
            "variants": variants,
            # tuned+fused against the PR-3-era shape (default tiles,
            # separate bias/act ops) — the headline number
            "speedup_tuned_fused_vs_baseline":
                variants["off_unfused"]["steady_call_s"]
                / variants["cached_fused"]["steady_call_s"],
            "speedup_fused_vs_unfused":
                variants["cached_unfused"]["steady_call_s"]
                / variants["cached_fused"]["steady_call_s"],
            # the precision axis: quantized vs fp32 at the same (cached,
            # fused) operating point, plus output fidelity
            "speedup_int8_vs_fp32":
                variants["cached_fused"]["steady_call_s"]
                / variants["cached_fused_int8"]["steady_call_s"],
            "int8_snr_db": float(quant.snr_db(
                outputs["cached_fused"], outputs["cached_fused_int8"])),
        }
        out["workloads"].append(row)
    out["int8_gate"] = _bench_int8_gate(repeats)
    cache = E.tune.load_cache()
    out["cache_entries"] = len(cache.get("entries", {}))
    return out


def _bench_int8_gate(repeats: int) -> dict:
    """The int8-vs-fp32 CI gate measurement: cached+fused fp32 against
    cached+fused int8 on the alexnet_fc GEMM workload (the paper's FC
    side), min-of-N, plus output SNR.

    Runs cached tiles only — the untuned variants of this workload cost
    ~18 s/call in interpret mode and say nothing about the precision axis —
    so the gate stays cheap enough for the CI smoke path. alexnet_fc is
    the gate workload (not mlp) because its GEMMs are large enough that
    the int8 path's structural win (bigger tiles fit VMEM at 1 byte/elt →
    fewer grid steps; half the operand traffic) dominates the per-call
    quantization overhead; on the small mlp stack that overhead rivals
    the entire fp32 runtime under CPU interpret mode, which measures the
    quantize ops, not the datapath the gate protects.
    """
    import jax

    from repro import engine as E
    from repro.core import quant

    params, x, prog_fused, _ = _tuning_workload(
        "alexnet_fc", TUNING_WORKLOADS["alexnet_fc"])
    out = {"workload": "alexnet_fc"}
    ys = {}
    for prec in ("fp32", "int8"):
        net = E.compile(prog_fused, E.EngineConfig(
            backend="pallas", tuning="cached",
            precision=prec))
        ys[prec] = jax.block_until_ready(net.apply(params, x))
        wall = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(net.apply(params, x))
            wall = min(wall, time.perf_counter() - t0)
        out[f"{prec}_steady_call_s"] = wall
        out[f"{prec}_tiles"] = [list(t) if t else None for t in net.tiles()]
    out["speedup_int8_vs_fp32"] = (out["fp32_steady_call_s"]
                                   / out["int8_steady_call_s"])
    out["int8_snr_db"] = float(quant.snr_db(ys["fp32"], ys["int8"]))
    return out


def emit_tuning_json(path: str, smoke: bool, retune: bool,
                     emit=print) -> None:
    result = bench_tuning(smoke, retune)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    for row in result["workloads"]:
        for variant, r in row["variants"].items():
            emit(f"tuning/{row['name']}_{variant},"
                 f"{r['steady_call_s']*1e6:.0f},")
        emit(f"tuning/{row['name']}_speedup,0,"
             f"tuned_fused_vs_baseline="
             f"{row['speedup_tuned_fused_vs_baseline']:.2f}x;"
             f"fused_vs_unfused={row['speedup_fused_vs_unfused']:.2f}x;"
             f"int8_vs_fp32={row['speedup_int8_vs_fp32']:.2f}x;"
             f"int8_snr_db={row['int8_snr_db']:.1f}")
    g = result["int8_gate"]
    emit(f"tuning/int8_gate_{g['workload']},0,"
         f"int8_vs_fp32={g['speedup_int8_vs_fp32']:.2f}x;"
         f"int8_snr_db={g['int8_snr_db']:.1f}")
    print(f"# wrote {path}", file=sys.stderr)


def emit_serve_json(path: str, smoke: bool, emit=print) -> None:
    result = bench_serve(smoke)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    seq = result["sequential"]
    for pol, r in result["policies"].items():
        emit(f"serve/batched_{pol},{r['wall_s']/result['workload']['requests']*1e6:.0f},"
             f"rps={r['throughput_rps']:.1f};p95_ms={r['p95_ms']:.2f};"
             f"occupancy={r['occupancy']:.2f}")
    emit(f"serve/sequential,{seq['wall_s']/result['workload']['requests']*1e6:.0f},"
         f"rps={seq['throughput_rps']:.1f}")
    emit(f"serve/speedup,0,batched_vs_sequential="
         f"{result['batched_vs_sequential_speedup']:.2f}x")
    print(f"# wrote {path}", file=sys.stderr)


def _bench_parallel_mode(mode: dict) -> dict:
    """One serve-parallel mode: the smoke prefill-scoring workload of
    `bench_serve` through a `Scheduler` over the first data x model
    devices, min-of-5 drains."""
    import jax
    import jax.numpy as jnp

    from repro import engine as E
    from repro.configs.base import reduced
    from repro.engine.parallel import ParallelConfig, make_mesh
    from repro.models import transformer as T
    from repro.serve import engine as SE
    from repro.serve.scheduler import Scheduler, latency_percentiles

    cfg = reduced("smollm_135m")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    seq, n_req, max_batch, repeats = 32, 16, 8, 5
    prog = SE.prefill_program(cfg, batch=1, seq=seq, logits_only=True)
    mesh = None
    scfg = E.EngineConfig(row_align=8)
    if mode["data"] * mode["model"] > 1:
        pcfg = ParallelConfig(data=mode["data"], model=mode["model"],
                              policy=mode["policy"])
        scfg = scfg.replace(parallel=pcfg)
        mesh = make_mesh(pcfg)

    def requests():
        return [{"tokens": jax.random.randint(jax.random.PRNGKey(i),
                                              (1, seq), 0, cfg.vocab_size)}
                for i in range(n_req)]

    sched = Scheduler(config=scfg, max_batch=max_batch, mesh=mesh)
    sched.register("score", prog, shared_args=(params,))
    sched.warmup("score")               # every (bucket, replica) pre-paid
    wall, tickets = float("inf"), []
    for _ in range(repeats):
        tickets = [sched.submit("score", r) for r in requests()]
        t0 = time.perf_counter()
        sched.drain()
        wall = min(wall, time.perf_counter() - t0)
    return {"devices": mode["data"] * mode["model"],
            "replicas": sched.stats()["replicas"],
            "wall_s": wall,
            "throughput_rps": n_req / wall,
            **latency_percentiles(tickets, (50, 95, 99))}


def parallel_modes(n_devices: int) -> dict:
    """mode name -> (data, model, per-op policy) over the devices present:
    one device, one replica per device, and 4-way model sharding with the
    remaining devices as replicas (when 4 divides the device count)."""
    modes = {"single": {"data": 1, "model": 1, "policy": "auto"}}
    if n_devices > 1:
        modes["replicated"] = {"data": n_devices, "model": 1,
                               "policy": "auto"}
    if n_devices % 4 == 0:
        modes["sharded"] = {"data": n_devices // 4, "model": 4,
                            "policy": "auto"}
    return modes


def bench_serve_parallel(smoke: bool) -> dict:
    """Scheduler throughput on one device vs every device, replica-spread
    vs sharded vs single-device — the smoke prefill-scoring workload of
    `bench_serve`, min-of-5 drains per mode.

    All modes run in this process, over subsets of `jax.devices()`: a chip
    belongs to one process at a time. On a CPU host, get several devices
    with `XLA_FLAGS=--xla_force_host_platform_device_count=8` set before
    the run; they split one socket's FLOPs, so their ratios are overhead
    ratios (dispatch, collectives, shard_map), not scaling.
    """
    import jax

    modes = {name: {**m, **_bench_parallel_mode(m)}
             for name, m in parallel_modes(len(jax.devices())).items()}
    single = modes["single"]["throughput_rps"]
    return {
        "bench": "serve_parallel",
        "workload": {"program": "smollm-prefill32-logits", "requests": 16,
                     "max_batch": 8, "repeats": 5},
        "modes": modes,
        **{f"{name}_vs_single": modes[name]["throughput_rps"] / single
           for name in ("replicated", "sharded") if name in modes},
    }


def emit_parallel_json(path: str, smoke: bool, emit=print) -> None:
    result = bench_serve_parallel(smoke)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    n_req = result["workload"]["requests"]
    for name, m in result["modes"].items():
        emit(f"serve_parallel/{name},{m['wall_s']/n_req*1e6:.0f},"
             f"rps={m['throughput_rps']:.1f};devices={m['devices']};"
             f"replicas={m['replicas']};p95_ms={m['p95_ms']:.2f}")
    ratios = ";".join(f"{k}={v:.2f}x" for k, v in result.items()
                      if k.endswith("_vs_single"))
    emit(f"serve_parallel/scaling,0,{ratios}")
    print(f"# wrote {path}", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI path: alexnet only, no kernel/train bench")
    ap.add_argument("--out", default="BENCH_engine.json",
                    help="machine-readable engine bench output path")
    ap.add_argument("--serve-out", default="BENCH_serve.json",
                    help="machine-readable serve-scheduler bench output path")
    ap.add_argument("--continuous-out", default="BENCH_serve_continuous.json",
                    help="machine-readable continuous-batching bench "
                         "output path")
    ap.add_argument("--tuning-out", default="BENCH_tuning.json",
                    help="machine-readable kernel-tuning bench output path")
    ap.add_argument("--parallel-out", default="BENCH_serve_parallel.json",
                    help="machine-readable multi-device serve bench "
                         "output path")
    ap.add_argument("--retune", action="store_true",
                    help="autotune the tuning-bench workloads first and "
                         "refresh .tuning/<device_kind>.json")
    ap.add_argument("--faults", action="store_true",
                    help="run ONLY the fault-injection degradation bench "
                         "(clean vs faulted goodput on the continuous "
                         "scheduler)")
    ap.add_argument("--faults-out", default="BENCH_serve_faults.json",
                    help="machine-readable fault-degradation bench "
                         "output path")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()

    if args.faults:
        print("name,us_per_call,derived")
        emit_faults_json(args.faults_out, args.smoke)
        return

    from benchmarks import paper_tables
    print("name,us_per_call,derived")
    # Paper tables are analytic (no wall time): emit as derived rows,
    # straight off the whole-network engine plan.
    for net, paper in paper_tables.PAPER_TABLE4.items():
        np_ = paper_tables.network_plan(net)
        print(f"paper_table4/{net}_conv,{np_.conv_latency_s*1e6:.0f},"
              f"eff={np_.conv_perf_efficiency:.3f};paper_ms={paper[0]};"
              f"MA_MB={np_.conv_ma_bytes/1e6:.1f}")
        print(f"paper_table4/{net}_fc,{np_.fc_latency_s*1e6:.0f},"
              f"eff={np_.fc_perf_efficiency:.3f};paper_ms={paper[1]};"
              f"MA_MB={np_.fc_ma_bytes/1e6:.1f}")
    for net, filt, s, t in paper_tables.table2_rows():
        print(f"paper_table2/{net}_{filt}_s{s},0,T={t}")
    for filt, s, n_eff, p_eff in paper_tables.table3_rows():
        print(f"paper_table3/{filt}_s{s},0,N_eff={n_eff};p_eff={p_eff}")

    nets = ["alexnet"] if args.smoke else ["alexnet", "vgg16", "resnet50"]
    emit_engine_json(args.out, nets)
    emit_serve_json(args.serve_out, args.smoke)
    emit_continuous_json(args.continuous_out, args.smoke)
    emit_tuning_json(args.tuning_out, args.smoke, args.retune)
    emit_parallel_json(args.parallel_out, args.smoke)

    if not args.smoke:
        from benchmarks import kernel_bench
        kernel_bench.run_all()

    print("", file=sys.stderr)
    print("full paper tables: PYTHONPATH=src python -m benchmarks.paper_tables",
          file=sys.stderr)


if __name__ == "__main__":
    main()
