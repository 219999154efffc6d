"""Bring-up proof: the paper's main path on a TPU, at published widths.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the multi-device path, on four chips

One chip runs three phases through the entry points a user calls:

  alexnet_pallas  AlexNet (227x227x3, ~61 M fp32 parameters from a seed) at
                  batch 8, compiled with `engine.compile(cnn.program(...),
                  EngineConfig(backend="pallas"))`: every conv (conv1 at
                  S=4) and every FC runs through a compiled GFID kernel.
                  The compiled program must hold one `tpu_custom_call` per
                  planned Pallas op.
  resnet50_auto   ResNet-50 at batch 8 under `policy="auto"`: the 1x1 convs,
                  stride-2 ones included, and the FC run on Pallas.
  serve_alexnet   A `Scheduler(max_batch=8)` answers 12 AlexNet requests
                  (buckets 8 and 4); every result is checked against the
                  request's batch-1 `CompiledNet.apply`.

`--chips 4` runs only what exists across chips, each against one chip:

  alexnet_sharded AlexNet under `ParallelConfig(data=1, model=4)`.
  serve_replicas  A `Scheduler` over a (data=4, model=1) mesh, one replica
                  per device, 16 requests in batches of 4.

The two forwards run at default precision and are compared with the same
net on `backend="ref"` on the same chip under
`jax.default_matmul_precision("highest")`; each is also traced again under
"highest", which the Pallas kernels honour, and compared with the same ref
at a tolerance far below bf16 rounding. Errors are normwise: max |y - ref|
and max |y - ref| / max |ref|. A phase fails when a relative error passes
the tolerance printed beside it (see TOLERANCES), when a kernel fell back
to another backend, or when anything raises; the script then exits
non-zero. It exits non-zero without running anything when JAX finds
no TPU: there is no CPU fallback. The last line of a run that passed is
`{"ok": true, "device": {...}}`.

JAX's persistent compile cache sits in $JAX_COMPILATION_CACHE_DIR when set,
else in `.jax_cache/` beside this file (`repro.launch.compile_cache`); a
second run reports lower compile seconds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SEED = 0
BATCH = 8

# Tolerances on the normwise relative error against ref at "highest", per
# phase: (forward at default precision, forward traced at "highest"). At
# default precision the MXU multiplies bf16-rounded f32 operands, in the
# Pallas kernels as in XLA (ref itself at default precision is 5.5e-3 and
# 4.2e-3 from ref at "highest"); at "highest" both multiply in f32. Each
# tolerance is about twice the error read on a TPU v5e with SEED:
# AlexNet 4.80e-3 and 6.8e-7, ResNet-50 4.27e-3 and 2.8e-7.
TOLERANCES = {
    "alexnet_pallas": (1e-2, 1.5e-6),
    "resnet50_auto": (9e-3, 6e-7),
}
TOL_SAME_PATH = 1e-5    # one config: batched vs batch-1, sharded vs not


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


def rel_err(y, ref) -> tuple:
    import numpy as np
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        raise AssertionError(f"output shape {y.shape} (want {ref.shape}) "
                             f"or non-finite values")
    err = float(np.abs(y - ref).max())
    return err, err / float(np.abs(ref).max())


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def compile_net(E, prog, cfg, *args):
    """engine.compile + AOT compile of `.apply` at `args`; (net, compiled
    executable, compile seconds)."""
    import jax
    t0 = time.perf_counter()
    net = E.compile(prog, cfg)
    exe = jax.jit(net.apply).lower(*args).compile()
    return net, exe, time.perf_counter() - t0


def forward_highest(E, prog, cfg, *args):
    """`prog` compiled under `cfg` and run, traced at "highest" precision."""
    import jax
    with jax.default_matmul_precision("highest"):
        net = E.compile(prog, cfg)
        return jax.block_until_ready(net.apply(*args))


def compare_with_ref(E, prog, cfg, y, *args) -> dict:
    """Errors against ref at "highest" of `y` (default precision) and of
    the same config traced at "highest"."""
    ref = forward_highest(E, prog, E.EngineConfig(backend="ref"), *args)
    err, rel = rel_err(y, ref)
    err_hi, rel_hi = rel_err(forward_highest(E, prog, cfg, *args), ref)
    return dict(max_abs_err=err, max_rel_err=rel,
                highest_max_abs_err=err_hi, highest_max_rel_err=rel_hi)


def check_errors(phase: str, errs: dict) -> None:
    tol, tol_hi = TOLERANCES[phase]
    rel, rel_hi = errs["max_rel_err"], errs["highest_max_rel_err"]
    check(rel <= tol, f"max_rel_err {rel} > {tol}")
    check(rel_hi <= tol_hi, f"highest_max_rel_err {rel_hi} > {tol_hi}")


def alexnet_inputs(cnn, batch: int):
    import jax
    kp, kx = jax.random.split(jax.random.PRNGKey(SEED))
    params = cnn.init_cnn("alexnet", kp)
    x = jax.random.normal(kx, (batch, 227, 227, 3))
    return params, x


def phase_alexnet_pallas(E, cnn) -> None:
    import jax
    params, x = alexnet_inputs(cnn, BATCH)
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(params))
    prog = cnn.program("alexnet", batch=BATCH)
    cfg = E.EngineConfig(backend="pallas", fallback="none")
    net, exe, compile_s = compile_net(E, prog, cfg, params, x)
    n_pallas = net.backends().count("pallas")
    n_calls = exe.as_text().count('custom_call_target="tpu_custom_call"')
    conv1_op, conv1_plan = net.exec_pairs[0]
    t0 = time.perf_counter()
    y = jax.block_until_ready(exe(params, x))
    run_s = time.perf_counter() - t0
    errs = compare_with_ref(E, prog, cfg, y, params, x)
    tol, tol_hi = TOLERANCES["alexnet_pallas"]
    emit(phase="alexnet_pallas", params=int(n_params),
         logits=list(y.shape), pallas_ops=n_pallas,
         tpu_custom_calls=n_calls,
         conv1=f"{conv1_op.w_shape[0]}x{conv1_op.w_shape[1]}"
               f" S={conv1_op.stride} on {conv1_plan.backend}",
         compile_s=compile_s, first_run_s=run_s, **errs, tol=tol,
         highest_tol=tol_hi, peak_bytes=peak_bytes())
    check(n_pallas == len(net.exec_pairs) == 8,
          f"{n_pallas} of {len(net.exec_pairs)} ops planned on Pallas")
    check(n_calls == n_pallas,
          f"{n_calls} tpu_custom_calls for {n_pallas} Pallas ops")
    check(conv1_op.stride == 4 and conv1_plan.backend == "pallas",
          "conv1 (S=4) is not a Pallas op")
    check_errors("alexnet_pallas", errs)


def phase_resnet50_auto(E, cnn) -> None:
    import jax
    kp, kx = jax.random.split(jax.random.PRNGKey(SEED + 1))
    params = cnn.init_cnn("resnet50", kp)
    x = jax.random.normal(kx, (BATCH, 224, 224, 3))
    prog = cnn.program("resnet50", batch=BATCH)
    cfg = E.EngineConfig(policy="auto", fallback="none")
    net, exe, compile_s = compile_net(E, prog, cfg, params, x)
    on_pallas = [op for op, plan in net.exec_pairs if plan.backend == "pallas"]
    strided = [op for op in on_pallas if op.kind == "conv2d" and op.stride > 1]
    n_calls = exe.as_text().count('custom_call_target="tpu_custom_call"')
    t0 = time.perf_counter()
    y = jax.block_until_ready(exe(params, x))
    run_s = time.perf_counter() - t0
    errs = compare_with_ref(E, prog, cfg, y, params, x)
    tol, tol_hi = TOLERANCES["resnet50_auto"]
    emit(phase="resnet50_auto", logits=list(y.shape),
         ops=len(net.exec_pairs), pallas_ops=len(on_pallas),
         pallas_strided_convs=len(strided), tpu_custom_calls=n_calls,
         compile_s=compile_s, first_run_s=run_s, **errs, tol=tol,
         highest_tol=tol_hi, peak_bytes=peak_bytes())
    check(len(strided) > 0, "no stride-2 1x1 conv planned on Pallas")
    check(n_calls == len(on_pallas),
          f"{n_calls} tpu_custom_calls for {len(on_pallas)} Pallas ops")
    check_errors("resnet50_auto", errs)


def serve_and_compare(E, cnn, sched, params, xs, solo_cfg, solo_device):
    """Submit one request per input, drain, and compare every result with
    the batch-1 `CompiledNet.apply` of `solo_cfg` on `solo_device`."""
    import jax
    import numpy as np
    sched.register("alexnet", cnn.program("alexnet"), shared_args=(params,))
    tickets = [sched.submit("alexnet", x) for x in xs]
    t0 = time.perf_counter()
    done = sched.drain()
    drain_s = time.perf_counter() - t0
    solo = E.compile(cnn.program("alexnet"), solo_cfg)
    with jax.default_device(solo_device):
        want = [solo.apply(params, x) for x in xs]
    bitwise = all(np.array_equal(np.asarray(t.result), np.asarray(w))
                  for t, w in zip(tickets, want))
    rel = max(rel_err(t.result, w)[1] for t, w in zip(tickets, want))
    check(len(done) == len(xs) and all(t.done for t in tickets),
          "not every request was answered")
    return tickets, drain_s, bitwise, rel


def phase_serve_alexnet(E, cnn) -> None:
    import jax
    from repro.serve.scheduler import Scheduler
    params, xb = alexnet_inputs(cnn, 12)
    xs = [xb[i:i + 1] for i in range(12)]
    cfg = E.EngineConfig(row_align=8, policy="auto")
    sched = Scheduler(config=cfg, max_batch=8)
    tickets, drain_s, bitwise, rel = serve_and_compare(
        E, cnn, sched, params, xs, cfg, jax.devices()[0])
    st = sched.stats()
    buckets = sorted({t.batch_bucket for t in tickets})
    emit(phase="serve_alexnet", requests=len(tickets), buckets=buckets,
         batches=st["batches"], fallbacks=st["fallbacks"],
         drain_s_with_compile=drain_s, bitwise_vs_batch1=bitwise,
         max_rel_err=rel, tol=TOL_SAME_PATH, peak_bytes=peak_bytes())
    check(buckets == [4, 8], f"buckets {buckets}, expected [4, 8]")
    check(not st["fallbacks"], f"kernel fallbacks: {st['fallbacks']}")
    check(rel <= TOL_SAME_PATH, f"max_rel_err {rel} > {TOL_SAME_PATH}")


def phase_alexnet_sharded(E, cnn) -> None:
    import jax
    from repro.engine.parallel import ParallelConfig
    params, x = alexnet_inputs(cnn, BATCH)
    prog = cnn.program("alexnet", batch=BATCH)
    base = E.EngineConfig(backend="pallas", fallback="none")
    net, exe, compile_s = compile_net(
        E, prog, base.replace(parallel=ParallelConfig(data=1, model=4)),
        params, x)
    y = jax.block_until_ready(exe(params, x))
    with jax.default_device(jax.devices()[0]):
        one = jax.block_until_ready(E.compile(prog, base).apply(params, x))
    err, rel = rel_err(y, one)
    shards = net.shards()
    emit(phase="alexnet_sharded", mesh=dict(net.mesh.shape),
         devices=len(net.mesh.devices.flat), shards=list(shards),
         compile_s=compile_s, max_abs_err=err, max_rel_err=rel,
         tol=TOL_SAME_PATH)
    check(len(net.mesh.devices.flat) == 4, "mesh is not 4 devices")
    check(any(s != "replicate" for s in shards), "no op was sharded")
    check(rel <= TOL_SAME_PATH, f"max_rel_err {rel} > {TOL_SAME_PATH}")


def phase_serve_replicas(E, cnn) -> None:
    import jax
    from repro.engine.parallel import ParallelConfig, make_mesh
    from repro.serve.scheduler import Scheduler
    params, xb = alexnet_inputs(cnn, 16)
    xs = [xb[i:i + 1] for i in range(16)]
    cfg = E.EngineConfig(row_align=8, policy="auto")
    pcfg = ParallelConfig(data=4, model=1)
    sched = Scheduler(config=cfg.replace(parallel=pcfg), max_batch=4,
                      mesh=make_mesh(pcfg))
    tickets, drain_s, bitwise, rel = serve_and_compare(
        E, cnn, sched, params, xs, cfg, jax.devices()[0])
    replicas = sorted({t.batch_replica for t in tickets})
    devices = sorted({d.id for t in tickets for d in t.result.devices()})
    emit(phase="serve_replicas", requests=len(tickets), replicas=replicas,
         result_devices=devices, fallbacks=sched.stats()["fallbacks"],
         drain_s_with_compile=drain_s, bitwise_vs_one_chip=bitwise,
         max_rel_err=rel, tol=TOL_SAME_PATH)
    check(replicas == [0, 1, 2, 3], f"replicas used: {replicas}")
    check(len(devices) == 4, f"results landed on devices {devices}")
    check(not sched.stats()["fallbacks"], "kernel fallbacks")
    check(rel <= TOL_SAME_PATH, f"max_rel_err {rel} > {TOL_SAME_PATH}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    # the repo first: without it the script fails before it prints a line
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import engine as E
    from repro.launch import compile_cache
    from repro.models import cnn

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {device}; this script has no CPU "
              "fallback", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{device}", file=sys.stderr)
        return 2
    emit(phase="device", **device)
    emit(phase="compile_cache", dir=str(compile_cache.enable()))

    phases = ([phase_alexnet_sharded, phase_serve_replicas]
              if args.chips == 4 else
              [phase_alexnet_pallas, phase_resnet50_auto,
               phase_serve_alexnet])
    for phase in phases:
        try:
            phase(E, cnn)
        except Exception as e:
            print(f"phase {phase.__name__[6:]} FAILED: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            raise
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
