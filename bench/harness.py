"""One run of one cell: set-up, a measured window, the check, the metrics.

The system under test is the program's own serving path:

  closed loop  `engine.compile(cnn.program(net).with_batch(B), cfg).apply`,
               one batch after another, each ending in block_until_ready,
               with the next ones dispatched before the wait
  open loop    `Scheduler.submit` / `Scheduler.step` of a `Scheduler` that
               serves `cnn.program(net)`, with requests submitted when they
               fall due

Everything else is the benchmark's: weights and inputs from the seed, the
schedule, the clocks, the trace reduction, the plain reference and the
comparison. The window records host-clock times only; the metric readers
(bench/metrics/<name>.py) turn a finished `Run` into numbers.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from bench import generator, spec
from bench import trace as tr

REF_BLOCK = 16          # reference rows per call: one compiled shape
POLL_S = 50e-6          # readiness poll period while batches are in flight
AHEAD = 3               # closed loop: batches dispatched and not yet waited on
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take it as `ctx`."""

    cell: Dict
    cfg: Dict
    mix: Dict
    peaks: Dict
    seed: int
    chips: int
    devices: List[int]
    setup_s: float = 0.0
    setup_parts: Dict[str, float] = dataclasses.field(default_factory=dict)
    t0: float = 0.0                 # window start, host perf_counter s
    t1: float = 0.0                 # last completion in the window
    batches: List[int] = dataclasses.field(default_factory=list)  # real rows
    steps: List[tuple] = dataclasses.field(default_factory=list)  # (s, e)
    completions: List[float] = dataclasses.field(default_factory=list)
    due: Optional[np.ndarray] = None        # per request, host s
    submitted: Optional[np.ndarray] = None
    dispatched: Optional[np.ndarray] = None
    done: Optional[np.ndarray] = None
    served: int = 0                 # scheduler counters over the window
    padded_slots: int = 0
    compiles_in_window: int = 0
    events: Optional[Dict] = None   # bench.trace.read() of a traced window
    trace_span: Optional[tuple] = None   # bench.window span, profiler ns

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def images(self) -> int:
        return int(sum(self.batches))


def seed_key(seed: int):
    """A PRNG key from any whole number, 64-bit ones included."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


class _Compiles:
    """Counts executables built or loaded while `on`."""

    def __init__(self):
        import jax.monitoring as mon
        self.on, self.count, self.hits, self.misses = False, 0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == BACKEND_COMPILE and self.on:
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class _Collections:
    """Python garbage collections while `on`: count and longest pause per
    generation, for the log."""

    def __init__(self):
        self.on, self.t = False, 0.0
        self.pauses: Dict[int, List[float]] = {0: [], 1: [], 2: []}
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.on:
            self.pauses[info["generation"]].append(time.perf_counter()
                                                   - self.t)

    def summary(self) -> str:
        return ", ".join(f"gen{g} {len(p)} (longest {max(p, default=0) * 1e3:.1f} ms)"
                         for g, p in self.pauses.items())


def _annotate(trace: bool):
    if not trace:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _engine_config(cfg: Dict, served: bool = False):
    """The configuration's EngineConfig; a served one adds the settings
    the configuration gives for serving (`served_engine`)."""
    from repro import engine as E
    kw = dict(cfg["engine"])
    if served:
        kw.update(cfg.get("served_engine", {}))
    return E.EngineConfig(**kw)


def pool_size(mix: Dict) -> int:
    """Distinct input images a mix serves."""
    if mix["loop"] == "closed":
        return mix["batch"] * mix["input_batches"]
    return mix["pool"]


def input_pool(cfg: Dict, mix: Dict, key):
    """The mix's standard-normal images, in one jitted call."""
    import jax
    import jax.numpy as jnp
    shape = (pool_size(mix), *cfg["input"])
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(key)


def seed_keys(seed: int):
    """(weights key, inputs key) of a seed."""
    import jax
    return jax.random.split(seed_key(seed))


def check_params(params, prog) -> None:
    """The benchmark's weights must have the program's parameter shapes."""
    import jax
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                  prog.in_avals[0])
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise spec.SpecError("the configuration's weights do not match the "
                             "program's parameter shapes")


class Closed:
    """Batches of `batch` back to back through one CompiledNet."""

    def __init__(self, run: Run, params, key, parts):
        import jax
        from repro import engine as E
        from repro.models import cnn
        b, nb = run.mix["batch"], run.mix["input_batches"]
        t = time.perf_counter()
        pool = input_pool(run.cfg, run.mix, key)
        self.xs = jax.jit(lambda p: tuple(p[i * b:(i + 1) * b]
                                          for i in range(nb)))(pool)
        self.pool = pool
        jax.block_until_ready(self.xs)
        parts["inputs_s"] = time.perf_counter() - t
        t = time.perf_counter()
        prog = cnn.program(run.cfg["program"]).with_batch(b)
        self.net = E.compile(prog, _engine_config(run.cfg))
        self.params = params
        check_params(params, prog)
        jax.block_until_ready(self.net.apply(params, self.xs[0]))
        parts["compile_s"] = time.perf_counter() - t
        t = time.perf_counter()
        for x in self.xs:
            jax.block_until_ready(self.net.apply(params, x))
        parts["warmup_s"] = time.perf_counter() - t
        self.outs: List[Any] = []

    def window(self, run: Run, seconds: float, annotate,
               rate_per_s: Optional[float] = None) -> None:
        """Batches back to back, AHEAD of them dispatched before the oldest
        is waited on, so that the device does not wait on a host hiccup
        shorter than AHEAD - 1 batches. Every batch ends in
        block_until_ready; no batch is dispatched after `seconds`, and the
        window ends when the last one dispatched has completed."""
        apply, params, xs = self.net.apply, self.params, self.xs
        outs, ends = self.outs, run.completions
        pending: collections.deque = collections.deque()
        t0 = t1 = time.perf_counter()
        deadline = t0 + seconds
        n = 0
        with annotate("bench.window"):
            while True:
                while len(pending) < AHEAD and t1 < deadline:
                    with annotate("bench.apply"):
                        pending.append(apply(params, xs[n % len(xs)]))
                    n += 1
                if not pending:
                    break
                y = pending.popleft()
                with annotate("bench.wait"):
                    y.block_until_ready()
                outs.append(y)
                t1 = time.perf_counter()
                ends.append(t1)
        run.t0, run.t1 = t0, t1
        run.batches = [len(outs[0])] * len(outs)

    def answers(self, run: Run):
        """(answers, pool row each answer is for, number missing)."""
        import jax
        b, nb = run.mix["batch"], run.mix["input_batches"]
        got = np.concatenate(jax.device_get(self.outs))
        rows = np.concatenate([(j % nb) * b + np.arange(b)
                               for j in range(len(self.outs))])
        return got, rows, 0

    def release(self) -> None:
        self.net = self.xs = None
        self.outs = []


class Open:
    """Requests of one image submitted to a Scheduler when they fall due."""

    def __init__(self, run: Run, params, key, parts):
        import jax
        from repro.models import cnn
        from repro.serve.scheduler import Scheduler
        mix = run.mix
        t = time.perf_counter()
        n = mix["pool"]
        self.pool = input_pool(run.cfg, mix, key)
        self.requests = jax.jit(
            lambda p: tuple(p[i:i + 1] for i in range(n)))(self.pool)
        jax.block_until_ready(self.requests)
        parts["inputs_s"] = time.perf_counter() - t
        t = time.perf_counter()
        sch = mix["scheduler"]
        self.sched = Scheduler(
            config=_engine_config(run.cfg, served=True),
            policy=sch.get("policy", "fifo"), max_batch=sch["max_batch"],
            buckets=sch.get("buckets"))
        prog = cnn.program(run.cfg["program"])
        check_params(params, prog)
        self.sched.register("net", prog, shared_args=(params,))
        self.sched.warmup()
        parts["compile_s"] = time.perf_counter() - t
        # then the path the window drives, every bucket
        t = time.perf_counter()
        for b in self.sched.buckets:
            ts = [self.sched.submit("net", self.requests[i])
                  for i in range(b)]
            self.sched.step()
            jax.block_until_ready([tk.result for tk in ts])
        parts["warmup_s"] = time.perf_counter() - t
        self.tickets: List[Any] = []

    def _counters(self):
        m = self.sched.stats()["models"]["net"]
        return m["served"], m["padded_slots"]

    def window(self, run: Run, seconds: float, annotate,
               rate_per_s: Optional[float] = None) -> None:
        sched, reqs = self.sched, self.requests
        offsets, idx = generator.arrivals(run.mix, run.seed, seconds,
                                          rate_per_s)
        self.idx = idx
        n = len(offsets)
        sub = np.zeros(n)
        disp = np.zeros(n)
        done = np.full(n, np.nan)
        tickets: List[Any] = [None] * n
        where: Dict[int, int] = {}
        # in dispatch order: (tickets of a batch, their request indices)
        inflight: collections.deque = collections.deque()
        served0, padded0 = self._counters()
        i = 0
        t0 = time.perf_counter()
        due = t0 + offsets
        with annotate("bench.window"):
            while True:
                now = time.perf_counter()
                if i < n and due[i] <= now:
                    with annotate("bench.submit"):
                        while i < n and due[i] <= now:
                            tk = sched.submit("net", reqs[idx[i]])
                            tickets[i] = tk
                            where[tk.rid] = i
                            sub[i] = time.perf_counter()
                            i += 1
                if sched.pending():
                    ts = time.perf_counter()
                    with annotate("bench.step"):
                        batch = sched.step()
                    run.steps.append((ts, time.perf_counter()))
                    js = [where[tk.rid] for tk in batch]
                    disp[js] = ts
                    run.batches.append(len(batch))
                    inflight.append((batch, js))
                if inflight:
                    with annotate("bench.poll"):
                        while inflight and all(tk.result.is_ready()
                                               for tk in inflight[0][0]):
                            done[inflight.popleft()[1]] = \
                                time.perf_counter()
                busy = bool(inflight)
                if i >= n and not busy and not sched.pending():
                    break
                if not sched.pending():
                    now = time.perf_counter()
                    wait = (due[i] - now) if i < n else POLL_S
                    if busy:
                        wait = min(wait, POLL_S)
                    if wait > 0:
                        with annotate("bench.sleep"):
                            time.sleep(wait)
        run.t0 = t0
        run.t1 = float(np.nanmax(done))
        run.due, run.submitted, run.dispatched, run.done = due, sub, disp, done
        served1, padded1 = self._counters()
        run.served, run.padded_slots = served1 - served0, padded1 - padded0
        self.tickets = tickets

    def answers(self, run: Run):
        import jax
        ok = [j for j, tk in enumerate(self.tickets)
              if tk is not None and tk.done and tk.result is not None]
        got = np.concatenate(jax.device_get(
            [self.tickets[j].result for j in ok]))
        return got, self.idx[ok], len(self.tickets) - len(ok)

    def release(self) -> None:
        self.sched = self.requests = None
        self.tickets = []


LOOPS = {"closed": Closed, "open": Open}


def reference_logits(ref, cfg: Dict, params, pool, precision: str):
    """The plain reference over every image of `pool`, REF_BLOCK at a time,
    on the host afterwards."""
    import jax
    fwd = jax.jit(lambda p, x: ref.forward(cfg, p, x, precision))
    n = pool.shape[0]
    block = min(REF_BLOCK, n)
    if n % block:
        raise spec.SpecError(f"an input pool of {n} images is not a "
                             f"multiple of {block}")
    return np.concatenate([np.asarray(fwd(params, pool[i:i + block]))
                           for i in range(0, n, block)])


def intervals_summary(t0: float, ends: List[float]) -> str:
    """For the log: the spacing of the closed loop's completions, and the
    time lost in intervals over 1.5 times the median one."""
    gaps = np.diff(np.concatenate([[t0], ends]))
    med = float(np.median(gaps))
    slow = gaps[gaps > 1.5 * med]
    at = np.asarray(ends) - t0
    longest = [(round(float(gaps[i]) * 1e3, 3), round(float(at[i]), 3))
               for i in np.argsort(gaps)[-3:]]
    return (f"batches {len(gaps)}, interval median {med * 1e3:.3f} ms, "
            f"first {gaps[0] * 1e3:.3f} ms, {len(slow)} over 1.5x losing "
            f"{float((slow - med).sum()) * 1e3:.1f} ms, the longest (ms, "
            f"at s): {longest}")


def row_rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: max |got - want| / max |want|; inf where got is not finite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)
    return np.where(np.isfinite(got).all(axis=1), err, np.inf)


def checks_for(cfg: Dict, errs: np.ndarray, missing: int) -> Dict:
    return {
        "max_rel_err": {"value": float(errs.max()) if errs.size
                        else float("inf"),
                        "limit": cfg["limits"]["max_rel_err"]},
        "answers_missing": {"value": int(missing), "limit": 0},
    }


def passed(checks: Dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _start_trace(root: Path) -> Path:
    import jax
    out = root / ".bench_trace"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    return out


def _stop_trace(out: Path) -> Dict:
    import jax
    jax.profiler.stop_trace()
    try:
        path = next(out.rglob("*.xplane.pb"))
        return tr.read(path)
    finally:
        shutil.rmtree(out, ignore_errors=True)


@dataclasses.dataclass
class Setup:
    """A cell made ready to measure: its Run, the system, the reference."""

    bm: Dict
    run: Run
    system: Any
    ref: Any
    params: Any
    used: List[Any]
    compiles: _Compiles


def set_up(root: Path, workload: str, seed: int, t_start: float,
           allow_cpu: bool = False, log=print) -> Setup:
    """Weights, inputs, compiles and warm-up of one cell; `run.setup_s` is
    the time from `t_start` to the end of it."""
    import jax
    bm = spec.benchmark(root)
    cell = spec.cell(bm, workload)
    cfg = spec.config(root, bm, cell["config"])
    mix = spec.traffic(root, cell["traffic"])
    devs = jax.devices()
    if (devs[0].platform != "tpu" and not allow_cpu) \
            or len(devs) < cell["chips"]:
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform} device(s)")
    used = devs[:cell["chips"]]
    peaks = spec.peaks(root, devs[0].device_kind)
    ref = spec.reference(root, cfg)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    compiles = _Compiles()
    run = Run(cell=cell, cfg=cfg, mix=mix, peaks=peaks, seed=int(seed),
              chips=cell["chips"], devices=[d.id for d in used])
    parts = run.setup_parts
    parts["start_s"] = time.perf_counter() - t_start

    t = time.perf_counter()
    k_weights, k_inputs = seed_keys(seed)
    params = ref.init(cfg, k_weights)
    jax.block_until_ready(params)
    parts["weights_s"] = time.perf_counter() - t
    system = LOOPS[mix["loop"]](run, params, k_inputs, parts)
    # Settle the heap that set-up leaves: collect it once and keep its
    # survivors out of later collections, so that the window pays only for
    # the garbage the window makes.
    t = time.perf_counter()
    gc.collect()
    gc.freeze()
    parts["gc_s"] = time.perf_counter() - t
    run.setup_s = time.perf_counter() - t_start
    parts["cache_hits"], parts["cache_misses"] = compiles.hits, compiles.misses
    log(f"setup {run.setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in parts.items()))
    return Setup(bm, run, system, ref, params, used, compiles)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, allow_cpu: bool = False,
             log=print) -> Dict:
    """One run: returns the result object the benchmark prints last."""
    su = set_up(root, workload, seed, t_start, allow_cpu, log)
    bm, run, system, ref, params = su.bm, su.run, su.system, su.ref, \
        su.params
    cfg, used, compiles = run.cfg, su.used, su.compiles
    gcs = _Collections()
    annotate = _annotate(trace)
    out = _start_trace(root) if trace else None
    compiles.on = gcs.on = True
    try:
        system.window(run, seconds, annotate)
    finally:
        compiles.on = gcs.on = False
        if trace:
            run.events = _stop_trace(out)
    run.compiles_in_window = compiles.count
    if trace:
        run.trace_span = tr.window(run.events)
    peak = memory_peak(used)

    got, rows, missing = system.answers(run)
    pool = system.pool
    system.release()
    del system
    gc.collect()
    t = time.perf_counter()
    want = reference_logits(ref, cfg, params, pool, "highest")
    errs = row_rel_err(got, want[rows])
    checks = checks_for(cfg, errs, missing)
    log(f"reference {time.perf_counter() - t:.3f} s over {len(pool)} "
        f"images; compared {len(errs)} answers")
    log(f"collections in window: {gcs.summary()}")
    if run.completions:
        log(intervals_summary(run.t0, run.completions))
    if run.due is not None:
        late = run.submitted - run.due
        log(f"generator lateness: p95 {np.percentile(late, 95) * 1e3:.4f} "
            f"ms, max {late.max() * 1e3:.4f} ms; compiles in window "
            f"{run.compiles_in_window}")
        longest = sorted(((e - s) * 1e3, round(s - run.t0, 4))
                         for s, e in run.steps)[-3:]
        log(f"steps {len(run.steps)}, the longest (ms, at s): {longest}")

    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(bm, workload, group):
        value = spec.reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    result = {"correct": passed(checks),
              "attempted": len(errs) + missing, "failed": missing,
              "metrics": metrics, "device": device}
    if trace:
        lo, hi = run.trace_span
        ids = run.devices
        device["busy_s"] = float(np.mean(tr.device_busy(run.events, lo, hi,
                                                        ids)))
        device["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": tr.top_ops(run.events, lo, hi, ids),
            "idle_gaps": tr.idle_gaps(run.events, lo, hi, ids)}
    result["checks"] = checks
    return result
