"""The program's spans read by the served cell's readers: the map onto the
profiler's clock on a CPU trace, and each reader on hand-made runs."""
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO

from bench import harness, spec
from bench import spans as bs
from bench import trace as tr

US = 1_000                  # ns
T0 = 100 * 10**9            # window start, perf_counter ns
READERS = ("step_dispatch_ms.server", "step_account_ms.server",
           "step_idle_ms.server")


def _read(name, run):
    return spec.reader(REPO, name)(run)


def _tiny_scheduler():
    from repro import engine as E
    from repro.serve.scheduler import Scheduler

    def fn(w, x):
        return E.dense(x, w)

    def avals(b):
        return (jax.ShapeDtypeStruct((16, 8), jnp.float32),
                jax.ShapeDtypeStruct((b, 16), jnp.float32))

    prog = E.trace_program(fn, *avals(1), name="tiny", batch_size=1,
                           batch_axes=E.infer_batch_axes(avals(1), avals(2)))
    sched = Scheduler(max_batch=2)
    sched.register("tiny", prog, shared_args=(jnp.ones((16, 8)),))
    sched.warmup()
    return sched


def test_a_cpu_trace_holds_the_program_spans_where_the_map_puts_them(
        tmp_path):
    from jax.profiler import ProfileData
    from repro.engine import spans
    sched = _tiny_scheduler()
    x = jnp.ones((1, 16))
    run = types.SimpleNamespace(steps=[], events=None)
    first = len(spans.snapshot()["spans"])
    out = harness._start_trace(tmp_path)
    try:
        for _ in range(20):
            sched.submit("tiny", x)
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                sched.step()
            run.steps.append((ts, time.perf_counter()))
            time.sleep(2e-3)
    finally:
        jax.profiler.stop_trace()
    path = next(out.rglob("*.xplane.pb"))
    run.events = tr.read(path)
    on_trace = sorted(e.start_ns for p in ProfileData.from_file(
        str(path)).planes for line in p.lines for e in line.events
        if e.name == "serve.step")
    recorded = [r.start_ns for r in spans.snapshot()["spans"][first:]
                if r.name == "serve.step"]
    assert len(on_trace) == len(recorded) == 20
    m, err = bs.to_trace(run)
    assert max(abs(m(s) - t) for s, t in zip(recorded, on_trace)) < 50 * US
    assert 0 <= err < 50 * US


def _rec(rid, parent, name, start, end, **attrs):
    from repro.engine.spans import Record
    return Record(rid, parent, name, T0 + start, T0 + end, attrs)


def _step(rid, at, dispatch, wait, account):
    """One serve.step starting `at` ns after T0, its phases back to back:
    form 10 us, pack, apply, unpack splitting `dispatch` less 10 us."""
    d = (dispatch - 10 * US) // 3
    edges = [at, at + 10 * US, at + 10 * US + d, at + 10 * US + 2 * d,
             at + dispatch, at + dispatch + wait,
             at + dispatch + wait + account]
    names = ("serve.form", "serve.pack", "engine.apply", "serve.unpack",
             "serve.wait", "serve.account")
    kids = [_rec(rid + 1 + i, rid, n, s, e)
            for i, (n, s, e) in enumerate(zip(names, edges, edges[1:]))]
    return kids + [_rec(rid, 0, "serve.step", at, edges[-1] + 5 * US,
                        rows=1)]


def _recorded(monkeypatch, recs, dropped=0):
    from repro.engine import spans
    snap = {"spans": recs,
            "counters": {spans.DROPPED: dropped} if dropped else {}}
    monkeypatch.setattr(spans, "snapshot", lambda: snap)


def _run(t0_ns=T0, t1_ns=T0 + 10**9):
    return types.SimpleNamespace(t0=t0_ns * 1e-9, t1=t1_ns * 1e-9, steps=[],
                                 events=None, trace_span=None, devices=[0])


STEPS = [  # (at, dispatch, wait, account), ns after T0
    (-5000 * US, 900 * US, 3000 * US, 900 * US),    # ends before it
    (1000 * US, 130 * US, 3000 * US, 200 * US),
    (9000 * US, 300 * US, 2500 * US, 400 * US),
    (20000 * US, 500 * US, 3500 * US, 100 * US),
    (2 * 10**9, 900 * US, 3000 * US, 900 * US),     # after it
]


def _steps():
    return [r for i, st in enumerate(STEPS) for r in _step(10 * i + 1, *st)]


def test_dispatch_and_account_are_medians_over_the_window(monkeypatch):
    _recorded(monkeypatch, _steps())
    run = _run()
    assert _read("step_dispatch_ms.server", run) == pytest.approx(0.3)
    assert _read("step_account_ms.server", run) == pytest.approx(0.2)


def test_nothing_to_read_is_none(monkeypatch):
    _recorded(monkeypatch, _steps())
    empty = _run(T0 + 30_000 * US, T0 + 40_000 * US)
    assert [_read(n, empty) for n in READERS] == [None] * 3
    # no trace: the two counters read, the idle share of a step does not
    assert _read("step_idle_ms.server", _run()) is None
    _recorded(monkeypatch, [])
    assert [_read(n, _run()) for n in READERS] == [None] * 3


def test_a_window_that_lost_records_to_the_ring_is_none(monkeypatch):
    # the first step's phases: the oldest record kept ended before the
    # window, and so did every one dropped
    _recorded(monkeypatch, _steps()[6:], dropped=6)
    assert _read("step_dispatch_ms.server", _run()) == pytest.approx(0.3)
    _recorded(monkeypatch, _steps()[8:], dropped=8)   # a window step's
    assert [_read(n, _run()) for n in READERS] == [None] * 3


def test_a_program_without_the_recorder_reads_none(monkeypatch):
    import repro.engine
    monkeypatch.delattr(repro.engine, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.engine.spans", None)
    assert [_read(n, _run()) for n in READERS] == [None] * 3


def _on_trace(ns):
    """The profiler's clock of the hand-made traces: offset and slewed."""
    return (ns - T0) * (1 + 2e-5) + 5e6


def _traced_run(recs, shift=0, shifts=None, span=10**9):
    """A traced run over the steps of a window `span` ns long: each
    bench.step 2 us around its serve.step; the device busy from each
    step's apply (which starts as its pack ends) to 40 us before its wait
    ends, placed `shift` ns late on the trace, and each step's ops
    `shifts` more."""
    run = _run(T0, T0 + span)
    steps = [r for r in recs if r.name == "serve.step"
             and T0 <= r.start_ns <= T0 + span]
    kids = {(r.parent, r.name): r for r in recs}
    ops, host = [], [("bench.window", _on_trace(T0), _on_trace(T0 + span))]
    for s, more in zip(steps, shifts or [0] * len(steps)):
        a, b = s.start_ns - 2 * US, s.end_ns + 2 * US
        run.steps.append((a * 1e-9, b * 1e-9))
        host.append(("bench.step", _on_trace(a), _on_trace(b)))
        late = shift + more
        ops.append(("op",
                    _on_trace(kids[s.id, "engine.apply"].start_ns + late),
                    _on_trace(kids[s.id, "serve.wait"].end_ns - 40 * US
                              + late)))
    run.events = {"devices": {0: ops}, "host": host}
    run.trace_span = tr.window(run.events)
    return run


def test_idle_in_a_step_outside_its_wait_per_step(monkeypatch):
    recs = _steps()
    _recorded(monkeypatch, recs)
    run = _traced_run(recs)
    m, err = bs.to_trace(run)
    assert m(T0 + 12_345 * US) == pytest.approx(_on_trace(T0 + 12_345 * US),
                                                abs=1.0)
    assert err < 1.0
    # idle outside the wait: form, pack, account and the 5 us after it
    want = [10 * US + (d - 10 * US) // 3 + acc + 5 * US
            for _, d, _, acc in STEPS[1:4]]
    assert _read("step_idle_ms.server", run) == pytest.approx(
        sum(want) / 3 * (1 + 2e-5) * 1e-6)
    split, n = bs.idle_by_phase(run, bs.window_steps(run))
    assert n == 3
    assert split["serve.wait"] == pytest.approx(3 * 40e-6 * (1 + 2e-5))
    assert split["serve.account"] == pytest.approx(700e-6 * (1 + 2e-5))
    assert split["serve.step"] == pytest.approx(15e-6 * (1 + 2e-5))
    assert split.get("engine.apply", 0) == pytest.approx(0, abs=1e-12)
    assert "none" not in split


@pytest.mark.parametrize("shift", [-1300 * US, -300 * US, 60 * US])
def test_the_device_placed_off_the_host_clock_is_moved_back(monkeypatch,
                                                            shift):
    """The lag, measured on the steps that follow 4 ms or more of quiet
    (the second and third), is undone: the split reads as if the trace
    had placed the device where it ran."""
    recs = _steps()
    _recorded(monkeypatch, recs)
    want = _read("step_idle_ms.server", _traced_run(recs))
    run = _traced_run(recs, shift)
    m, _ = bs.to_trace(run)
    lags = bs.device_lags(bs.window_steps(run), m, bs._op_starts(run),
                          run.trace_span[0])
    assert lags == pytest.approx([shift * (1 + 2e-5)] * 3, abs=1.0)
    assert _read("step_idle_ms.server", run) == pytest.approx(want)


def test_a_step_placed_before_its_pack_is_none_among_few(monkeypatch):
    """The first step's ops 100 us earlier than the others': they start
    before the `serve.pack` that enqueued them, one step in three."""
    recs = _steps()
    _recorded(monkeypatch, recs)
    assert _read("step_idle_ms.server",
                 _traced_run(recs, shifts=(-100 * US, 0, 0))) is None
    # 30 us earlier still starts after its pack began: a reading
    assert _read("step_idle_ms.server",
                 _traced_run(recs, shifts=(-30 * US, 0, 0))) is not None
    # no step after 4 ms of quiet: no lag to measure
    monkeypatch.setattr(bs, "ISOLATED_NS", 10e6)
    assert _read("step_idle_ms.server", _traced_run(recs)) is None


def _uniform(n):
    """n like steps, 10 ms apart: idle outside the wait 255 us each
    (form 10, pack 40, account 200, 5 after it)."""
    return [r for i in range(n)
            for r in _step(10 * i + 1, 1000 * US + i * 10_000 * US,
                           130 * US, 3000 * US, 200 * US)]


@pytest.mark.parametrize("stray, want", [(0, 0.255), (1, 0.255),
                                         (10, 0.255), (11, None)])
def test_the_lag_is_followed_where_it_moves_and_few_strays_are_left_out(
        monkeypatch, stray, want):
    """200 steps, placed 300 us early for the first 100 and where they
    ran after: each step's lag is read about it. `stray` steps placed 100
    us earlier still start before their pack: left out up to 5% of the
    steps, None past it."""
    recs = _uniform(200)
    _recorded(monkeypatch, recs)
    shifts = [-300 * US] * 100 + [0] * 100
    for i in range(stray):
        shifts[20 + 15 * i] -= 100 * US
    got = _read("step_idle_ms.server",
                _traced_run(recs, shifts=shifts, span=3 * 10**9))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want * (1 + 2e-5))


def test_idle_needs_a_device_trace_that_pairs_with_the_run(monkeypatch):
    recs = _steps()
    _recorded(monkeypatch, recs)
    cpu = _traced_run(recs)
    cpu.events["devices"] = {}                  # a CPU trace: no TPU ops
    assert _read("step_idle_ms.server", cpu) is None
    unpaired = _traced_run(recs)
    unpaired.steps.pop()
    assert _read("step_idle_ms.server", unpaired) is None


def test_a_traced_cpu_server_run_reports_the_program_counters(root):
    out = harness.run_cell(root, "t.server", 2**33 + 13, 1.0, True,
                           time.perf_counter(), allow_cpu=True,
                           log=lambda m: None)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert 0 < m["step_dispatch_ms.server"]["value"] < 1e3
    assert 0 < m["step_account_ms.server"]["value"] < 1e3
    assert "step_idle_ms.server" not in m       # no TPU in the trace
