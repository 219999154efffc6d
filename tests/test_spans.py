"""The host span recorder (repro.engine.spans), the spans inside
`Scheduler.step`, and the plan-op names that the lowered device ops
carry."""
import collections
import gc
import re
import statistics

import jax
import jax.numpy as jnp
import pytest

from repro import engine as E
from repro.engine import spans
from repro.models import cnn
from repro.serve import scheduler as SCH

BATCHES = (3, 4, 1, 2, 4)
PHASES = ("serve.form", "serve.pack", "engine.apply", "serve.unpack",
          "serve.wait", "serve.account")


def _since(first_id):
    return [r for r in spans.snapshot()["spans"] if r.id >= first_id]


def _next_id():
    with spans.span("marker") as sp:
        pass
    return sp.id + 1


def test_the_ring_keeps_the_newest_records_and_counts_the_dropped(
        monkeypatch):
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=4))
    monkeypatch.setattr(spans, "_counters", {})
    for i in range(6):
        with spans.span("s", i=i):
            pass
    snap = spans.snapshot()
    assert [r.attrs["i"] for r in snap["spans"]] == [2, 3, 4, 5]
    assert snap["counters"] == {spans.DROPPED: 2}
    spans.count("c", 3)
    assert spans.snapshot()["counters"] == {spans.DROPPED: 2, "c": 3}


def test_nested_spans_record_their_parent():
    first = _next_id()
    with spans.span("outer") as outer:
        with spans.span("inner") as inner:
            with spans.span("leaf") as leaf:
                pass
        with spans.span("inner2") as inner2:
            pass
    recs = {r.name: r for r in _since(first)}
    # records enter the ring as their spans end
    assert [r.name for r in _since(first)] == ["leaf", "inner", "inner2",
                                               "outer"]
    assert recs["outer"].parent == 0
    assert recs["outer"].attrs is None      # a span with no attributes
    assert recs["inner"].parent == recs["inner2"].parent == outer.id
    assert recs["leaf"].parent == inner.id
    assert (leaf.id, inner2.id) == (recs["leaf"].id, recs["inner2"].id)
    assert recs["outer"].start_ns <= recs["inner"].start_ns \
        <= recs["leaf"].start_ns <= recs["leaf"].end_ns \
        <= recs["inner"].end_ns <= recs["inner2"].start_ns \
        <= recs["outer"].end_ns
    assert (outer.start_ns, outer.end_ns) == (recs["outer"].start_ns,
                                              recs["outer"].end_ns)


def _small_cnn():
    """A conv and an FC through the engine, as a traced program: on the
    CPU a batch takes some ms, so that the few tens of us between two
    spans stay under 2% of a step."""
    def fn(w, x):
        y = E.conv2d(x, w["c"], pad=1, act="relu")
        return E.dense(y.reshape(y.shape[0], -1), w["f"])

    def avals(b):
        return ({"c": jax.ShapeDtypeStruct((3, 3, 16, 64), jnp.float32),
                 "f": jax.ShapeDtypeStruct((64 * 64 * 64, 10), jnp.float32)},
                jax.ShapeDtypeStruct((b, 64, 64, 16), jnp.float32))

    prog = E.trace_program(fn, *avals(1), name="small_cnn", batch_size=1,
                           batch_axes=E.infer_batch_axes(avals(1), avals(2)))
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = {"c": jax.random.normal(k1, (3, 3, 16, 64)),
         "f": jax.random.normal(k2, (64 * 64 * 64, 10))}
    return prog, w


@pytest.fixture(scope="module")
def served():
    """A warmed Scheduler over the small CNN, and six steps of it: the
    tickets of each step and the records the steps left. The steps run
    with the collector off: a collection lands wherever an allocation
    triggers it, between two spans too, and in a test process with a
    large heap it can take milliseconds."""
    prog, w = _small_cnn()
    sched = SCH.Scheduler(max_batch=4)
    sched.register("cnn", prog, shared_args=(w,))
    sched.warmup()
    wall0 = sched.stats()["dispatch_wall_s"]
    xs = [jax.random.normal(jax.random.PRNGKey(i), (1, 64, 64, 16))
          for i in range(sum(BATCHES))]
    steps = []
    gc.collect()
    gc.disable()
    try:
        first = _next_id()
        for n in BATCHES:
            for x in xs[:n]:
                sched.submit("cnn", x)
            del xs[:n]
            steps.append(sched.step())
        steps.append(sched.step())      # an empty queue: no batch
    finally:
        gc.enable()
    return sched, wall0, steps, _since(first)


def test_a_step_is_tiled_by_its_phases(served):
    """In order and without overlap; what they leave uncovered is the
    spans' own cost, under 2% in the median step (a step that the OS
    stalled between two spans may leave more)."""
    recs = served[-1]
    steps = [r for r in recs if r.name == "serve.step"]
    assert len(steps) == len(BATCHES) + 1
    covered = []
    for st in steps[:-1]:
        kids = sorted((r for r in recs if r.parent == st.id),
                      key=lambda r: r.start_ns)
        assert tuple(r.name for r in kids) == PHASES
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns
        covered.append(sum(r.end_ns - r.start_ns for r in kids)
                       / (st.end_ns - st.start_ns))
    assert statistics.median(covered) >= 0.98


def test_dispatch_wall_sums_each_batch_from_pack_to_ready(served):
    """The spans' clock: `serve.pack`'s start to `serve.wait`'s end."""
    sched, wall0, _, recs = served
    kid = {(r.parent, r.name): r for r in recs}
    steps = [r for r in recs if r.name == "serve.step"][:-1]
    want = sum((kid[st.id, "serve.wait"].end_ns
                - kid[st.id, "serve.pack"].start_ns) * 1e-9 for st in steps)
    assert sched.stats()["dispatch_wall_s"] - wall0 == pytest.approx(
        want, rel=1e-9)


def test_tickets_join_their_step_by_batch_seq(served):
    sched, _, tickets, recs = served
    steps = [r for r in recs if r.name == "serve.step"]
    waits = {r.parent: r for r in recs if r.name == "serve.wait"}
    assert [len(b) for b in tickets] == [*BATCHES, 0]
    for batch, st in zip(tickets[:-1], steps):
        assert st.attrs["rows"] == len(batch)
        assert st.attrs["bucket"] == (4 if len(batch) > 2 else len(batch))
        assert st.attrs["model"] == "cnn"
        assert {t.batch_seq for t in batch} == {st.attrs["batch_seq"]}
        # one readiness time per batch: the end of its serve.wait
        assert {t.done_s for t in batch} == {waits[st.id].end_ns * 1e-9}
    assert [st.attrs["queue_depth"] for st in steps] == [*BATCHES, 0]
    seqs = [st.attrs["batch_seq"] for st in steps[:-1]]
    assert seqs == list(range(seqs[0], seqs[0] + len(BATCHES)))
    assert "batch_seq" not in steps[-1].attrs


def test_compiled_apply_outside_a_scheduler_records_nothing():
    """`engine.apply` is the Scheduler's phase: offline batches pay no
    span."""
    prog, w = _small_cnn()
    net = E.compile(prog)
    x = jnp.zeros((1, 64, 64, 16))
    jax.block_until_ready(net.apply(w, x))
    first = _next_id()
    jax.block_until_ready(net.apply(w, x))
    assert _since(first) == []


def _scopes(net, prog):
    text = net._jitted.lower(*prog.in_avals).as_text(debug_info=True)
    return set(re.findall(r"jit\(_run\)/([\w.-]+)/", text))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_lowered_alexnet_carries_every_plan_op_name(backend):
    prog = cnn.program("alexnet").with_batch(1)
    net = E.compile(prog, E.EngineConfig(backend=backend))
    names = [op.name for op in prog.ops]
    assert names == ["conv1", "conv2", "conv3", "conv4", "conv5", "fc6",
                     "fc7", "fc8"]
    assert set(names) <= _scopes(net, prog)


def test_unnamed_ops_are_named_by_kind_and_position():
    prog, _ = _small_cnn()
    net = E.compile(prog)
    assert [op.name for op, _ in net.exec_pairs] == ["conv2d0", "dense1"]
    assert {"conv2d0", "dense1"} <= _scopes(net, prog)


@pytest.mark.parametrize("net,some", [
    ("vgg16", {"conv1_1", "conv3_2", "fc6"}),
    ("resnet50", {"conv1", "s3b2_3x3", "fc"}),
])
def test_lowered_benchmark_nets_carry_every_plan_op_name(net, some):
    """Under the benchmark configurations' `policy="auto"` too, every plan
    op's device ops carry its name scope, by which a trace ties device
    time to the op."""
    prog = cnn.program(net).with_batch(2)
    cnet = E.compile(prog, E.EngineConfig(policy="auto"))
    names = {op.name for op, _ in cnet.exec_pairs}
    assert some <= names
    assert names <= _scopes(cnet, prog)
