"""The plan-driven batched serving scheduler (repro.serve.scheduler).

The acceptance contract is *golden parity*: any admitted request's result
must be bitwise identical to running that request alone through the
batch-1 `CompiledNet.apply` under the scheduler's config — whatever batch
bucket the scheduler packed it into, whatever else shared the batch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.models import cnn
from repro.serve import scheduler as SCH


def _mlp_program(d_in=16, d_h=32, d_out=10, name="mlp"):
    """A tiny traced two-layer MLP program (cheap scheduler fodder)."""
    def fn(w, x):
        h = jax.nn.relu(E.dense(x, w["w1"]))
        return E.dense(h, w["w2"])

    def avals(b):
        return ({"w1": jax.ShapeDtypeStruct((d_in, d_h), jnp.float32),
                 "w2": jax.ShapeDtypeStruct((d_h, d_out), jnp.float32)},
                jax.ShapeDtypeStruct((b, d_in), jnp.float32))

    return E.trace_program(
        fn, *avals(1), name=name, batch_size=1,
        batch_axes=E.infer_batch_axes(avals(1), avals(2)))


def _mlp_weights(d_in=16, d_h=32, d_out=10, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w1": jax.random.normal(k1, (d_in, d_h), jnp.float32),
            "w2": jax.random.normal(k2, (d_h, d_out), jnp.float32)}


# ---------------------------------------------------------------------------
# Golden parity: scheduler output == batch-1 CompiledNet.apply, bitwise
# ---------------------------------------------------------------------------


class TestGoldenParity:
    def test_mlp_requests_bitwise(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config, max_batch=4)
        sched.register("mlp", prog, shared_args=(w,))
        xs = [jax.random.normal(jax.random.PRNGKey(10 + i), (1, 16))
              for i in range(6)]
        tickets = [sched.submit("mlp", x) for x in xs]
        done = sched.drain()
        assert len(done) == 6 and all(t.done for t in tickets)
        alone = E.compile(prog, serving_config)
        for t, x in zip(tickets, xs):
            want = alone.apply(w, x)
            np.testing.assert_array_equal(np.asarray(t.result),
                                          np.asarray(want))

    def test_cnn_requests_bitwise(self, serving_config):
        # AlexNet through cnn.program: conv modes + FC modes in one batch.
        key = jax.random.PRNGKey(0)
        params = cnn.init_cnn("alexnet", key)
        prog = cnn.program("alexnet")
        sched = SCH.Scheduler(config=serving_config, max_batch=2)
        sched.register("alexnet", prog, shared_args=(params,))
        xs = [jax.random.normal(jax.random.PRNGKey(i), (1, 227, 227, 3),
                                jnp.float32) * 0.1 for i in range(3)]
        tickets = [sched.submit("alexnet", x) for x in xs]
        done = sched.drain()
        assert [t.batch_bucket for t in done] == [2, 2, 1]
        alone = E.compile(prog, serving_config)
        assert alone.lowerings()[0] == "fold"      # conv1: C_in 3, taps folded
        for t, x in zip(tickets, xs):
            want = alone.apply(params, x)
            np.testing.assert_array_equal(np.asarray(t.result),
                                          np.asarray(want))

    def test_decode_requests_bitwise(self, serving_config, smollm_reduced,
                                     smollm_params):
        # Transformer decode: per-request KV state (batch axis 1 for the
        # grouped layers) packed into one batch-8 step.
        from repro.models import transformer as T
        from repro.serve import engine as SE
        cfg, params = smollm_reduced, smollm_params
        prog = SE.decode_program(cfg, batch=1, max_len=32)
        sched = SCH.Scheduler(config=serving_config, max_batch=8)
        sched.register("decode", prog,
                       shared_args=(params, jnp.int32(3)))
        states = [T.init_decode_state(cfg, 1, 32) for _ in range(8)]
        toks = [jnp.full((1, 1), 7 + i, jnp.int32) for i in range(8)]
        tickets = [sched.submit("decode", s, t)
                   for s, t in zip(states, toks)]
        done = sched.drain()
        assert len(done) == 8 and done[0].batch_bucket == 8
        alone = E.compile(prog, serving_config)
        for t, s, tok in zip(tickets, states, toks):
            want = alone.apply(params, s, tok, jnp.int32(3))
            np.testing.assert_array_equal(np.asarray(t.result),
                                          np.asarray(want))

    def test_bucket_beyond_row_align_bitwise(self, serving_config):
        # max_batch=16 > row_align=8: the 16-bucket GEMMs run M=16 while
        # the solo path pads to M=8 — the only regime where padded M
        # differs across buckets, so parity can't ride on equal shapes.
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config, max_batch=16)
        sched.register("mlp", prog, shared_args=(w,))
        xs = [jax.random.normal(jax.random.PRNGKey(40 + i), (1, 16))
              for i in range(16)]
        tickets = [sched.submit("mlp", x) for x in xs]
        done = sched.drain()
        assert all(t.batch_bucket == 16 for t in done)
        alone = E.compile(prog, serving_config)
        for t, x in zip(tickets, xs):
            np.testing.assert_array_equal(np.asarray(t.result),
                                          np.asarray(alone.apply(w, x)))

    def test_tuned_fused_requests_bitwise(self, tmp_path):
        # PR-4 follow-through: the scheduler passes EngineConfig.tuning into
        # every (program, bucket) CompiledNet. Under tuning="cached" + fused
        # epilogues on the Pallas backend, batched results must STILL be
        # bitwise identical to batch-1 — tile keys are batch-invariant
        # (engine/tune.py), so every bucket runs the same (bk-order) tiles.
        from repro.engine import tune

        def fn(w, x):
            h = E.dense(x, w["w1"], bias=w["b1"], act="relu")
            return E.dense(h, w["w2"], bias=w["b2"])

        def avals(b):
            return ({"w1": jax.ShapeDtypeStruct((16, 32), jnp.float32),
                     "b1": jax.ShapeDtypeStruct((32,), jnp.float32),
                     "w2": jax.ShapeDtypeStruct((32, 10), jnp.float32),
                     "b2": jax.ShapeDtypeStruct((10,), jnp.float32)},
                    jax.ShapeDtypeStruct((b, 16), jnp.float32))

        prog = E.trace_program(fn, *avals(1), name="fusedmlp", batch_size=1,
                               batch_axes=E.infer_batch_axes(avals(1),
                                                             avals(2)))
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        w = {"w1": jax.random.normal(ks[0], (16, 32), jnp.float32),
             "b1": jax.random.normal(ks[1], (32,), jnp.float32),
             "w2": jax.random.normal(ks[2], (32, 10), jnp.float32),
             "b2": jax.random.normal(ks[3], (10,), jnp.float32)}
        tune.set_cache_dir(tmp_path)
        try:
            cfg = E.EngineConfig(backend="pallas", interpret=True,
                                 row_align=8, tuning="cached")
            # seed the cache so "cached" actually resolves tuned tiles
            tuned = tune.tune_program(prog.ops,
                                      cfg.replace(tuning="autotune"))
            assert tuned == 2
            sched = SCH.Scheduler(config=cfg, max_batch=4)
            sched.register("fusedmlp", prog, shared_args=(w,))
            assert sched.stats()["tuning"] == "cached"
            xs = [jax.random.normal(jax.random.PRNGKey(60 + i), (1, 16))
                  for i in range(6)]
            tickets = [sched.submit("fusedmlp", x) for x in xs]
            sched.drain()
            alone = E.compile(prog, cfg)
            assert all(t is not None for t in alone.tiles())
            for t, x in zip(tickets, xs):
                np.testing.assert_array_equal(np.asarray(t.result),
                                              np.asarray(alone.apply(w, x)))
        finally:
            tune.set_cache_dir(None)

    def test_mixed_queue_keeps_parity(self, serving_config):
        # heterogeneous queue: two different programs interleaved
        big, bw = _mlp_program(64, 128, 32, "big"), _mlp_weights(64, 128, 32)
        small, sw = _mlp_program(8, 16, 4, "small"), _mlp_weights(8, 16, 4, 1)
        sched = SCH.Scheduler(config=serving_config, policy="spf",
                              max_batch=4)
        sched.register("big", big, shared_args=(bw,))
        sched.register("small", small, shared_args=(sw,))
        reqs = []
        for i in range(4):
            name = "big" if i % 2 == 0 else "small"
            d_in = 64 if name == "big" else 8
            x = jax.random.normal(jax.random.PRNGKey(20 + i), (1, d_in))
            reqs.append((name, x, sched.submit(name, x)))
        sched.drain()
        compiled = {"big": E.compile(big, serving_config),
                    "small": E.compile(small, serving_config)}
        weights = {"big": bw, "small": sw}
        for name, x, t in reqs:
            want = compiled[name].apply(weights[name], x)
            np.testing.assert_array_equal(np.asarray(t.result),
                                          np.asarray(want))


# ---------------------------------------------------------------------------
# Policies: plan-cost-aware ordering
# ---------------------------------------------------------------------------


class TestPolicies:
    def _mixed_queue(self, policy, serving_config):
        big, bw = _mlp_program(512, 512, 256, "big"), \
            _mlp_weights(512, 512, 256)
        small, sw = _mlp_program(8, 16, 4, "small"), _mlp_weights(8, 16, 4, 1)
        sched = SCH.Scheduler(config=serving_config, policy=policy,
                              max_batch=4)
        sched.register("big", big, shared_args=(bw,))
        sched.register("small", small, shared_args=(sw,))
        order = ["big", "small", "big", "small"]
        for i, name in enumerate(order):
            d_in = 512 if name == "big" else 8
            sched.submit(name, jax.random.normal(jax.random.PRNGKey(i),
                                                 (1, d_in)))
        done = sched.drain()
        return [t.model for t in done], sched

    def test_spf_serves_cheapest_plan_first(self, serving_config):
        models, sched = self._mixed_queue("spf", serving_config)
        # both smalls (cheapest analytic plan) complete before any big
        assert models == ["small", "small", "big", "big"]
        e = sched._entries
        assert e["small"].unit_plan.total_latency_s \
            < e["big"].unit_plan.total_latency_s

    def test_fifo_serves_arrival_order(self, serving_config):
        models, _ = self._mixed_queue("fifo", serving_config)
        # head-of-queue model batches first, pulling its later twin forward
        assert models == ["big", "big", "small", "small"]

    def test_unknown_policy_rejected(self):
        with pytest.raises(ValueError, match="unknown policy"):
            SCH.Scheduler(policy="lifo")


# ---------------------------------------------------------------------------
# Cost-aware admission
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_queue_cost_budget(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config, max_batch=4)
        entry = sched.register("mlp", prog, shared_args=(w,))
        unit = entry.unit_plan.total_latency_s
        sched.max_queue_cost_s = 2.5 * unit        # room for two requests
        x = jnp.ones((1, 16))
        sched.submit("mlp", x)
        sched.submit("mlp", x)
        assert sched.queue_cost_s() == pytest.approx(2 * unit)
        with pytest.raises(SCH.AdmissionError, match="max_queue_cost_s"):
            sched.submit("mlp", x)
        sched.drain()                              # queue empties ->
        sched.submit("mlp", x)                     # admission reopens

    def test_submit_validation(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config)
        sched.register("mlp", prog, shared_args=(w,))
        with pytest.raises(KeyError, match="unknown model"):
            sched.submit("nope", jnp.ones((1, 16)))
        with pytest.raises(ValueError, match="per-request"):
            sched.submit("mlp", jnp.ones((1, 16)), jnp.ones((1, 16)))
        with pytest.raises(ValueError, match="batch-1 avals"):
            sched.submit("mlp", jnp.ones((2, 16)))     # batch-2 request
        with pytest.raises(ValueError, match="batch-1 avals"):
            sched.submit("mlp", jnp.ones((1, 8)))      # wrong feature dim

    def test_register_validation(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config)
        sched.register("mlp", prog, shared_args=(w,))
        with pytest.raises(ValueError, match="already registered"):
            sched.register("mlp", prog, shared_args=(w,))
        with pytest.raises(ValueError, match="shared_args"):
            sched.register("mlp2", prog)               # missing weights
        bare = E.Program("bare", prog.ops)
        with pytest.raises(ValueError, match="no executable fn"):
            sched.register("bare", bare)

    def test_mixed_batched_unbatched_leaves_rejected(self, serving_config):
        # a per-request pytree mixing batched and unbatched leaves would
        # silently reuse request 0's unbatched value for the whole batch
        def fn(w, req):
            return E.dense(req["x"], w) * req["scale"]

        def avals(b):
            return (jax.ShapeDtypeStruct((16, 4), jnp.float32),
                    {"x": jax.ShapeDtypeStruct((b, 16), jnp.float32),
                     "scale": jax.ShapeDtypeStruct((), jnp.float32)})

        prog = E.trace_program(fn, *avals(1), name="mixed", batch_size=1,
                               batch_axes=E.infer_batch_axes(avals(1),
                                                             avals(2)))
        sched = SCH.Scheduler(config=serving_config)
        with pytest.raises(ValueError, match="mixes batched and unbatched"):
            sched.register("mixed", prog)

    def test_register_does_not_pollute_active_ledgers(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config)
        with E.tracking() as led:
            sched.register("mlp", prog, shared_args=(w,))
        # the out-aval shape probes are dry traces, not served work
        assert len(led) == 0


# ---------------------------------------------------------------------------
# Shape bucketing + padding
# ---------------------------------------------------------------------------


class TestBucketing:
    def test_bucket_ladder_and_padding(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config, max_batch=8)
        assert sched.buckets == (1, 2, 4, 8)
        sched.register("mlp", prog, shared_args=(w,))
        for i in range(3):
            sched.submit("mlp", jnp.ones((1, 16)))
        done = sched.drain()
        # 3 requests pack into the 4-bucket: fill 3, one padded slot
        assert all(t.batch_bucket == 4 and t.batch_fill == 3 for t in done)
        stats = sched.stats()
        assert stats["models"]["mlp"]["padded_slots"] == 1
        assert stats["models"]["mlp"]["occupancy"] == pytest.approx(0.75)
        # the jit cache holds exactly the buckets that actually ran
        assert stats["models"]["mlp"]["compiled_buckets"] == [4]

    def test_warmup_prebuilds_every_bucket_path(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config, max_batch=4)
        entry = sched.register("mlp", prog, shared_args=(w,))
        sched.warmup()
        # the whole pack -> apply -> unpack path exists per bucket (keys
        # are (bucket, replica); replica is always 0 without a mesh)
        assert sorted(entry.compiled) == [(1, 0), (2, 0), (4, 0)]
        assert entry.pack_fn is not None
        assert sorted(entry.unpack) == [1, 2, 4]
        # warmed buckets still serve correctly (and bitwise, per parity)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 16))
        t = sched.submit("mlp", x)
        sched.drain()
        want = E.compile(prog, serving_config).apply(w, x)
        np.testing.assert_array_equal(np.asarray(t.result),
                                      np.asarray(want))

    def test_pending_ticket_latency_is_nan(self, serving_config):
        import math
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config)
        sched.register("mlp", prog, shared_args=(w,))
        t = sched.submit("mlp", jnp.ones((1, 16)))
        assert math.isnan(t.latency_s)          # not served yet
        sched.drain()
        assert t.latency_s >= 0.0

    def test_explicit_buckets_validated(self):
        with pytest.raises(ValueError, match="must end at"):
            SCH.Scheduler(max_batch=8, buckets=(1, 2))
        s = SCH.Scheduler(max_batch=6, buckets=(2, 6))
        assert s.buckets == (2, 6)
        assert s._bucket_for(1) == 2 and s._bucket_for(3) == 6


# ---------------------------------------------------------------------------
# Per-request plan accounting
# ---------------------------------------------------------------------------


class TestLedgerAccounting:
    def test_ticket_ledger_records_unit_plan(self, serving_config):
        prog, w = _mlp_program(), _mlp_weights()
        sched = SCH.Scheduler(config=serving_config, max_batch=4)
        entry = sched.register("mlp", prog, shared_args=(w,))
        tickets = [sched.submit("mlp", jnp.ones((1, 16))) for _ in range(4)]
        sched.drain()
        unit = entry.unit_plan
        for t in tickets:
            assert len(t.ledger) == len(unit.plans)
            assert t.ledger.total_macs == unit.total_macs
            assert t.ledger.total_cycles \
                == unit.conv_cycles + unit.fc_cycles
            assert t.latency_s >= 0.0
        # scheduler-wide ledger aggregates every served request's unit plan
        assert sched.ledger.total_macs == 4 * unit.total_macs
        stats = sched.stats()
        assert stats["plan_macs_served"] == 4 * unit.total_macs
        assert stats["throughput_rps"] > 0.0
