"""The compiled NetworkProgram API: whole-network planning
(`engine.compile` / `Program` / `NetworkPlan`), the `cnn.program` and
`trace_program` builders, per-layer backend selection ("auto" policy), and
the serve-side `EngineConfig` threading."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.core.analytics import network_cost
from repro.models import cnn

# CPU platform pin + shared fixtures live in conftest.py

NETS = ("alexnet", "vgg16", "resnet50")


# ---------------------------------------------------------------------------
# NetworkPlan == analytics.network_cost (acceptance: Table 4 exactly)
# ---------------------------------------------------------------------------

class TestNetworkPlanMatchesTable4:
    @pytest.mark.parametrize("net", NETS)
    def test_aggregates_exact(self, net):
        nplan = E.plan_network(cnn.program(net), E.EngineConfig())
        convs, fcs = cnn.analytics_layers(net)
        nc = network_cost(net, convs, fcs)
        assert nplan.conv_cycles == nc.conv_cycles
        assert nplan.fc_cycles == nc.fc_cycles
        assert nplan.conv_latency_s == nc.conv_latency_s
        assert nplan.fc_latency_s == nc.fc_latency_s
        assert nplan.conv_ma_bytes == nc.conv_ma_bytes
        assert nplan.fc_ma_bytes == nc.fc_ma_bytes
        assert nplan.conv_perf_efficiency == nc.conv_perf_efficiency
        assert nplan.fc_perf_efficiency == nc.fc_perf_efficiency

    def test_resnet_paper_counting_vs_real_geometry(self):
        # paper counting: 49 main-path convs + conv1; real geometry adds the
        # 4 projection shortcuts.
        paper = cnn.program("resnet50")
        real = cnn.program("resnet50", main_path_only=False)
        assert len(paper.ops) == 49 + 1            # 49 convs + fc
        assert len(real.ops) == 53 + 1
        # counting differences are *structural* only: the shared main-path
        # layers are booked identically (decimated S=1 == strided geometry).
        proj = [op for op in real.ops if op.name.endswith("_proj")]
        assert len(proj) == 4
        shared = [op for op in real.ops if not op.name.endswith("_proj")]
        p_plan = E.plan_network(paper, E.EngineConfig())
        s_plan = E.NetworkPlan("shared", tuple(
            E.plan_op(op, "xla") for op in shared))
        assert p_plan.conv_cycles == s_plan.conv_cycles
        assert p_plan.conv_macs == s_plan.conv_macs
        assert p_plan.conv_ma_words == s_plan.conv_ma_words

    def test_plan_without_running(self):
        # planning is pure shape math — no arrays, no device buffers
        prog = cnn.program("vgg16")
        nplan = E.plan_network(prog, E.EngineConfig(backend="pallas"))
        assert nplan.total_macs > 15e9
        assert all(p.backend == "pallas" for p in nplan.plans)
        assert 0.8 < nplan.conv_perf_efficiency <= 1.0


# ---------------------------------------------------------------------------
# compile -> CompiledNet.apply (acceptance: bitwise vs apply_cnn)
# ---------------------------------------------------------------------------

class TestCompiledApply:
    def test_alexnet_bitwise(self):
        key = jax.random.PRNGKey(0)
        params = cnn.init_cnn("alexnet", key)
        x = jax.random.normal(key, (1, 227, 227, 3), jnp.float32) * 0.1
        compiled = E.compile(cnn.program("alexnet"), E.EngineConfig())
        got = compiled.apply(params, x)
        want = cnn.apply_cnn("alexnet", params, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_resnet50_bitwise(self):
        key = jax.random.PRNGKey(1)
        params = cnn.init_cnn("resnet50", key)
        x = jax.random.normal(key, (1, 224, 224, 3), jnp.float32) * 0.1
        compiled = E.compile(cnn.program("resnet50"), E.EngineConfig())
        got = compiled.apply(params, x)
        want = cnn.apply_cnn("resnet50", params, x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # paper-counting plan (50 ops) vs real-geometry execution (54 ops)
        assert len(compiled.plan.plans) == 50
        assert len(compiled.exec_pairs) == 54

    def test_shape_divergence_raises(self):
        key = jax.random.PRNGKey(0)
        params = cnn.init_cnn("alexnet", key)
        compiled = E.compile(cnn.program("alexnet"), E.EngineConfig())
        with pytest.raises(RuntimeError, match="diverged|mismatch"):
            compiled.apply(params, jnp.ones((2, 227, 227, 3), jnp.float32))

    def test_program_without_fn_cannot_apply(self):
        prog = E.Program("bare", cnn.program("alexnet").ops)
        compiled = E.compile(prog, E.EngineConfig())
        assert compiled.plan.conv_cycles > 0
        with pytest.raises(ValueError, match="no executable fn"):
            compiled.apply(None, None)

    def test_tracking_prices_compiled_trace(self):
        key = jax.random.PRNGKey(0)
        params = cnn.init_cnn("alexnet", key)
        x = jnp.zeros((1, 227, 227, 3), jnp.float32)
        with E.tracking() as led:
            compiled = E.compile(cnn.program("alexnet"), E.EngineConfig())
            compiled.apply(params, x)
        # capture is paused (no phantom ops); the jitted trace records once
        assert len(led) == 8
        assert led.total_cycles == compiled.plan.conv_cycles \
            + compiled.plan.fc_cycles


# ---------------------------------------------------------------------------
# trace_program (transformer / SSM serve forwards)
# ---------------------------------------------------------------------------

class TestTraceProgram:
    def test_trace_simple_fn(self):
        def f(w, x):
            h = E.conv2d(x, w["c"], pad=1)
            return E.dense(h.reshape(h.shape[0], -1), w["d"])

        avals = ({"c": jax.ShapeDtypeStruct((3, 3, 4, 8), jnp.float32),
                  "d": jax.ShapeDtypeStruct((8 * 8 * 8, 10), jnp.float32)},
                 jax.ShapeDtypeStruct((1, 8, 8, 4), jnp.float32))
        prog = E.trace_program(f, *avals, name="tiny")
        assert [op.kind for op in prog.ops] == ["conv2d", "dense"]
        compiled = E.compile(prog, E.EngineConfig())
        w = {"c": jnp.ones((3, 3, 4, 8)), "d": jnp.ones((8 * 8 * 8, 10))}
        x = jnp.ones((1, 8, 8, 4))
        np.testing.assert_array_equal(np.asarray(compiled.apply(w, x)),
                                      np.asarray(f(w, x)))

    def test_trace_is_abstract_and_unledgered(self):
        calls = []

        def f(x, w):
            calls.append(1)
            return E.dense(x, w)

        with E.tracking() as led:
            prog = E.trace_program(
                f, jax.ShapeDtypeStruct((4, 16), jnp.float32),
                jax.ShapeDtypeStruct((16, 8), jnp.float32))
        assert len(prog.ops) == 1 and len(led) == 0

    def test_transformer_prefill_program(self, smollm_reduced):
        from repro.serve import engine as SE
        cfg = smollm_reduced
        prog = SE.prefill_program(cfg, batch=2, seq=16)
        assert len(prog.ops) > 0
        assert all(op.kind == "dense" for op in prog.ops)
        nplan = E.plan_network(prog, E.EngineConfig())
        assert nplan.fc_cycles > 0 and nplan.total_macs > 0

    def test_ssm_programs(self):
        from repro.configs.base import reduced
        from repro.serve import engine as SE
        cfg = reduced("xlstm_125m")
        prog = SE.prefill_program(cfg, batch=2, seq=16)
        kinds = {op.kind for op in prog.ops}
        # the xLSTM short conv rides the 1-D conv mode of the same engine
        assert kinds == {"dense", "conv1d_dw"}
        # decode updates the conv state incrementally (taps as FC work)
        dprog = SE.decode_program(cfg, batch=2, max_len=32)
        assert {op.kind for op in dprog.ops} == {"dense"}
        assert E.plan_network(dprog, E.EngineConfig()).fc_cycles > 0


# ---------------------------------------------------------------------------
# Batch rewrite: Program.with_batch (re-plan without re-tracing)
# ---------------------------------------------------------------------------


class TestWithBatch:
    def test_cnn_program_rebatch_scales_plan_linearly(self):
        p1 = cnn.program("alexnet")
        p4 = p1.with_batch(4)
        assert p4.batch_size == 4
        assert all(op.x_shape[0] == 4 for op in p4.ops)
        assert p4.in_avals[1].shape == (4, 227, 227, 3)
        n1 = E.plan_network(p1, E.EngineConfig())
        n4 = E.plan_network(p4, E.EngineConfig())
        assert n4.conv_cycles == 4 * n1.conv_cycles
        assert n4.fc_cycles == 4 * n1.fc_cycles
        assert n4.total_macs == 4 * n1.total_macs

    def test_rebatch_identity_and_validation(self):
        p = cnn.program("alexnet", batch=2)
        assert p.with_batch(2) is p
        with pytest.raises(ValueError, match="batch must be"):
            p.with_batch(0)
        bare = E.Program("bare", p.ops)
        with pytest.raises(ValueError, match="no batch metadata"):
            bare.with_batch(4)

    def test_traced_decode_program_rebatch(self, smollm_reduced):
        # decode state buries the batch at axis 1 for grouped layers —
        # infer_batch_axes must find it per leaf, not assume axis 0.
        from repro.serve import engine as SE
        dp1 = SE.decode_program(smollm_reduced, batch=1, max_len=32)
        dp8 = dp1.with_batch(8)
        want = SE.decode_program(smollm_reduced, batch=8, max_len=32)
        assert dp8.ops == want.ops
        got_shapes = jax.tree_util.tree_map(
            lambda a: tuple(a.shape), dp8.in_avals)
        want_shapes = jax.tree_util.tree_map(
            lambda a: tuple(a.shape), want.in_avals)
        assert got_shapes == want_shapes

    def test_infer_batch_axes_errors(self):
        a = (jax.ShapeDtypeStruct((1, 4), jnp.float32),)
        amb = (jax.ShapeDtypeStruct((2, 8), jnp.float32),)
        with pytest.raises(ValueError, match="ambiguous"):
            E.infer_batch_axes(a, amb)
        with pytest.raises(ValueError, match="pass batch_size"):
            E.trace_program(lambda x: x, a[0], batch_size=1)

    def test_rebatched_compile_executes(self):
        key = jax.random.PRNGKey(0)
        params = cnn.init_cnn("alexnet", key)
        x2 = jax.random.normal(key, (2, 227, 227, 3), jnp.float32) * 0.1
        compiled = E.compile(cnn.program("alexnet").with_batch(2),
                             E.EngineConfig())
        got = compiled.apply(params, x2)
        want = cnn.apply_cnn("alexnet", params, x2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# "auto" backend-selection policy
# ---------------------------------------------------------------------------

class TestAutoPolicy:
    def test_selection_rules(self):
        gemm = E.OpSpec("dense", (64, 256), (256, 128), spec="...n,nm->...m")
        small = E.OpSpec("dense", (64, 32), (32, 16), spec="...n,nm->...m")
        moe = E.OpSpec("dense", (4, 8, 256), (4, 256, 128),
                       spec="ecd,edf->ecf")
        c1x1 = E.OpSpec("conv2d", (1, 28, 28, 256), (1, 1, 256, 128))
        c3x3 = E.OpSpec("conv2d", (1, 28, 28, 256), (3, 3, 256, 256))
        assert E.auto_backend(gemm) == "pallas"
        assert E.auto_backend(small) == "xla"          # under-fills the MXU
        assert E.auto_backend(moe) == "xla"            # batched weights
        assert E.auto_backend(c1x1) == "pallas"        # T=1: pure GEMM
        assert E.auto_backend(c3x3) == "xla"
        assert E.auto_backend(small, fallback="ref") == "ref"

    def test_compile_auto_assigns_per_layer(self):
        def f(w, x):
            h = E.conv2d(x, w["c"], pad=0)             # 1x1, 128ch: pallas
            h = h.reshape(h.shape[0], -1)
            h = E.dense(h, w["d1"])                    # large GEMM: pallas
            return E.dense(h, w["d2"])                 # tiny out: xla

        avals = ({"c": jax.ShapeDtypeStruct((1, 1, 128, 128), jnp.float32),
                  "d1": jax.ShapeDtypeStruct((4 * 4 * 128, 128), jnp.float32),
                  "d2": jax.ShapeDtypeStruct((128, 10), jnp.float32)},
                 jax.ShapeDtypeStruct((1, 4, 4, 128), jnp.float32))
        prog = E.trace_program(f, *avals)
        compiled = E.compile(prog, E.EngineConfig(policy="auto"))
        assert compiled.backends() == ("pallas", "pallas", "xla")
        w = {"c": jax.random.normal(jax.random.PRNGKey(0), (1, 1, 128, 128)),
             "d1": jax.random.normal(jax.random.PRNGKey(1),
                                     (4 * 4 * 128, 128)),
             "d2": jax.random.normal(jax.random.PRNGKey(2), (128, 10))}
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 4, 4, 128))
        fixed = E.compile(prog, E.EngineConfig())
        np.testing.assert_allclose(np.asarray(compiled.apply(w, x)),
                                   np.asarray(fixed.apply(w, x)),
                                   rtol=2e-4, atol=2e-4)

    def test_lowerings_fold_only_the_stem(self):
        # ResNet-50's conv1 (C_in 3) is the one conv whose taps fold; every
        # other conv under "auto" is Pallas or keeps the XLA band loop.
        compiled = E.compile(cnn.program("resnet50"),
                             E.EngineConfig(policy="auto"))
        lowerings = compiled.lowerings()
        assert len(lowerings) == len(compiled.backends()) == 54
        folded = [op.name for (op, _), low in zip(compiled.exec_pairs,
                                                  lowerings) if low == "fold"]
        assert folded == ["conv1"]
        assert set(lowerings) == {"fold", "band", "pallas"}
        # Under backend="pallas" AlexNet's conv1 (C_in 3) folds in the
        # Pallas wrapper; every op still runs on Pallas.
        pallas = E.compile(cnn.program("alexnet"),
                           E.EngineConfig(backend="pallas"))
        assert pallas.lowerings() == ("fold",) + ("pallas",) * 7
        assert set(pallas.backends()) == {"pallas"}

    def test_eager_auto_policy(self):
        x = jnp.ones((64, 256))
        w = jnp.ones((256, 128))
        with E.tracking() as led, E.using_config(
                E.EngineConfig(policy="auto")):
            E.dense(x, w)
        assert led.records[0].plan.backend == "pallas"


# ---------------------------------------------------------------------------
# apply_cnn config threading + serve builders
# ---------------------------------------------------------------------------

class TestConfigThreading:
    def test_apply_cnn_accepts_config(self):
        key = jax.random.PRNGKey(0)
        params = cnn.init_cnn("alexnet", key)
        x = jax.random.normal(key, (1, 227, 227, 3), jnp.float32) * 0.1
        with E.tracking() as led:
            y = cnn.apply_cnn("alexnet", params, x,
                              config=E.EngineConfig(backend="ref"))
        assert y.shape == (1, 1000)
        assert all(r.plan.backend == "ref" for r in led)

    def test_apply_cnn_rejects_both_config_and_backend(self):
        x = jnp.zeros((1, 227, 227, 3), jnp.float32)
        with pytest.raises(ValueError, match="not both"):
            cnn.apply_cnn("alexnet", {}, x, backend="xla",
                          config=E.EngineConfig())

    def test_serve_step_accepts_engine_config(self, smollm_reduced,
                                              host_mesh, smollm_params):
        from repro.models import transformer as T
        from repro.serve import engine as SE
        cfg = smollm_reduced
        jitted, contract = SE.build_serve_step(
            cfg, host_mesh, batch=2, max_len=32,
            engine_config=E.EngineConfig(backend="xla"))
        state = T.init_decode_state(cfg, 2, 32)
        tok = jnp.zeros((2, 1), jnp.int32)
        logits, nxt, _ = jitted(smollm_params, state, tok, jnp.int32(0))
        assert logits.shape[0] == 2 and nxt.shape == (2,)
