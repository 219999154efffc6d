"""The plan-based engine API (repro.engine): backend parity across the
registry, plan purity/hashability (jit-cache stability) and ledger
semantics under tracing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E

# CPU platform pin + shared fixtures live in conftest.py

TABLE3_MODES = [(11, 4), (7, 2), (5, 1), (3, 1), (1, 1)]
BACKENDS = ("ref", "xla", "pallas")


# ---------------------------------------------------------------------------
# Backend parity
# ---------------------------------------------------------------------------

class TestBackendParity:
    @pytest.mark.parametrize("w_f,s", TABLE3_MODES)
    def test_conv2d_all_backends(self, w_f, s):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 23, 23, 8),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (w_f, w_f, 8, 16),
                              jnp.float32)
        outs = {b: E.conv2d(x, w, stride=s, pad=w_f // 2, backend=b)
                for b in BACKENDS}
        for b in ("xla", "pallas"):
            np.testing.assert_allclose(outs[b], outs["ref"], rtol=2e-4,
                                       atol=2e-4)

    def test_dense_all_backends(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 48), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (48, 32), jnp.float32)
        outs = {b: E.dense(x, w, backend=b) for b in BACKENDS}
        for b in ("xla", "pallas"):
            np.testing.assert_allclose(outs[b], outs["ref"], rtol=1e-4,
                                       atol=1e-4)

    def test_conv1d_depthwise_all_backends(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 17, 6), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (4, 6), jnp.float32)
        outs = {b: E.conv1d_depthwise(x, w, causal=True) for b in BACKENDS}
        for b in ("xla", "pallas"):
            np.testing.assert_allclose(outs[b], outs["ref"], rtol=1e-4,
                                       atol=1e-4)

    @pytest.mark.parametrize("spec,xs,ws", [
        ("...d,df->...f", (2, 5, 16), (16, 24)),     # FFN in-proj
        ("...d,vd->...v", (2, 5, 16), (40, 16)),     # tied unembed
        ("ecd,edf->ecf", (3, 7, 16), (3, 16, 8)),    # MoE expert GEMMs
        ("bhd,chd->bhc", (2, 4, 8), (10, 4, 8)),     # MLA absorbed W_uk
    ])
    def test_einsum_matches_jnp(self, spec, xs, ws):
        x = jax.random.normal(jax.random.PRNGKey(0), xs, jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), ws, jnp.float32)
        want = jnp.einsum(spec, x, w)
        for b in BACKENDS:
            got = E.einsum(spec, x, w, backend=b)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Plans: pure, hashable, jit-cache stable
# ---------------------------------------------------------------------------

class TestPlans:
    def test_plan_pure_and_hashable(self):
        a = E.plan_conv2d((1, 12, 12, 8), (3, 3, 8, 16), 1, 1, 1, "xla")
        b = E.plan_conv2d((1, 12, 12, 8), (3, 3, 8, 16), 1, 1, 1, "xla")
        assert a == b and hash(a) == hash(b)
        assert {a: "v"}[b] == "v"                  # usable as dict key
        assert a.mode == E.plan_conv2d(
            (1, 12, 12, 8), (3, 3, 8, 16), 1, 1, 1, "pallas").mode

    def test_plan_matches_paper_mode(self):
        for w_f, s in TABLE3_MODES:
            p = E.plan_conv2d((1, 23, 23, 8), (w_f, w_f, 8, 16), s, 0, 1,
                              "xla")
            assert (p.mode.w_f, p.mode.s) == (w_f, s)
            assert p.macs > 0 and p.cycles > 0
            assert 0.0 < p.performance_efficiency <= 1.0

    def test_plan_tolerates_wide_1d_filters(self):
        # hubert's 128-tap positional conv exceeds the 11-register MMIE
        # weight generator; the plan books a derived schedule, no crash.
        p = E.plan_conv1d_depthwise((2, 64, 32), (128, 32), "xla")
        assert p.mode.w_f == 128 and p.cycles > 0

    def test_jit_cache_stable(self):
        traces = []

        @jax.jit
        def f(x, w):
            traces.append(1)
            return E.dense(x, w)

        x = jnp.ones((4, 16)); w = jnp.ones((16, 8))
        f(x, w); f(x, w); f(x + 1, w)
        assert len(traces) == 1                    # one trace, one compile

    def test_dense_einsum_macs_accounting(self):
        p = E.plan_einsum("...n,nm->...m", (7, 3, 64), (64, 32), "xla")
        assert p.macs == 7 * 3 * 64 * 32
        pe = E.plan_einsum("ecd,edf->ecf", (4, 9, 16), (4, 16, 8), "xla")
        assert pe.macs == 4 * 9 * 16 * 8

    def test_unsupported_specs_raise(self):
        with pytest.raises(ValueError):
            E.plan_einsum("ab,bc", (2, 3), (3, 4), "xla")     # no output
        with pytest.raises(ValueError):
            E.plan_einsum("ab,cd->ad", (2, 3), (3, 4), "xla")  # summed label


# ---------------------------------------------------------------------------
# Ledger / tracking
# ---------------------------------------------------------------------------

class TestLedger:
    def test_totals_identical_across_retraces(self):
        def f(x, w):
            return E.dense(E.conv2d(x, w, pad=1).reshape(x.shape[0], -1),
                           jnp.ones((12 * 12 * 16, 8), jnp.float32))

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16))
        with E.tracking() as eager:
            f(x, w)
        with E.tracking() as trace1:
            jax.jit(f)(x, w)
        jax.clear_caches()                         # force a genuine re-trace
        with E.tracking() as trace2:
            jax.jit(f)(x, w)
        assert (eager.total_cycles, eager.total_macs) \
            == (trace1.total_cycles, trace1.total_macs) \
            == (trace2.total_cycles, trace2.total_macs)
        assert len(eager) == len(trace1) == len(trace2) == 2

    def test_no_tracking_records_nothing(self):
        with E.tracking() as led:
            pass
        E.dense(jnp.ones((2, 4)), jnp.ones((4, 3)))
        assert len(led) == 0

    def test_nested_tracking_stacks(self):
        x, w = jnp.ones((2, 4)), jnp.ones((4, 3))
        with E.tracking() as outer:
            E.dense(x, w)
            with E.tracking() as inner:
                E.dense(x, w)
        assert len(outer) == 2 and len(inner) == 1

    def test_report_format(self):
        with E.tracking() as led:
            E.conv2d(jnp.ones((1, 8, 8, 4)), jnp.ones((3, 3, 4, 8)), pad=1)
        lines = led.report().splitlines()
        assert lines[0].startswith("kind,mode(Wf,S)")
        assert lines[1].startswith("conv2d,(3,1),3,")


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_builtin_backends_registered(self):
        assert set(BACKENDS) <= set(E.backend_names())

    def test_unknown_backend_raises(self):
        with pytest.raises(KeyError, match="unknown engine backend"):
            E.dense(jnp.ones((2, 4)), jnp.ones((4, 3)), backend="nope")

    def test_register_custom_backend(self):
        ref = E.get_backend("ref")
        custom = E.EngineBackend("_test_double", ref.conv2d,
                                 ref.conv1d_depthwise,
                                 lambda spec, x, w, plan, st, **kw:
                                 2.0 * jnp.einsum(spec, x, w))
        E.register_backend(custom, overwrite=True)
        x, w = jnp.ones((2, 4)), jnp.ones((4, 3))
        np.testing.assert_allclose(E.dense(x, w, backend="_test_double"),
                                   2.0 * (x @ w))
        with pytest.raises(ValueError, match="already registered"):
            E.register_backend(custom)

    def test_using_backend_ambient(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 9, 4))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8))
        with E.tracking() as led, E.using_backend("ref"):
            E.conv2d(x, w, pad=1)
        assert led.records[0].plan.backend == "ref"


# ---------------------------------------------------------------------------
# EngineConfig: frozen, hashable, jit-static; context semantics
# ---------------------------------------------------------------------------

class TestEngineConfig:
    def test_frozen_hashable_equal(self):
        a = E.EngineConfig(backend="pallas", interpret=False)
        b = E.EngineConfig(backend="pallas", interpret=False)
        assert a == b and hash(a) == hash(b)
        assert {a: "v"}[b] == "v"
        with pytest.raises(Exception):
            a.backend = "xla"                       # frozen

    def test_invalid_policy_rejected(self):
        with pytest.raises(ValueError, match="policy"):
            E.EngineConfig(policy="greedy")

    def test_using_config_ambient(self):
        assert E.current_config().backend == "xla"
        with E.using_config(E.EngineConfig(backend="ref", interpret=False)):
            assert E.current_config().backend == "ref"
            assert not E.current_config().interpret
            with E.using_backend("pallas"):        # shim keeps other knobs
                assert E.current_config().backend == "pallas"
                assert not E.current_config().interpret
        assert E.current_config().backend == "xla"

    def test_set_default_config_errors_in_context(self):
        # a base write inside a context would be shadowed until it exits
        with E.using_backend("ref"):
            with pytest.raises(RuntimeError, match="silently shadowed"):
                E.set_default_config(E.EngineConfig())
        # outside a context it is a well-defined base write
        E.set_default_config(E.EngineConfig(backend="ref"))
        try:
            assert E.default_backend() == "ref"
        finally:
            E.set_default_config(E.EngineConfig())

    def test_config_as_static_jit_arg(self):
        from functools import partial
        traces = []

        @partial(jax.jit, static_argnums=0)
        def f(cfg, x, w):
            traces.append(1)
            with E.using_config(cfg):
                return E.dense(x, w)

        x, w = jnp.ones((4, 16)), jnp.ones((16, 8))
        f(E.EngineConfig(backend="ref"), x, w)
        f(E.EngineConfig(backend="ref"), x, w)      # equal config: cache hit
        assert len(traces) == 1
        f(E.EngineConfig(backend="xla"), x, w)      # distinct config: retrace
        assert len(traces) == 2

    def test_plan_cache_hits_across_retraces_under_config(self):
        from functools import partial

        @partial(jax.jit, static_argnums=0)
        def f(cfg, x, w):
            with E.using_config(cfg):
                return E.dense(x, w)

        x = jnp.ones((6, 24))
        w = jnp.ones((24, 12))
        cfg = E.EngineConfig(backend="xla")
        f(cfg, x, w)
        hits0 = E.plan_einsum.cache_info().hits
        jax.clear_caches()                          # force a genuine retrace
        f(cfg, x, w)
        assert E.plan_einsum.cache_info().hits > hits0

    def test_config_accum_policy(self):
        x = jnp.ones((2, 8), jnp.bfloat16)
        w = jnp.ones((8, 4), jnp.bfloat16)
        with E.using_config(E.EngineConfig(accum="float32")):
            y32 = E.einsum("...n,nm->...m", x, w)
        with E.using_config(E.EngineConfig(accum="native")):
            ynat = E.einsum("...n,nm->...m", x, w)
        assert y32.dtype == jnp.float32             # preferred_element_type
        assert ynat.dtype == jnp.bfloat16           # plain-@ numerics


# ---------------------------------------------------------------------------
# parse_einsum / plan_einsum edge cases
# ---------------------------------------------------------------------------

class TestEinsumEdgeCases:
    def test_ellipsis_on_both_operands(self):
        spec = "...ab,...bc->...ac"
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 3), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 5), jnp.float32)
        np.testing.assert_allclose(E.einsum(spec, x, w),
                                   jnp.einsum(spec, x, w), rtol=1e-6)
        p = E.plan_einsum(spec, (4, 2, 3), (4, 3, 5), "xla")
        assert p.macs == 4 * 2 * 3 * 5
        assert "batched weights" in p.note

    def test_repeated_labels_rejected(self):
        with pytest.raises(ValueError, match="repeated label"):
            E.plan_einsum("aa,ab->ab", (3, 3), (3, 4), "xla")
        with pytest.raises(ValueError, match="repeated label"):
            E.plan_einsum("ab,bb->ab", (2, 3), (3, 3), "xla")

    def test_zero_size_contract_dim(self):
        x = jnp.zeros((2, 0), jnp.float32)
        w = jnp.zeros((0, 3), jnp.float32)
        y = E.einsum("ab,bc->ac", x, w)
        np.testing.assert_array_equal(y, jnp.zeros((2, 3)))
        p = E.plan_einsum("ab,bc->ac", (2, 0), (0, 3), "xla")
        assert p.macs == 0 and p.cycles == 0 and p.ma_words == 0
        assert p.performance_efficiency == 0.0      # no div-by-zero

    def test_zero_size_free_dim(self):
        p = E.plan_einsum("ab,bc->ac", (0, 4), (4, 3), "xla")
        assert p.macs == 0 and p.cycles == 0

    def test_outer_product_books_one_mac_per_output(self):
        # no contract labels: still a planable FC op, not zero work
        p = E.plan_einsum("a,b->ab", (3,), (5,), "xla")
        assert p.macs == 3 * 5
