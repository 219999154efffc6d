"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.kernels import ops, ref
from repro.models.attention import dense_attention

jax.config.update("jax_platform_name", "cpu")


_PAPER_MODES = [(1, 1, 0, 1), (3, 1, 1, 1), (5, 1, 2, 1), (7, 2, 3, 1),
                (11, 4, 0, 1), (3, 1, 1, 2), (5, 1, 2, 2)]


def _pallas_grids(fn, *args):
    """The grids of the pallas_calls `fn` traces to."""
    return [e.params["grid_mapping"].grid
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]


class TestGfidConv2d:
    # C_in 8 (4 or 8 a group) folds its taps (`gfid.folds_taps`); C_in 32
    # (16 or 32 a group) keeps the kernel's stride-phase band loop.
    @pytest.mark.parametrize("k,s,pad,groups,c_in", [
        pytest.param(*m, c, id="-".join(map(str, m))
                     + ("" if c == 8 else f"-c{c}"))
        for c in (8, 32) for m in _PAPER_MODES])
    def test_paper_modes(self, k, s, pad, groups, c_in):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 23, 23, c_in),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1),
                              (k, k, c_in // groups, 16), jnp.float32)
        got = ops.gfid_conv2d(x, w, stride=s, pad=pad, groups=groups)
        want = ref.conv2d_ref(x, w, stride=s, pad=pad, groups=groups)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("epilogue", [False, True])
    def test_fold_alexnet_conv1(self, epilogue):
        # AlexNet's conv1: 227x227x3, 11x11, stride 4, 96 filters. Folded,
        # the kernel runs 3 height taps a row at stride 1 over K = 144.
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 227, 227, 3),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (11, 11, 3, 96),
                              jnp.float32) * (2 / 363) ** 0.5   # He-normal
        b = jax.random.normal(jax.random.PRNGKey(2), (96,), jnp.float32)
        kw = dict(bias=b, act="relu") if epilogue else {}
        fn = lambda x, w: ops.gfid_conv2d(x, w, stride=4, **kw)  # noqa: E731
        assert _pallas_grids(fn, x, w) == [(1, 55, 1, 3, 1)]
        with jax.default_matmul_precision("highest"):
            got = fn(x, w)
            want = ref.conv2d_ref(x, w, stride=4)
        if epilogue:
            want = jax.nn.relu(want + b)
        assert got.shape == (1, 55, 55, 96)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_fold_grouped(self):
        # Two groups of C_in 4: each group folds inside the vmap over
        # groups, 5x5 stride 2 -> 3 height taps over K = 3*2*2*4 = 48.
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 21, 21, 8),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (5, 5, 4, 16),
                              jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(2), (16,), jnp.float32)
        fn = lambda x, w: ops.gfid_conv2d(  # noqa: E731
            x, w, stride=2, pad=2, groups=2, bias=b, act="relu")
        # (groups, B, H_out, n_co, height taps, n_ci)
        assert _pallas_grids(fn, x, w) == [(2, 2, 11, 1, 3, 1)]
        with jax.default_matmul_precision("highest"):
            got = fn(x, w)
            want = jax.nn.relu(
                ref.conv2d_ref(x, w, stride=2, pad=2, groups=2) + b)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 12, 4), dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8), dtype)
        got = ops.gfid_conv2d(x, w, stride=1, pad=1)
        want = ref.conv2d_ref(x, w, stride=1, pad=1)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    @given(h=st.integers(8, 20), k=st.sampled_from([1, 3, 5]),
           s=st.integers(1, 2))
    @settings(max_examples=10, deadline=None)
    def test_shape_sweep(self, h, k, s):
        x = jax.random.normal(jax.random.PRNGKey(h), (1, h, h, 4),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, k, 4, 8),
                              jnp.float32)
        got = ops.gfid_conv2d(x, w, stride=s, pad=k // 2)
        want = ref.conv2d_ref(x, w, stride=s, pad=k // 2)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


class TestGfidMatmul:
    @given(m=st.integers(1, 80), k=st.integers(1, 96), n=st.integers(1, 80))
    @settings(max_examples=15, deadline=None)
    def test_shapes(self, m, k, n):
        x = jax.random.normal(jax.random.PRNGKey(m * 7 + n), (m, k),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(k), (k, n), jnp.float32)
        got = ops.gfid_matmul(x, w)
        np.testing.assert_allclose(got, ref.matmul_ref(x, w),
                                   rtol=1e-4, atol=1e-4)

    def test_batched_lead_dims(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 32), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 16), jnp.float32)
        got = ops.gfid_matmul(x, w)
        np.testing.assert_allclose(got, x @ w, rtol=1e-4, atol=1e-4)


class TestConv1dDepthwise:
    @pytest.mark.parametrize("w_f,causal", [(4, True), (4, False),
                                            (128, False), (2, True)])
    def test_modes(self, w_f, causal):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 8), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (w_f, 8), jnp.float32)
        got = ops.gfid_conv1d_depthwise(x, w, causal=causal)
        want = ref.conv1d_depthwise_ref(x, w, causal=causal)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("b,s,h,kv,d,causal", [
        (2, 64, 4, 2, 16, True), (1, 128, 8, 8, 32, True),
        (2, 96, 4, 4, 16, False), (1, 64, 6, 3, 8, True)])
    def test_vs_dense(self, b, s, h, kv, d, causal):
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d),
                              jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d),
                              jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d),
                              jnp.float32)
        got = ops.flash_attention(q, k, v, causal=causal)
        want = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
