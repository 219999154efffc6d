"""GFID dataflow algebra: the banded matrix (Eq. 3-7), active-neuron counts
(Table 2), and the shifted-GEMM lowering vs XLA's direct convolution."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import gfid
from repro.core.modes import pes_per_tile

jax.config.update("jax_platform_name", "cpu")


class TestGFIDMatrix:
    def test_table1_example(self):
        """Paper Table 1 / Eq. 4: Wf=3, S=1, N=6 -> 8x6 banded matrix."""
        w = np.array([1.0, 2.0, 3.0])
        m = gfid.gfid_matrix(w, 6, 1)
        assert m.shape == (8, 6)
        np.testing.assert_array_equal(m[:3, 0], w)
        np.testing.assert_array_equal(m[5:8, 5], w)
        assert (np.count_nonzero(m, axis=1) <= 3).all()

    def test_eq5_identity_like(self):
        """Wf=1, S=1 (Eq. 5): square, one active neuron per cycle."""
        m = gfid.gfid_matrix(np.array([2.0]), 5, 1)
        assert m.shape == (5, 5)
        np.testing.assert_array_equal(m, 2.0 * np.eye(5))

    @pytest.mark.parametrize("w_f,s,t", [
        (1, 1, 1), (3, 1, 3), (5, 1, 5), (7, 2, 4), (11, 4, 3)])
    def test_table2_active_neurons(self, w_f, s, t):
        """Table 2: T = ceil(Wf/S) active neurons, verified structurally."""
        assert pes_per_tile(w_f, s) == t
        assert gfid.active_neurons_per_cycle(w_f, s, 8) == t

    @given(w_f=st.integers(1, 11), s=st.integers(1, 4),
           n=st.integers(2, 16))
    @settings(max_examples=50, deadline=None)
    def test_matrix_rows_equal_input_pixels(self, w_f, s, n):
        """Row count = S*N + Wf - S (paper §3.6) and the matrix-product
        semantics equal a direct valid conv."""
        w = np.random.default_rng(0).normal(size=w_f)
        m = gfid.gfid_matrix(w, n, s)
        assert m.shape == (s * n + w_f - s, n)
        x = np.random.default_rng(1).normal(size=m.shape[0])
        y = x @ m
        direct = np.array([(x[i * s:i * s + w_f] * w).sum()
                           for i in range(n)])
        np.testing.assert_allclose(y, direct, rtol=1e-10)


class TestShiftedGemmConv:
    @given(
        h=st.integers(6, 14), wdt=st.integers(6, 14),
        ci=st.sampled_from([1, 3, 8]), co=st.sampled_from([4, 8]),
        k=st.sampled_from([1, 3, 5]), s=st.integers(1, 2),
        p=st.integers(0, 2))
    @settings(max_examples=25, deadline=None)
    def test_matches_xla_conv(self, h, wdt, ci, co, k, s, p):
        if h + 2 * p < k or wdt + 2 * p < k:
            return
        kx = jax.random.PRNGKey(h * 100 + wdt)
        x = jax.random.normal(kx, (2, h, wdt, ci), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, k, ci, co),
                              jnp.float32)
        y1 = gfid.conv2d_gfid(x, w, stride=s, pad=p)
        y2 = gfid.conv2d_reference(x, w, stride=s, pad=p)
        np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("k,s,p,g", [
        (11, 4, 0, 1), (7, 2, 3, 1), (5, 1, 2, 2), (3, 1, 1, 1),
        (1, 1, 0, 1)])
    def test_paper_filter_modes(self, k, s, p, g):
        """All five (Wf, S) modes of Table 2."""
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 23, 23, 4),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, k, 4 // g, 8),
                              jnp.float32)
        y1 = gfid.conv2d_gfid(x, w, stride=s, pad=p, groups=g)
        y2 = gfid.conv2d_reference(x, w, stride=s, pad=p, groups=g)
        np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("x_shape,w_shape,s,p,g", [
        ((2, 224, 224, 3), (7, 7, 3, 64), 2, 3, 1),    # ResNet-50 stem
        ((1, 227, 227, 3), (11, 11, 3, 96), 4, 0, 1),  # AlexNet conv1
        ((2, 32, 32, 3), (3, 3, 3, 64), 1, 1, 1),      # VGG-16 conv1_1
        ((2, 13, 11, 1), (3, 3, 1, 8), 2, 1, 1),       # C_in 1
        ((2, 17, 19, 6), (5, 5, 3, 8), 2, 2, 2),       # grouped, 3 a group
        ((1, 23, 22, 3), (7, 7, 3, 8), 3, 0, 1),       # H, W not multiples of S
    ], ids=["resnet50_stem", "alexnet_conv1", "vgg16_conv1_1", "c_in_1",
            "grouped", "ragged"])
    def test_folded_taps_match_xla_conv(self, x_shape, w_shape, s, p, g):
        """Small-C_in convs take the folded lowering and match XLA's conv."""
        assert gfid.folds_taps(w_shape)
        x = jax.random.normal(jax.random.PRNGKey(0), x_shape, jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), w_shape, jnp.float32)
        y1 = gfid.conv2d_gfid(x, w, stride=s, pad=p, groups=g)
        y2 = gfid.conv2d_reference(x, w, stride=s, pad=p, groups=g)
        np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("c_in", [16, 64, 256])
    def test_band_loop_kept_from_16_channels(self, c_in):
        """From 16 channels a group the lowering is the band loop, bitwise."""
        assert not gfid.folds_taps((3, 3, c_in, 8))
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 9, c_in))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, c_in, 8))
        xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
        want = jnp.zeros((2, 5, 5, 8), jnp.float32)
        for j in range(3):
            for i in range(3):
                xs = jax.lax.slice(xp, (0, j, i, 0), (2, j + 9, i + 9, c_in),
                                   (1, 2, 2, 1))
                want = want + jnp.einsum("bhwc,cd->bhwd", xs, w[j, i],
                                         preferred_element_type=jnp.float32)
        got = gfid.conv2d_gfid(x, w, stride=2, pad=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(l=st.integers(4, 32), d=st.sampled_from([4, 8]),
           w_f=st.sampled_from([2, 4, 7]), causal=st.booleans())
    @settings(max_examples=25, deadline=None)
    def test_conv1d_depthwise(self, l, d, w_f, causal):
        x = jax.random.normal(jax.random.PRNGKey(l), (2, l, d), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (w_f, d), jnp.float32)
        y = gfid.conv1d_depthwise_gfid(x, w, causal=causal)
        # reference by explicit padding + shifted sums
        if causal:
            xp = jnp.pad(x, ((0, 0), (w_f - 1, 0), (0, 0)))
        else:
            lp = (w_f - 1) // 2
            xp = jnp.pad(x, ((0, 0), (lp, w_f - 1 - lp), (0, 0)))
        ref = sum(xp[:, i:i + l, :] * w[i] for i in range(w_f))
        np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)

    def test_fc_mode_is_gemm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        np.testing.assert_allclose(gfid.fc_gfid(x, w), x @ w, rtol=1e-5)
