"""Functional CNN models through the multi-mode engine (backends agree),
plus the paper's fixed-point quantization simulation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.core.quant import (ACT_FORMAT, WEIGHT_FORMAT, quantization_snr_db,
                              quantize)
from repro.models import cnn

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def small_resnet_io():
    # reduced spatial input keeps CPU runtime sane; engines must still agree
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 64, 64, 3), jnp.float32)
    return x


def test_backends_agree_alexnet():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn("alexnet", key)
    x = jax.random.normal(key, (1, 227, 227, 3), jnp.float32) * 0.1
    outs = {}
    for backend in ("xla", "ref"):
        outs[backend] = cnn.apply_cnn("alexnet", params, x, backend=backend)
    np.testing.assert_allclose(outs["xla"], outs["ref"], rtol=2e-3,
                               atol=2e-3)
    assert outs["xla"].shape == (1, 1000)


def test_engine_ledger_matches_table4_shape():
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn("alexnet", key)
    x = jax.random.normal(key, (1, 227, 227, 3), jnp.float32)
    with E.tracking() as led:
        cnn.apply_cnn("alexnet", params, x, backend="xla")
    conv_records = [r for r in led if r.kind == "conv2d"]
    fc_records = [r for r in led if r.kind == "matmul"]
    assert len(conv_records) == 5 and len(fc_records) == 3
    # ledger MACs equal the analytic census
    cm, fm = cnn.total_macs("alexnet")
    assert sum(r.macs for r in conv_records) == cm
    assert sum(r.macs for r in fc_records) == fm
    # total efficiency in the paper's ballpark
    assert 0.5 < led.performance_efficiency < 1.0


def test_fixed_point_quantization():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (256,)) * 0.05       # weight-scale values
    a = jax.random.normal(key, (256,)) * 2.0        # activation-scale
    wq = quantize(w, WEIGHT_FORMAT)
    aq = quantize(a, ACT_FORMAT)
    assert float(jnp.abs(wq - w).max()) <= 0.5 / WEIGHT_FORMAT.scale + 1e-9
    assert float(jnp.abs(aq - a).max()) <= 0.5 / ACT_FORMAT.scale + 1e-9
    # paper: <0.5% accuracy loss => SNR must be healthy for weights
    assert float(quantization_snr_db(w, WEIGHT_FORMAT)) > 40.0
