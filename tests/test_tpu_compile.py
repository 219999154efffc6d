"""Compile the main-path Pallas kernels at real widths for a TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
described `v5e:2x2` topology, so a kernel that Mosaic refuses (a strided
vector slice, a block that breaks the (8, 128) rule, a tile over the
scoped-VMEM limit) fails here instead of on the chip. Every test asserts
the kernel is in the compiled program as a `tpu_custom_call`.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library at a time, and under
pytest-xdist only the worker that runs this file should.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine as E
from repro.engine import tune
from repro.kernels import ops
from repro.models import cnn


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs to /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes, dtypes=None):
    dtypes = dtypes or (jnp.float32,) * len(shapes)
    avals = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
             for s, d in zip(shapes, dtypes)]
    return jax.jit(fn).lower(*avals).compile().as_text()


# (name, x shape, w shape, stride, pad, groups) at published widths, batch 8
CONVS = [
    ("alexnet_conv1_s4", (8, 227, 227, 3), (11, 11, 3, 96), 4, 0, 1),
    ("alexnet_conv2_grouped", (8, 27, 27, 96), (5, 5, 48, 256), 1, 2, 2),
    ("resnet50_conv1_7x7_s2", (8, 224, 224, 3), (7, 7, 3, 64), 2, 3, 1),
    ("resnet50_3x3_s2", (8, 56, 56, 128), (3, 3, 128, 128), 2, 1, 1),
    ("resnet50_proj_1x1_s2", (8, 56, 56, 256), (1, 1, 256, 512), 2, 0, 1),
    ("vgg16_conv1_2_224", (8, 224, 224, 64), (3, 3, 64, 64), 1, 1, 1),
]


@pytest.mark.parametrize("name,xs,ws,stride,pad,groups", CONVS,
                         ids=[c[0] for c in CONVS])
def test_gfid_conv2d_compiles(one_chip, name, xs, ws, stride, pad, groups):
    def fn(x, w, b):
        return ops.gfid_conv2d(x, w, stride=stride, pad=pad, groups=groups,
                               bias=b, act="relu", interpret=False)
    text = _compiled_text(fn, one_chip, xs, ws, (ws[3],))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,xs,ws,stride,pad", [
    ("alexnet_conv1_s4", (8, 227, 227, 3), (11, 11, 3, 96), 4, 0),
    ("alexnet_conv3", (8, 13, 13, 256), (3, 3, 256, 384), 1, 1),
], ids=["alexnet_conv1_s4", "alexnet_conv3"])
def test_int8_conv_compiles_at_batch_8(one_chip, name, xs, ws, stride, pad):
    def fn(x, w, b):
        return ops.gfid_conv2d(x, w, stride=stride, pad=pad, bias=b,
                               act="relu", interpret=False, precision="int8")
    text = _compiled_text(fn, one_chip, xs, ws, (ws[3],))
    assert "tpu_custom_call" in text


FCS = [("fc6", 9216, 4096), ("fc7", 4096, 4096), ("fc8", 4096, 1000)]


@pytest.mark.parametrize("precision", ["fp32", "int8"])
@pytest.mark.parametrize("name,k,n", FCS, ids=[f[0] for f in FCS])
def test_gfid_matmul_compiles_alexnet_fc(one_chip, name, k, n, precision):
    def fn(x, w, b):
        return ops.gfid_matmul(x, w, bias=b, act="relu", interpret=False,
                               precision=precision)
    text = _compiled_text(fn, one_chip, (8, k), (k, n), (n,))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_largest_kept_fc6_tile_compiles(one_chip, precision):
    """The tuner's VMEM guard is the compiler's: the largest-footprint tile
    `candidates_for` keeps for FC6 (M = 1024 rows) must compile."""
    op = E.OpSpec("dense", (1024, 9216), (9216, 4096), spec=E.dense_spec(2))
    kept = tune.candidates_for(op, limit=10 ** 6, precision=precision)
    tile = max(kept, key=lambda t: tune.dense_vmem_bytes(t, precision))
    assert tune.dense_vmem_bytes(tile, precision) <= tune.SCOPED_VMEM_BYTES

    def fn(x, w):
        return ops.gfid_matmul(x, w, tile=tile, interpret=False,
                               precision=precision)
    text = _compiled_text(fn, one_chip, (1024, 9216), (9216, 4096))
    assert "tpu_custom_call" in text


def test_alexnet_program_has_one_kernel_per_pallas_op(one_chip):
    """The whole batch-8 AlexNet forward under backend="pallas": every conv
    (conv1 at S=4 and the grouped ones) and every FC is one kernel."""
    prog = cnn.program("alexnet", batch=8)
    net = E.compile(prog, E.EngineConfig(backend="pallas", interpret=False))
    n_pallas = sum(b == "pallas" for b in net.backends())
    assert n_pallas == 8
    params, x = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        prog.in_avals)
    text = jax.jit(net.apply).lower(params, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_pallas


def test_alexnet_program_compiles_at_highest_precision(one_chip):
    """Traced under default_matmul_precision("highest"), the kernels' dots
    lower with an fp32 contract precision, which Mosaic must accept for
    every AlexNet conv and FC (chip_smoke.py compares this forward with
    ref)."""
    prog = cnn.program("alexnet", batch=8)
    net = E.compile(prog, E.EngineConfig(backend="pallas", interpret=False))
    params, x = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        prog.in_avals)
    with jax.default_matmul_precision("highest"):
        jaxpr = str(jax.make_jaxpr(net.apply)(params, x))
        lowered = jax.jit(net.apply).lower(params, x)
    # every dot of the net is inside a kernel body
    assert "precision=(Precision.HIGHEST" in jaxpr
    assert "precision=None" not in jaxpr
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 8
