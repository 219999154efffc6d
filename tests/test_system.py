"""End-to-end behaviour tests for the paper's system: the multi-mode engine
runs a CNN (conv mode) and an LM (FC mode) through ONE engine; training
makes progress; the crash/resume driver works."""
import math

import jax
import jax.numpy as jnp
import pytest

from repro import engine as E

jax.config.update("jax_platform_name", "cpu")


def test_engine_runs_conv_and_fc():
    """The paper's headline: conv AND fc work on the same engine, and the
    engine's ledger prices both in the same PE currency."""
    key = jax.random.PRNGKey(0)
    x_img = jax.random.normal(key, (1, 12, 12, 8))
    w_conv = jax.random.normal(key, (3, 3, 8, 16))
    x_vec = jax.random.normal(key, (4, 64))
    w_fc = jax.random.normal(key, (64, 32))
    x_seq = jax.random.normal(key, (2, 10, 6))
    w_1d = jax.random.normal(key, (4, 6))
    with E.tracking() as led:
        y = E.conv2d(x_img, w_conv, stride=1, pad=1, backend="xla")
        z = E.dense(x_vec, w_fc, backend="xla")
        s = E.conv1d_depthwise(x_seq, w_1d, backend="xla")
    assert y.shape == (1, 12, 12, 16)
    assert z.shape == (4, 32)
    assert s.shape == (2, 10, 6)
    kinds = {r.kind for r in led}       # the ledger books dense as "matmul"
    assert kinds == {"conv2d", "matmul", "conv1d_dw"}
    assert led.total_cycles > 0 and 0 < led.performance_efficiency <= 1.0


def test_end_to_end_train_and_generate():
    """Tiny LM: train a few steps, loss drops, then prefill+decode."""
    from repro.configs.base import reduced
    from repro.data import pipeline as dp
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.train import step as TS

    cfg = reduced("smollm_135m")
    mesh = make_host_mesh()
    ts, contract = TS.build_train_step(
        cfg, mesh, hyper=TS.TrainHyper(peak_lr=1e-3, warmup_steps=2,
                                       total_steps=12))
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    opt = contract["opt_init"](params)
    dcfg = dp.DataConfig(seq_len=48, global_batch=4,
                         vocab_size=cfg.vocab_size)
    b0 = dp.lm_batch(cfg, dcfg, 0)
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.asarray(x).dtype), b0)
    jitted = TS.jit_train_step(cfg, mesh, ts, contract, shapes)
    losses = []
    for step in range(12):
        batch = dp.lm_batch(cfg, dcfg, step)
        params, opt, m = jitted(params, opt, batch, jnp.int32(step))
        losses.append(float(m["loss"]))
    assert all(math.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]

    prompt = {"tokens": dp.lm_batch(cfg, dcfg, 99)["tokens"][:2, :12]}
    logits, state = T.prefill(cfg, params, prompt, max_len=24)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    for i in range(4):
        lg, state = T.decode_step(cfg, params, state, tok, jnp.int32(12 + i))
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    assert tok.shape == (2, 1)
    assert not bool(jnp.isnan(lg).any())


def test_crash_resume_driver(tmp_path):
    """The launch/train.py fault-tolerance path: train, 'crash', resume."""
    from repro.launch import train as train_mod
    base = ["--arch", "smollm-135m", "--reduced",
            "--seq", "32", "--batch", "4", "--ckpt-dir", str(tmp_path),
            "--ckpt-every", "4", "--log-every", "5"]
    h1 = train_mod.main(base + ["--steps", "6"])      # 'crash' after 6
    h2 = train_mod.main(base + ["--steps", "10", "--resume"])
    assert h1 and h2, "resume produced no steps"
    assert h2[0]["step"] >= 4, h2                     # resumed, not restarted
