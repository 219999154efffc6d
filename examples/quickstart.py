"""Quickstart: train a tiny llama-family LM on the synthetic pipeline for a
handful of steps, checkpoint it, restore it, and generate — all on CPU in
about a minute.

  PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import reduced
from repro.data import pipeline as dp
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.train import step as TS


def main():
    compile_cache.enable()
    cfg = reduced("smollm-135m")
    mesh = make_host_mesh()
    hyper = TS.TrainHyper(peak_lr=1e-3, warmup_steps=5, total_steps=30)
    train_step, contract = TS.build_train_step(cfg, mesh, hyper=hyper)

    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    opt_state = contract["opt_init"](params)
    dcfg = dp.DataConfig(seq_len=64, global_batch=8,
                         vocab_size=cfg.vocab_size)
    batch0 = dp.lm_batch(cfg, dcfg, 0)
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.asarray(x).dtype), batch0)
    jitted = TS.jit_train_step(cfg, mesh, train_step, contract, shapes)

    print(f"training {cfg.name}: "
          f"{sum(x.size for x in jax.tree_util.tree_leaves(params))/1e3:.0f}k"
          " params")
    for step in range(30):
        batch = dp.lm_batch(cfg, dcfg, step)
        params, opt_state, m = jitted(params, opt_state, batch,
                                      jnp.int32(step))
        if step % 5 == 0:
            print(f"  step {step:3d} loss {float(m['loss']):.4f}")

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(30, {"params": params})
        params = mgr.restore(30, {"params": params})["params"]
        print("checkpoint roundtrip ok")

    # greedy generation from a prompt
    prompt = {"tokens": dp.lm_batch(cfg, dcfg, 99)["tokens"][:2, :16]}
    logits, state = T.prefill(cfg, params, prompt, max_len=48)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    outs = [tok]
    for i in range(15):
        lg, state = T.decode_step(cfg, params, state, tok, jnp.int32(16 + i))
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        outs.append(tok)
    gen = jnp.concatenate(outs, 1)
    print("generated:", gen[0].tolist())
    print("quickstart done.")


if __name__ == "__main__":
    main()
