"""Multi-device semantics (8 host devices in a subprocess, since jax locks
the device count at first init — see the `run_distributed` fixture in
conftest.py): sharded train step, MoE EP-vs-dense parity, int8 DP gradient
sync, sharding-rule divisibility on a real mesh, elastic checkpoint restore
across meshes."""
import textwrap

PREAMBLE = """
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.engine.parallel import device_mesh
mesh = device_mesh((4, 2), ("data", "model"))
"""


def test_sharded_train_step_matches_single_device(run_distributed):
    res = run_distributed(PREAMBLE + textwrap.dedent("""
        from repro.configs.base import reduced
        from repro.models import transformer as T
        from repro.train import step as TS

        cfg = reduced('qwen3_32b')
        key = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        params = T.init_params(cfg, k1, jnp.float32)
        batch = {'tokens': jax.random.randint(k1, (8, 32), 0, cfg.vocab_size),
                 'labels': jax.random.randint(k2, (8, 32), 0, cfg.vocab_size)}
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)

        losses = {}
        for tag, m in (('sharded', mesh),
                       ('single', device_mesh((1, 1), ('data', 'model')))):
            ts, contract = TS.build_train_step(cfg, m)
            # donation consumes buffers: use fresh copies per mesh
            pc = jax.tree_util.tree_map(lambda a: a.copy(), params)
            opt = contract['opt_init'](pc)
            jitted = TS.jit_train_step(cfg, m, ts, contract, shapes)
            p2, o2, met = jitted(pc, opt, batch, jnp.int32(0))
            losses[tag] = float(met['loss'])
        print('RESULT', json.dumps(losses))
    """))
    assert abs(res["sharded"] - res["single"]) < 2e-3, res


def test_moe_ep_matches_dense(run_distributed):
    res = run_distributed(PREAMBLE + textwrap.dedent("""
        import dataclasses
        from repro.configs.base import reduced
        from repro.models import transformer as T, moe as M

        cfg = reduced('granite_moe_1b')
        # ensure experts divide the 2-way model axis and no capacity drops
        key = jax.random.PRNGKey(0)
        dummy = T.init_params(cfg, key, jnp.float32)
        p = jax.tree_util.tree_map(lambda a: a[0],
                                   dummy['groups']['0'])['moe']
        x = jax.random.normal(key, (4, 16, cfg.d_model), jnp.float32)
        y_dense, aux_d = M.moe_forward_dense(cfg, p, x)
        with mesh:
            y_ep, aux_e = M.moe_forward_ep(
                cfg, p, x, mesh, ('data',), 'model',
                capacity_factor=float(cfg.moe.n_experts))  # no drops
        err = float(jnp.abs(y_dense - y_ep).max())
        print('RESULT', json.dumps({'err': err,
                                    'aux_d': float(aux_d),
                                    'aux_e': float(aux_e)}))
    """))
    assert res["err"] < 2e-4, res
    # EP aux is the pmean of per-shard load-balance losses — statistically
    # close to, but not identical with, the global-batch value
    assert abs(res["aux_d"] - res["aux_e"]) < 0.1, res


def test_int8_dp_sync(run_distributed):
    res = run_distributed(PREAMBLE + textwrap.dedent("""
        from repro.parallel.compression import dp_sync_int8
        g = {'w': jnp.linspace(-1.0, 1.0, 64).reshape(8, 8)}
        synced = dp_sync_int8(g, mesh, ('data',))
        # all shards contributed the same replicated values -> mean == g
        err = float(jnp.abs(synced['w'] - g['w']).max())
        print('RESULT', json.dumps({'err': err}))
    """))
    assert res["err"] < 2e-2, res


def test_sharding_divisibility_on_real_mesh(run_distributed):
    res = run_distributed(PREAMBLE + textwrap.dedent("""
        from repro.parallel import sharding as S
        from repro.models import layers as L
        rules = S.make_rules(mesh)
        # heads=9 does not divide model=2 evenly? 9 % 2 = 1 -> replicated
        s1 = S.spec_for((9, 16), (L.HEADS, None), rules, mesh)
        # d_ff=8 divides model=2 -> sharded
        s2 = S.spec_for((8, 16), (L.D_FF, None), rules, mesh)
        print('RESULT', json.dumps({'s1': list(map(str, tuple(s1))),
                                    's2': list(map(str, tuple(s2)))}))
    """))
    assert res["s1"][:1] in ([], ["None"]) or res["s1"] == []
    assert res["s2"][0] == "model"


def test_elastic_restore_across_meshes(run_distributed, tmp_path):
    res = run_distributed(PREAMBLE + textwrap.dedent(f"""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        tree = {{'w': jnp.arange(64.0).reshape(8, 8)}}
        mgr = CheckpointManager({str(tmp_path)!r})
        # save under a 4x2 mesh sharding
        sh1 = NamedSharding(mesh, P('data', 'model'))
        tree_sharded = jax.device_put(tree['w'], sh1)
        mgr.save(1, {{'w': tree_sharded}})
        # restore under a DIFFERENT mesh (2x4)
        mesh2 = device_mesh((2, 4), ('data', 'model'))
        sh2 = NamedSharding(mesh2, P('model', 'data'))
        got = mgr.restore(1, {{'w': jnp.zeros((8, 8))}}, {{'w': sh2}})
        ok = bool(jnp.array_equal(got['w'], tree['w']))
        nshards = len(got['w'].sharding.device_set)
        print('RESULT', json.dumps({{'ok': ok, 'nshards': nshards}}))
    """))
    assert res["ok"] and res["nshards"] == 8, res
