"""Serving: jitted prefill and decode steps with sharded, donated KV/SSM
state. `build_serve_step` is what the decode_32k / long_500k dry-run cells
lower (one new token against a seq_len cache), `build_prefill` is the
prefill_32k cell (and the encoder forward for encoder-only archs).

Two multi-device paths coexist here:

  * `build_serve_step` / `build_prefill` shard *params and state* via
    `parallel.sharding` rules and let XLA's SPMD partitioner place the
    compute (the production dry-run path);
  * the `*_program` builders below stay mesh-free — pass a mesh plus
    `EngineConfig(parallel=ParallelConfig(...))` to `engine.compile` (or
    `mesh=` on the serving schedulers) and the *plan* decides per layer
    whether a GEMM replicates, shards its contraction (all-reduce) or its
    output features (all-gather), priced by the same analytic cost model
    that picks pallas-vs-xla (engine/parallel.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import engine as E
from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.models import layers as L
from repro.parallel import sharding as S


def _ns(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def decode_state_shapes(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(
        lambda: T.init_decode_state(cfg, batch, max_len))


def _engine_ctx(engine_config: Optional[E.EngineConfig]):
    """Ambient-engine context for a traced step: `engine_config` for its
    extent, or the ambient config when None."""
    if engine_config is not None:
        return E.using_config(engine_config)
    return contextlib.nullcontext()


def build_serve_step(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int,
                     rules: Optional[S.ShardingRules] = None,
                     engine_config: Optional[E.EngineConfig] = None):
    """Returns (jitted step, contract). step(params, state, tokens, pos) ->
    (logits, state'); state donated. `engine_config` selects the
    multi-mode-engine configuration (backend, interpret, accum, policy) for
    every dense op traced into the step."""
    rules = rules or S.make_rules(mesh)
    defs = T.model_defs(cfg)
    param_specs = S.tree_specs(defs, rules, mesh)
    st_shapes = decode_state_shapes(cfg, batch, max_len)
    st_specs = S.state_specs(cfg, st_shapes, rules, mesh)
    shard_fn = S.make_shard_fn(rules, mesh)
    ctx = T.FwdContext(mesh=mesh, dp_axes=rules.dp_axes,
                       tp_axis=rules.tp_axis, remat=False, shard_fn=shard_fn)

    def step(params, state, tokens, pos):
        with _engine_ctx(engine_config):
            logits, state2 = T.decode_step(cfg, params, state, tokens, pos,
                                           ctx)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return logits, tok, state2

    tok_spec = S.spec_for((batch, 1), (L.BATCH, None), rules, mesh)
    jitted = jax.jit(
        step,
        in_shardings=(_ns(mesh, param_specs), _ns(mesh, st_specs),
                      NamedSharding(mesh, tok_spec), NamedSharding(mesh, P())),
        out_shardings=(None, None, _ns(mesh, st_specs)),
        donate_argnums=(1,))
    contract = {"param_specs": param_specs, "state_specs": st_specs,
                "state_shapes": st_shapes, "rules": rules, "ctx": ctx}
    return jitted, contract


def build_prefill(cfg: ModelConfig, mesh: Mesh, batch: int, seq: int,
                  max_len: int, rules: Optional[S.ShardingRules] = None,
                  engine_config: Optional[E.EngineConfig] = None):
    """Prefill (or encoder forward): returns (jitted fn, contract).
    `engine_config` as in `build_serve_step`."""
    rules = rules or S.make_rules(mesh)
    defs = T.model_defs(cfg)
    param_specs = S.tree_specs(defs, rules, mesh)
    shard_fn = S.make_shard_fn(rules, mesh)
    ctx = T.FwdContext(mesh=mesh, dp_axes=rules.dp_axes,
                       tp_axis=rules.tp_axis, remat=False, shard_fn=shard_fn)

    if cfg.is_encoder:
        def fn(params, batch_in):
            with _engine_ctx(engine_config):
                hidden, _ = T.forward(cfg, params, batch_in, ctx)
                return T.logits_fn(cfg, params, hidden)
    else:
        def fn(params, batch_in):
            with _engine_ctx(engine_config):
                return T.prefill(cfg, params, batch_in, max_len, ctx)

    def batch_spec(x):
        axes = ((L.BATCH, L.SEQ, None) if x.ndim == 3
                else (L.BATCH,) + (None,) * (x.ndim - 1))
        return S.spec_for(x.shape, axes, rules, mesh)

    jitted_holder = {}

    def jit_for(batch_shapes):
        bspecs = jax.tree_util.tree_map(batch_spec, batch_shapes)
        if cfg.is_encoder:
            out_sh = None
        else:
            _, state_sh = jax.eval_shape(fn, T.param_shapes(cfg),
                                         batch_shapes)
            out_sh = (None, _ns(mesh, S.state_specs(cfg, state_sh, rules,
                                                    mesh)))
        return jax.jit(fn, in_shardings=(_ns(mesh, param_specs),
                                         _ns(mesh, bspecs)),
                       out_shardings=out_sh)

    contract = {"param_specs": param_specs, "rules": rules, "ctx": ctx,
                "jit_for": jit_for}
    return fn, contract


def prefill_program(cfg: ModelConfig, batch: int, seq: int,
                    max_len: Optional[int] = None,
                    logits_only: bool = False) -> "E.Program":
    """The serving prefill forward (or encoder forward) as an
    `engine.Program` — the transformer/SSM counterpart of
    `models.cnn.program`. Captured by shape alone via
    `engine.trace_program`, so `engine.compile(prefill_program(...),
    cfg).plan` prices one prefill without touching any weights.

    `logits_only=True` drops the decode-state output (a scoring /
    classification service: tokens in, last-token logits out) — the
    lightweight request shape the serve scheduler's smoke benchmark packs
    into batches. Encoder archs are always logits-only.
    """
    max_len = seq if max_len is None else max_len
    params_sh = T.param_shapes(cfg)

    def batch_sh(b):
        return {"tokens": jax.ShapeDtypeStruct((b, seq), jnp.int32)}

    if cfg.is_encoder:
        def fn(params, batch_in):
            hidden, _ = T.forward(cfg, params, batch_in)
            return T.logits_fn(cfg, params, hidden)
    elif logits_only:
        def fn(params, batch_in):
            return T.prefill(cfg, params, batch_in, max_len)[0]
    else:
        def fn(params, batch_in):
            return T.prefill(cfg, params, batch_in, max_len)

    axes = E.infer_batch_axes((params_sh, batch_sh(batch)),
                              (params_sh, batch_sh(batch + 1)))
    # the variants return different outputs: keep their identities distinct
    # (Program equality/hash is (name, ops); fn is excluded)
    suffix = "-logits" if logits_only and not cfg.is_encoder else ""
    return E.trace_program(fn, params_sh, batch_sh(batch),
                           name=f"{cfg.name}-prefill{seq}{suffix}",
                           batch_size=batch, batch_axes=axes)


def decode_program(cfg: ModelConfig, batch: int,
                   max_len: int) -> "E.Program":
    """One greedy decode step (one token against a `max_len` cache) as an
    `engine.Program`."""
    params_sh = T.param_shapes(cfg)
    pos_sh = jax.ShapeDtypeStruct((), jnp.int32)

    def avals(b):
        return (params_sh, decode_state_shapes(cfg, b, max_len),
                jax.ShapeDtypeStruct((b, 1), jnp.int32), pos_sh)

    def fn(params, state, tok, pos):
        logits, _ = T.decode_step(cfg, params, state, tok, pos)
        return logits

    axes = E.infer_batch_axes(avals(batch), avals(batch + 1))
    return E.trace_program(fn, *avals(batch),
                           name=f"{cfg.name}-decode{max_len}",
                           batch_size=batch, batch_axes=axes)


def paged_decode_program(cfg: ModelConfig, layout, batch: int,
                         guard: bool = False) -> "E.Program":
    """One continuous-batching decode step over a paged KV pool, as an
    `engine.Program` — the block-pool replacement for the dense
    `decode_program`/`decode_state_shapes` serving path.

    Signature of the traced fn:
        (params, pool_arrays, tables (B, blocks_per_req) i32,
         slots (B,) i32, tokens (B, 1) i32, pos (B,) i32)
        -> (next_token (B,) i32, pool_arrays')

    Each step gathers every row's dense state view from its blocks
    (`engine.paged_gather` — recorded ops, so the program's `NetworkPlan`
    prices the reconstruction), runs the unchanged `T.decode_step` at
    per-row positions, and scatters back only the slot each row wrote.
    `layout` is a `serve.kv_pool.PagedLayout`. Compile with
    `engine.compile(prog, cfg, donate_argnums=(1,))` so the pool arrays
    are donated through every step instead of copied.

    `guard=True` builds the numerics-guard variant the fault-injecting
    `ContinuousScheduler` compiles instead — an extra trailing argument
    `poison (B,) f32` (0.0 clean, NaN to poison a row's logits) and an
    extra output `ok (B,) bool` (all-finite verdict per row's last-token
    logits). The poison lands on the *logits only*, selected via
    `jnp.where` after `T.decode_step` ran — so non-poisoned rows keep the
    clean program's exact argmax inputs bitwise (where-select copies
    them, including signed zeros), and the state written back to the pool
    is always the finite state the clean math produced (the pool's
    NEG_INF-masking parity contract requires finite block contents —
    injecting into the cache would break *other* requests). The guard is
    runtime data, never trace-time branching: with no injector the
    scheduler compiles the unguarded program, byte-identical to PR 8's.
    """
    params_sh = T.param_shapes(cfg)
    npb = layout.blocks_per_req

    def fn(params, arrays, tables, slots, tokens, pos, poison=None):
        state = layout.gather(arrays, tables, slots)
        logits, new_state = T.decode_step(cfg, params, state, tokens, pos)
        last = logits[:, -1]
        out = layout.scatter_step(arrays, new_state, tables, slots, pos)
        if poison is None:
            tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return tok, out
        last = jnp.where(jnp.isnan(poison)[:, None],
                         jnp.float32(float("nan")), last)
        ok = jnp.all(jnp.isfinite(last), axis=-1)
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return tok, ok, out

    avals = (params_sh, layout.array_avals(),
             jax.ShapeDtypeStruct((batch, npb), jnp.int32),
             jax.ShapeDtypeStruct((batch,), jnp.int32),
             jax.ShapeDtypeStruct((batch, 1), jnp.int32),
             jax.ShapeDtypeStruct((batch,), jnp.int32))
    if guard:
        avals = avals + (jax.ShapeDtypeStruct((batch,), jnp.float32),)
    suffix = "-guard" if guard else ""
    return E.trace_program(
        fn, *avals,
        name=f"{cfg.name}-paged-decode{layout.max_len}"
             f"x{layout.block_size}b{batch}{suffix}")


def prefill_ingest_program(cfg: ModelConfig, layout, seq: int,
                           guard: bool = False) -> "E.Program":
    """Prefill one request at its exact prompt length and ingest the
    resulting dense state into the paged pool (the continuous scheduler's
    admission path; compiled per distinct prompt length so the GEMM M
    dimension — and with it bitwise parity against a solo prefill — never
    depends on batchmates).

    Signature: (params, pool_arrays, table_row (blocks_per_req,) i32,
    slot () i32, tokens (1, seq) i32) -> (first_token (1,) i32, arrays').

    `guard=True` is the numerics-guard variant (see
    `paged_decode_program`): a trailing `poison () f32` argument and an
    `ok () bool` output — NaN poison hits the prefill logits only, never
    the ingested cache state, so a quarantined admission leaves the pool
    contents finite.
    """
    params_sh = T.param_shapes(cfg)
    n_blocks = -(-seq // layout.block_size)

    def fn(params, arrays, table_row, slot, tokens, poison=None):
        logits, state = T.prefill(cfg, params, {"tokens": tokens},
                                  layout.max_len)
        out = layout.scatter_prefill(arrays, state, table_row, slot,
                                     n_blocks)
        if poison is None:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return tok, out
        logits = jnp.where(jnp.isnan(poison), jnp.float32(float("nan")),
                           logits)
        ok = jnp.all(jnp.isfinite(logits))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, ok, out

    avals = (params_sh, layout.array_avals(),
             jax.ShapeDtypeStruct((layout.blocks_per_req,), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32),
             jax.ShapeDtypeStruct((1, seq), jnp.int32))
    if guard:
        avals = avals + (jax.ShapeDtypeStruct((), jnp.float32),)
    suffix = "-guard" if guard else ""
    return E.trace_program(
        fn, *avals, name=f"{cfg.name}-prefill-ingest{seq}{suffix}")


def greedy_generate(cfg: ModelConfig, params, batch_in: Dict, steps: int,
                    max_len: int, ledger: Optional[E.Ledger] = None):
    """Single-host convenience loop (examples / tests): prefill then greedy
    decode `steps` tokens. Pass an `engine.Ledger` to collect the
    MMIE-projected cost of one prefill + one decode trace."""
    track = (E.tracking(ledger) if ledger is not None
             else contextlib.nullcontext())
    with track:
        logits, state = T.prefill(cfg, params, batch_in, max_len)
        pos0 = batch_in["tokens"].shape[1]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out = [tok]
        step_fn = jax.jit(partial(T.decode_step, cfg),
                          donate_argnums=(1,), static_argnums=())
        for i in range(steps - 1):
            logits_i, state = step_fn(params, state, tok, jnp.int32(pos0 + i))
            tok = jnp.argmax(logits_i[:, -1],
                             axis=-1).astype(jnp.int32)[:, None]
            out.append(tok)
    return jnp.concatenate(out, axis=1)
