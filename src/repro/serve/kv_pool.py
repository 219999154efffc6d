"""Fixed-size paged KV block pool for continuous-batching decode serving.

The PR-3 scheduler packs *fixed* shape-bucketed batches and drains them:
every request in a batch owns a dense `(max_len, ...)` cache slice for its
whole lifetime, and the batch dimension empties out as requests finish —
stranded capacity, the software analogue of the fixed-dataflow utilization
failure the paper attacks. This module replaces the dense per-request
buffers with the flashinfer/vLLM page-table layout:

  * one preallocated pool array per KV leaf, shaped
    `(num_blocks, block_size, *feature)` — e.g. the grouped attention
    cache `(g, B, max_len, kv_heads, head_dim)` becomes
    `(num_blocks, block_size, g, kv_heads, head_dim)`;
  * a host-side free-list (`BlockAllocator`) handing blocks to requests on
    demand as their position advances, and reclaiming them the step a
    request finishes, is cancelled, or is preempted;
  * per-request *block tables* mapping cache position `p` to pool block
    `table[p // block_size]`, offset `p % block_size`.

Decode-state leaves without a `max_len` axis (SSM recurrent states,
cross-attention caches, sliding-window ring buffers shorter than
`max_len`) are not paged: each live request owns one row of a
`(max_slots, *feature)` slot store — bounded memory by construction.

Block 0 and slot 0 are reserved as dummies: unallocated table entries and
scheduler pad rows point at them, so a gather over a partially-allocated
table is always in-bounds. Their contents are garbage *by contract* and
are exactly masked downstream (see the parity note below).

Bitwise-parity mechanism
------------------------
`PagedLayout.gather` reconstructs each request's dense decode state from
its blocks (`engine.paged_gather` — an exact copy); the *unchanged* dense
decode math runs on it; `PagedLayout.scatter_step` writes back only the
one slot each row touched. Positions `<= pos` hold bit-identical values to
the dense path; positions beyond `pos` hold recycled-block garbage where
the dense path holds zeros — but the decode mask sends both to `NEG_INF`
scores, whose softmax weight is exactly `0.0` in fp32, and `0.0 * finite`
contributes exactly `±0.0` to the weighted sum. Hence a request's tokens
are bitwise identical whether its cache lived in a dense buffer or in
scattered blocks. (The one hazard would be `inf`/`NaN` stale values —
impossible here because every value ever written to the pool is a finite
cache entry and the pool initializes to zeros.)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro import engine as E
from repro.configs.base import ModelConfig
from repro.models import transformer as T


class PoolExhausted(RuntimeError):
    """Block allocation failed: the free-list is empty. The failed alloc
    has no side effects — already-held blocks stay recorded in their
    tables, so the caller can preempt/queue and retry without repair."""


# ---------------------------------------------------------------------------
# Host-side allocator (free-list + block tables)
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Free-list of pool blocks plus per-request block tables.

    Pure host-side bookkeeping (no jax arrays), so its invariants are
    directly property-testable (tests/test_kv_pool.py):

      * conservation — `free_blocks + live_blocks == num_blocks - 1`
        always (block 0 is reserved and never allocated);
      * disjointness — live requests' tables never share a block;
      * no double-free — releasing a request twice raises `KeyError`;
      * clean exhaustion — `PoolExhausted` leaves all state consistent.
    """

    def __init__(self, num_blocks: int, blocks_per_req: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved dummy), "
                f"got {num_blocks}")
        if blocks_per_req < 1:
            raise ValueError(
                f"blocks_per_req must be >= 1, got {blocks_per_req}")
        self.num_blocks = int(num_blocks)
        self.blocks_per_req = int(blocks_per_req)
        # LIFO free-list: recently-freed (cache-warm) blocks are reused first
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self.tables: Dict[int, List[int]] = {}      # rid -> [block or 0] * bpr
        self.low_water = num_blocks - 1             # min free count ever seen

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return sum(sum(1 for b in t if b) for t in self.tables.values())

    def register(self, rid: int) -> None:
        """Open an (empty) block table for request `rid`. Raises
        `ValueError` if `rid` is already registered (live)."""
        if rid in self.tables:
            raise ValueError(f"request {rid} already registered")
        self.tables[rid] = [0] * self.blocks_per_req

    def alloc_block(self, rid: int, idx: int) -> int:
        """Allocate table slot `idx` for `rid` (idempotent if already
        allocated); raises `PoolExhausted` when the free-list is empty."""
        table = self.tables[rid]
        if table[idx]:
            return table[idx]
        if not self._free:
            usable = self.num_blocks - 1
            raise PoolExhausted(
                f"no free blocks for request {rid} (need table slot {idx}): "
                f"{self.live_blocks}/{usable} blocks live "
                f"({self.live_blocks / usable:.0%} occupancy) across "
                f"{len(self.tables)} requests, free-block low-water "
                f"{self.low_water} — evict or wait")
        block = self._free.pop()
        table[idx] = block
        self.low_water = min(self.low_water, len(self._free))
        return block

    def ensure(self, rid: int, pos: int, block_size: int) -> List[int]:
        """Allocate every block covering cache positions [0, pos]; returns
        the newly-allocated block ids (usually 0 or 1 of them)."""
        new = []
        table = self.tables[rid]
        for idx in range(pos // block_size + 1):
            if not table[idx]:
                new.append(self.alloc_block(rid, idx))
        return new

    def release(self, rid: int) -> List[int]:
        """Return `rid`'s blocks to the free-list; raises `KeyError` on a
        double release (the table is gone after the first)."""
        table = self.tables.pop(rid)
        blocks = [b for b in table if b]
        self._free.extend(blocks)
        return blocks


# ---------------------------------------------------------------------------
# Layout: classify decode-state leaves, build pool arrays, gather/scatter
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _LeafSpec:
    """Axis roles of one decode-state leaf (from shape diffs alone)."""

    batch_ax: int
    len_ax: int         # -1: not paged (whole-leaf slot store)
    ndim: int

    @property
    def paged(self) -> bool:
        return self.len_ax >= 0

    def rest_axes(self) -> Tuple[int, ...]:
        drop = {self.batch_ax} | ({self.len_ax} if self.paged else set())
        return tuple(i for i in range(self.ndim) if i not in drop)

    def to_bl_perm(self) -> Tuple[int, ...]:
        """Permutation taking the dense leaf to (B, L, *rest) layout."""
        return (self.batch_ax, self.len_ax) + self.rest_axes()

    def from_bl_perm(self) -> Tuple[int, ...]:
        """Inverse: (B, L, *rest) back to the dense leaf's axis order."""
        src = self.to_bl_perm()
        return tuple(src.index(i) for i in range(self.ndim))


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """The model's decode state mapped onto a block pool + slot store.

    Derived from `T.init_decode_state` shapes alone: diffing the state at
    two batch sizes locates each leaf's batch axis; diffing at two
    `max_len` values locates the cache-length axis. A leaf is *paged* iff
    its length axis scales 1:1 with `max_len` (sliding-window ring caches
    clipped below `max_len` stay whole-leaf, their memory already bounded).

    All methods are pure array->array functions, safe under `jax.jit` and
    `engine.trace_program` (the gathers are `engine.paged_gather` ops, so
    a compiled paged decode program prices its reconstruction honestly).
    """

    cfg: ModelConfig = dataclasses.field(compare=False)
    max_len: int
    block_size: int
    num_blocks: int
    max_slots: int
    specs: Any = dataclasses.field(compare=False)       # _LeafSpec tree
    template: Any = dataclasses.field(compare=False)    # batch-1 avals tree

    @property
    def blocks_per_req(self) -> int:
        return self.max_len // self.block_size

    # -- construction -------------------------------------------------------

    @staticmethod
    def build(cfg: ModelConfig, *, max_len: int, block_size: int,
              num_blocks: int, max_slots: int = 64,
              state_dtype=jnp.bfloat16) -> "PagedLayout":
        if max_len % block_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"block_size={block_size}")
        sh = lambda b, ml: jax.eval_shape(  # noqa: E731
            lambda: T.init_decode_state(cfg, b, ml, state_dtype))
        base, b2, l2 = sh(1, max_len), sh(2, max_len), sh(1, 2 * max_len)

        def spec(la, lb, lc):
            bdiff = [i for i, (p, q) in enumerate(zip(la.shape, lb.shape))
                     if p != q]
            if len(bdiff) != 1:
                raise ValueError(
                    f"ambiguous batch axis for leaf {la.shape}: {bdiff}")
            ldiff = [i for i, (p, q) in enumerate(zip(la.shape, lc.shape))
                     if p != q]
            paged = (len(ldiff) == 1
                     and la.shape[ldiff[0]] == max_len
                     and lc.shape[ldiff[0]] == 2 * max_len)
            return _LeafSpec(bdiff[0], ldiff[0] if paged else -1,
                             len(la.shape))

        specs = jax.tree_util.tree_map(spec, base, b2, l2)
        return PagedLayout(cfg=cfg, max_len=max_len, block_size=block_size,
                           num_blocks=num_blocks, max_slots=max_slots,
                           specs=specs, template=base)

    def init_arrays(self) -> Any:
        """Zero-filled pool/slot arrays, one per decode-state leaf."""
        def leaf(aval, sp):
            rest = tuple(aval.shape[i] for i in sp.rest_axes())
            if sp.paged:
                shape = (self.num_blocks, self.block_size) + rest
            else:
                shape = (self.max_slots,) + rest
            return jnp.zeros(shape, aval.dtype)
        return jax.tree_util.tree_map(leaf, self.template, self.specs)

    def array_avals(self) -> Any:
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.eval_shape(self.init_arrays))

    # -- gather / scatter (pure, jittable) ----------------------------------

    def gather(self, arrays: Any, tables: jax.Array,
               slots: jax.Array) -> Any:
        """Dense decode state for a batch: tables (B, blocks_per_req) int32,
        slots (B,) int32 -> the exact `init_decode_state(cfg, B, max_len)`
        pytree, reconstructed leaf-by-leaf from the pool."""
        def leaf(arr, sp):
            if sp.paged:
                g = E.paged_gather(arr, tables)      # (B, L, *rest)
                return jnp.transpose(g, sp.from_bl_perm())
            g = jnp.take(arr, slots, axis=0)         # (B, *rest)
            return jnp.moveaxis(g, 0, sp.batch_ax)
        return jax.tree_util.tree_map(leaf, arrays, self.specs)

    def scatter_step(self, arrays: Any, state: Any, tables: jax.Array,
                     slots: jax.Array, pos: jax.Array) -> Any:
        """Write one decode step back: for paged leaves only the slot each
        row wrote (position `pos[b]`), for slot leaves the whole row.

        Pad rows (table all-zeros, pos 0) land in reserved block 0 / slot
        0 — never read by live requests, so their duplicate writes are
        harmless by construction."""
        bs = self.block_size
        bids = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        offs = pos % bs

        def leaf(arr, new, sp):
            if sp.paged:
                bl = jnp.transpose(new, sp.to_bl_perm())   # (B, L, *rest)
                vals = bl[jnp.arange(bl.shape[0]), pos]    # (B, *rest)
                return arr.at[bids, offs].set(vals.astype(arr.dtype))
            vals = jnp.moveaxis(new, sp.batch_ax, 0)       # (B, *rest)
            return arr.at[slots].set(vals.astype(arr.dtype))
        return jax.tree_util.tree_map(leaf, arrays, state, self.specs)

    def scatter_prefill(self, arrays: Any, state: Any, table_row: jax.Array,
                        slot: jax.Array, n_blocks: int) -> Any:
        """Ingest a batch-1 prefill state: the first `n_blocks` blocks of
        every paged leaf (`n_blocks = ceil(prompt_len / block_size)`,
        static per compiled prompt length) plus the whole slot-store row.
        The tail of the last block carries the dense state's zeros — the
        same values the dense path would read there."""
        npb, bs = self.blocks_per_req, self.block_size

        def leaf(arr, new, sp):
            if sp.paged:
                bl = jnp.transpose(new, sp.to_bl_perm())   # (1, L, *rest)
                vals = bl[0].reshape((npb, bs) + bl.shape[2:])[:n_blocks]
                return arr.at[table_row[:n_blocks]].set(vals.astype(arr.dtype))
            vals = jnp.moveaxis(new, sp.batch_ax, 0)[0]    # (*rest,)
            return arr.at[slot].set(vals.astype(arr.dtype))
        return jax.tree_util.tree_map(leaf, arrays, state, self.specs)


# ---------------------------------------------------------------------------
# KVBlockPool: layout + allocator + live arrays
# ---------------------------------------------------------------------------

class KVBlockPool:
    """The serving-side pool: `PagedLayout` arrays plus the host allocator.

    The scheduler threads `self.arrays` through its jitted (donating) step
    functions and stores the result back; alloc/free/snapshot stay pure
    host bookkeeping and never touch device memory.
    """

    def __init__(self, cfg: ModelConfig, *, max_len: int, block_size: int,
                 num_blocks: int, max_slots: int = 64,
                 state_dtype=jnp.bfloat16):
        self.layout = PagedLayout.build(
            cfg, max_len=max_len, block_size=block_size,
            num_blocks=num_blocks, max_slots=max_slots,
            state_dtype=state_dtype)
        self.allocator = BlockAllocator(num_blocks,
                                        self.layout.blocks_per_req)
        self.arrays = self.layout.init_arrays()
        # slot 0 reserved for pad rows, like block 0
        self._free_slots: List[int] = list(range(max_slots - 1, 0, -1))
        self._slot_of: Dict[int, int] = {}
        # namespace for fault-injection sites (ReplicaSpread sets "r<i>:"
        # so per-request fault schedules stay distinct across replicas)
        self.fault_site = ""

    # -- request lifecycle ---------------------------------------------------

    def register(self, rid: int) -> None:
        if not self._free_slots:
            s = self.snapshot()
            raise PoolExhausted(
                f"no free state slots for request {rid} "
                f"(max_slots={self.layout.max_slots}, "
                f"{s['live_requests']} live requests, block occupancy "
                f"{s['occupancy']:.0%}, free-block low-water "
                f"{s['free_low_water']})")
        self.allocator.register(rid)
        self._slot_of[rid] = self._free_slots.pop()

    def ensure(self, rid: int, pos: int) -> List[int]:
        """Blocks covering positions [0, pos] — allocate the missing ones.

        An installed `serve.faults` injector may fire the "pool" point
        here (an injected exhaustion storm): the raise is indistinguishable
        from a genuine empty free-list — no side effects, already-held
        blocks stay valid — so the schedulers' preempt/retry paths are
        exercised exactly as real pressure would.
        """
        from repro.serve import faults as _faults
        inj = _faults.active()
        if inj is not None and inj.fire("pool",
                                        site=f"{self.fault_site}{rid}"):
            s = self.snapshot()
            raise PoolExhausted(
                f"injected pool-exhaustion storm for request {rid} "
                f"({s['live_blocks']}/{s['num_blocks'] - 1} blocks live, "
                f"{s['live_requests']} live requests)")
        return self.allocator.ensure(rid, pos, self.layout.block_size)

    def release(self, rid: int) -> List[int]:
        blocks = self.allocator.release(rid)
        self._free_slots.append(self._slot_of.pop(rid))
        return blocks

    def scrub_release(self, rid: int) -> List[int]:
        """Zero `rid`'s blocks and state slot, then release them.

        The quarantine path: the parity contract requires pool contents to
        stay finite (NEG_INF masking only yields exactly-0.0 softmax
        weight for finite garbage — see the module docstring), so a
        request failed for non-finite *model state* must not recycle its
        blocks with NaN/Inf still in them. The guarded programs only ever
        poison logits, never the cache, so this scrub is belt-and-braces —
        it also covers organically non-finite state (a model bug), which
        the numerics guard detects the same way.
        """
        table = self.allocator.tables[rid]
        blocks = jnp.asarray([b for b in table if b], jnp.int32)
        slot = self._slot_of[rid]

        def leaf(arr, sp):
            if sp.paged:
                if blocks.size == 0:
                    return arr
                return arr.at[blocks].set(jnp.zeros((), arr.dtype))
            return arr.at[slot].set(jnp.zeros((), arr.dtype))
        self.arrays = jax.tree_util.tree_map(leaf, self.arrays,
                                             self.layout.specs)
        return self.release(rid)

    # -- batch views ---------------------------------------------------------

    def table_rows(self, rids: List[int], bucket: int) -> jax.Array:
        """(bucket, blocks_per_req) int32 block tables; pad rows all-zero
        (the reserved dummy block)."""
        npb = self.layout.blocks_per_req
        rows = [self.allocator.tables[r] for r in rids]
        rows += [[0] * npb] * (bucket - len(rids))
        return jnp.asarray(rows, jnp.int32)

    def slot_rows(self, rids: List[int], bucket: int) -> jax.Array:
        slots = [self._slot_of[r] for r in rids]
        slots += [0] * (bucket - len(rids))
        return jnp.asarray(slots, jnp.int32)

    # -- observability -------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        alc = self.allocator
        usable = alc.num_blocks - 1
        return {
            "num_blocks": alc.num_blocks,
            "block_size": self.layout.block_size,
            "blocks_per_req": self.layout.blocks_per_req,
            "free_blocks": alc.free_blocks,
            "live_blocks": alc.live_blocks,
            "live_requests": len(alc.tables),
            "occupancy": (alc.live_blocks / usable) if usable else 0.0,
            "free_low_water": alc.low_water,
            "free_slots": len(self._free_slots),
        }
