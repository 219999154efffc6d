"""Property tests for `BlockAllocator` invariants (hypothesis; skipped
when the dependency is absent, same policy as the other property suites):

  * conservation — free + live == num_blocks - 1 under any interleaving
    of register / ensure / release (block 0 reserved forever);
  * disjointness — live requests never share a block, live and free sets
    never overlap, block 0 is never handed out;
  * no double-free — releasing twice raises `KeyError`;
  * clean exhaustion — a failed (exhausted) alloc changes nothing.
"""
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.serve.kv_pool import BlockAllocator, PoolExhausted  # noqa: E402

SETTINGS = settings(max_examples=60, deadline=None)

# A random op trace: (kind, rid, pos) triples driven against a small pool.
ops_strategy = st.lists(
    st.tuples(st.sampled_from(["register", "ensure", "release"]),
              st.integers(0, 5),        # rid
              st.integers(0, 31)),      # pos (block_size 4 -> idx 0..7)
    min_size=1, max_size=60)

BLOCK_SIZE = 4


def _drive(alloc, trace):
    """Apply a raw op trace to live ids only (re-registering a live id is
    a documented `ValueError`), swallowing `PoolExhausted`."""
    cap = alloc.blocks_per_req * BLOCK_SIZE - 1
    for kind, rid, pos in trace:
        try:
            if kind == "register":
                if rid not in alloc.tables:
                    alloc.register(rid)
            elif kind == "ensure":
                if rid in alloc.tables:
                    alloc.ensure(rid, min(pos, cap), BLOCK_SIZE)
            else:
                if rid in alloc.tables:
                    alloc.release(rid)
        except PoolExhausted:
            pass


class TestAllocatorProperties:
    @SETTINGS
    @given(trace=ops_strategy, num_blocks=st.integers(2, 12))
    def test_conservation(self, trace, num_blocks):
        alloc = BlockAllocator(num_blocks, blocks_per_req=8)
        _drive(alloc, trace)
        assert alloc.free_blocks + alloc.live_blocks == num_blocks - 1
        assert 0 <= alloc.low_water <= num_blocks - 1
        assert alloc.low_water <= alloc.free_blocks

    @SETTINGS
    @given(trace=ops_strategy, num_blocks=st.integers(2, 12))
    def test_disjoint_tables_and_reserved_zero(self, trace, num_blocks):
        alloc = BlockAllocator(num_blocks, blocks_per_req=8)
        _drive(alloc, trace)
        live = [b for t in alloc.tables.values() for b in t if b]
        assert 0 not in live                      # block 0 never allocated
        assert len(live) == len(set(live))        # no block shared
        assert not set(live) & set(alloc._free)   # live disjoint from free

    @SETTINGS
    @given(trace=ops_strategy, num_blocks=st.integers(2, 12))
    def test_double_release_raises(self, trace, num_blocks):
        alloc = BlockAllocator(num_blocks, blocks_per_req=8)
        _drive(alloc, trace)
        rid = 99
        alloc.register(rid)
        alloc.release(rid)
        with pytest.raises(KeyError):
            alloc.release(rid)
        assert alloc.free_blocks + alloc.live_blocks == num_blocks - 1

    @SETTINGS
    @given(trace=ops_strategy, num_blocks=st.integers(2, 8))
    def test_clean_exhaustion(self, trace, num_blocks):
        alloc = BlockAllocator(num_blocks, blocks_per_req=num_blocks + 4)
        _drive(alloc, trace)
        rid = 99
        alloc.register(rid)
        # drain the free-list, then one more: must raise and change nothing
        idx = 0
        while alloc.free_blocks:
            alloc.alloc_block(rid, idx)
            idx += 1
        before = (alloc.free_blocks, list(alloc.tables[rid]))
        with pytest.raises(PoolExhausted):
            alloc.alloc_block(rid, idx)
        assert (alloc.free_blocks, list(alloc.tables[rid])) == before
        assert alloc.free_blocks + alloc.live_blocks == num_blocks - 1
