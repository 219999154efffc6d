"""Continuous-batching serving: the bitwise golden-parity contract.

The pinned claim (ISSUE/ROADMAP): a request's generated tokens are
*bitwise identical* whether it

  * ran solo (`max_batch=1`),
  * rode a static drained batch (`admission="drain"`), or
  * rode a continuous batch where another request joined and a third
    finished mid-generation,

and all three match the plain dense-cache reference (`T.prefill` + scalar
`T.decode_step` loop) under the same `EngineConfig(row_align=8)`. Plus the
serving semantics around the pool: cancellation frees blocks immediately,
deadlines expire queued and running requests, preemption under a tiny pool
still completes every request, and the stats/plan surfaces (pool
occupancy, fill ratio, paged-gather costing) are populated.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from repro import engine as E
from repro.models import transformer as T
from repro.serve import engine as SE
from repro.serve.scheduler import ContinuousScheduler, GenTicket

jax.config.update("jax_platform_name", "cpu")

MAX_LEN = 32
# Mixed workload: different prompt lengths AND step counts, so requests
# join and leave the decode batch at different steps.
WORK = [((3, 1, 4, 1, 5), 6), ((9, 2, 6), 12), ((2, 7, 1, 8), 3),
        ((1, 1, 2, 3, 5, 8), 8)]


@pytest.fixture(scope="module")
def dense_ref(smollm_reduced, smollm_params, serving_config):
    """Reference greedy generation on the dense cache path, memoized."""
    cache = {}

    def ref(prompt, steps):
        key = (tuple(prompt), steps)
        if key in cache:
            return cache[key]
        with E.using_config(serving_config):
            toks = jnp.asarray([list(prompt)], jnp.int32)
            lg, st = T.prefill(smollm_reduced, smollm_params,
                               {"tokens": toks}, MAX_LEN)
            out = [int(jnp.argmax(lg, -1)[0])]
            for i in range(steps - 1):
                lg, st = T.decode_step(
                    smollm_reduced, smollm_params, st,
                    jnp.asarray([[out[-1]]], jnp.int32),
                    jnp.int32(len(prompt) + i))
                out.append(int(jnp.argmax(lg[:, -1], -1)[0]))
        cache[key] = out
        return out

    return ref


def make_sched(cfg, params, **kw):
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    return ContinuousScheduler(cfg, params, **kw)


class TestGoldenParity:
    @pytest.mark.parametrize("mode,max_batch", [
        ("solo", 1), ("drain", 4), ("continuous", 4)])
    def test_tokens_bitwise_equal(self, smollm_reduced, smollm_params,
                                  dense_ref, mode, max_batch):
        s = make_sched(smollm_reduced, smollm_params, max_batch=max_batch,
                       admission="drain" if mode == "drain" else "continuous")
        tickets = [s.submit(list(p), n) for p, n in WORK]
        s.run()
        for t, (p, n) in zip(tickets, WORK):
            assert t.status == "done"
            assert t.tokens == dense_ref(p, n), (mode, t.rid)
            assert t.preemptions == 0

    def test_mid_generation_join_and_finish(self, smollm_reduced,
                                            smollm_params, dense_ref):
        """The acceptance case: request B finishes while A decodes, then C
        joins the running batch mid-generation — A's tokens still match
        its solo run bitwise."""
        s = make_sched(smollm_reduced, smollm_params)
        a = s.submit([3, 1, 4, 1, 5], 10)
        b = s.submit([2, 7, 1], 3)
        for _ in range(4):
            s.step()
        assert b.status == "done" and a.status == "running"
        c = s.submit([9, 2, 6, 4], 6)       # joins while A is mid-flight
        s.run()
        assert a.tokens == dense_ref((3, 1, 4, 1, 5), 10)
        assert b.tokens == dense_ref((2, 7, 1), 3)
        assert c.tokens == dense_ref((9, 2, 6, 4), 6)
        # C really joined a non-empty batch
        hist = s.stats()["admitted_per_step"]
        assert hist[0] == 2 and 1 in hist[1:]

    def test_single_step_request(self, smollm_reduced, smollm_params,
                                 dense_ref):
        """steps=1 finishes at prefill and never occupies a decode row."""
        s = make_sched(smollm_reduced, smollm_params)
        t = s.submit([5, 4, 3], 1)
        done = s.step()
        assert done == [t] and t.status == "done"
        assert t.tokens == dense_ref((5, 4, 3), 1)
        assert s.stats()["steps"] == 0
        assert s.pool.snapshot()["live_requests"] == 0


class TestLifecycle:
    def test_cancel_releases_blocks_immediately(self, smollm_reduced,
                                                smollm_params, dense_ref):
        s = make_sched(smollm_reduced, smollm_params)
        a = s.submit([3, 1, 4, 1, 5], 10)
        b = s.submit([2, 7, 1], 10)
        s.step()
        live = s.pool.snapshot()["live_blocks"]
        assert s.cancel(a) and a.status == "cancelled"
        assert s.pool.snapshot()["live_blocks"] < live
        assert not s.cancel(a)              # idempotent after the fact
        s.run()                             # survivor unaffected, bitwise
        assert b.tokens == dense_ref((2, 7, 1), 10)
        assert s.stats()["cancelled"] == 1

    def test_cancel_queued(self, smollm_reduced, smollm_params):
        s = make_sched(smollm_reduced, smollm_params)
        t = s.submit([1, 2, 3], 4)
        assert s.cancel(t) and t.status == "cancelled"
        assert s.pending() == 0
        assert s.run() == []

    def test_deadline_expires_queued_and_running(self, smollm_reduced,
                                                 smollm_params):
        s = make_sched(smollm_reduced, smollm_params)
        a = s.submit([3, 1, 4], 10, timeout_s=0.0)
        time.sleep(0.01)
        s.step()
        assert a.status == "expired" and not a.tokens
        b = s.submit([2, 7, 1], 25, timeout_s=0.2)
        s.step()
        assert b.status == "running"
        time.sleep(0.25)
        s.step()
        assert b.status == "expired"
        assert s.pool.snapshot()["live_requests"] == 0
        assert s.stats()["expired"] == 2

    def test_preemption_under_tiny_pool(self, smollm_reduced,
                                        smollm_params):
        """4 usable blocks, two requests needing 3 + 2: the youngest gets
        evicted when the pool runs dry, re-prefills, and both finish."""
        s = ContinuousScheduler(smollm_reduced, smollm_params, max_len=24,
                                num_blocks=5, block_size=8, max_batch=2)
        a = s.submit([1, 2, 3, 4, 5, 6, 7], 16)
        b = s.submit([4, 5, 6], 12)
        s.run()
        assert a.status == "done" and len(a.tokens) == 16
        assert b.status == "done" and len(b.tokens) == 12
        st = s.stats()
        assert st["evicted"] >= 1
        assert a.preemptions + b.preemptions == st["evicted"]
        assert st["pool"]["free_low_water"] == 0
        assert st["pool"]["live_blocks"] == 0

    def test_submit_validation(self, smollm_reduced, smollm_params):
        s = make_sched(smollm_reduced, smollm_params)
        with pytest.raises(ValueError, match="exceeds"):
            s.submit([1] * 30, 10)          # 40 > max_len
        with pytest.raises(ValueError, match="empty"):
            s.submit([], 4)
        tiny = ContinuousScheduler(smollm_reduced, smollm_params,
                                   max_len=32, num_blocks=3, block_size=8,
                                   max_batch=2)
        with pytest.raises(ValueError, match="blocks"):
            tiny.submit([1] * 20, 10)       # needs 4 blocks, pool has 2

    def test_live_cost_budget_limits_admission(self, smollm_reduced,
                                               smollm_params):
        s = make_sched(smollm_reduced, smollm_params)
        # room for exactly one live request under the analytic step cost
        s.max_live_cost_s = 1.5 * s.unit_step_s
        a = s.submit([1, 2, 3], 4)
        b = s.submit([4, 5, 6], 4)
        s.step()
        assert a.status == "running" and b.status == "queued"
        s.run()
        assert a.status == "done" and b.status == "done"


class TestStatsAndPlan:
    def test_stats_surfaces(self, smollm_reduced, smollm_params):
        s = make_sched(smollm_reduced, smollm_params)
        for p, n in WORK:
            s.submit(list(p), n)
        s.run()
        st = s.stats()
        assert st["tokens_out"] == sum(n for _, n in WORK) - len(WORK)
        assert 0.0 < st["decode_fill"] <= 1.0
        assert st["admitted"] == len(WORK)
        assert len(st["admitted_per_step"]) >= st["steps"]
        assert sum(st["admitted_per_step"]) == st["admitted"]
        assert sum(st["evicted_per_step"]) == st["evicted"] == 0
        pool = st["pool"]
        assert pool["live_blocks"] == 0 and pool["occupancy"] == 0.0
        assert pool["free_low_water"] < pool["num_blocks"] - 1
        assert st["unit_step_s"] > 0
        assert 1 in st["compiled_decode_buckets"] or \
            st["compiled_decode_buckets"]

    def test_continuous_fills_more_decode_rows_than_drain(
            self, smollm_reduced, smollm_params):
        """Short and long requests, queue deeper than the batch: drain
        admission leaves the rows of finished short requests idle until the
        batch empties, continuous admission refills them the same step."""
        work = [([1 + (i * 7 + j) % 199 for j in range(4 if i % 2 else 8)],
                 2 if i % 2 else 14) for i in range(8)]
        stats = {}
        for admission in ("drain", "continuous"):
            s = make_sched(smollm_reduced, smollm_params, buckets=(4,),
                           admission=admission)
            tickets = [s.submit(p, n) for p, n in work]
            s.run()
            assert all(t.status == "done" for t in tickets)
            stats[admission] = s.stats()
        assert (stats["continuous"]["decode_fill"]
                > stats["drain"]["decode_fill"])
        assert stats["continuous"]["steps"] < stats["drain"]["steps"]

    def test_paged_decode_plan_prices_gather(self, smollm_reduced,
                                             serving_config):
        """The paged decode program's NetworkPlan carries the gather
        reconstruction as first-class planned ops."""
        from repro.serve.kv_pool import PagedLayout
        layout = PagedLayout.build(smollm_reduced, max_len=MAX_LEN,
                                   block_size=8, num_blocks=16)
        prog = SE.paged_decode_program(smollm_reduced, layout, 2)
        plan = E.plan_network(prog, serving_config)
        assert plan.gather_plans
        assert plan.gather_cycles > 0
        assert plan.gather_latency_s > 0
        assert plan.total_latency_s > plan.fc_latency_s

    def test_gen_ticket_latency(self):
        t = GenTicket(rid=0, prompt=(1,), steps=1, submit_s=10.0)
        assert t.latency_s != t.latency_s   # NaN while pending
        t.status = "done"
        t.done_s = 10.5
        assert t.latency_s == pytest.approx(0.5)
