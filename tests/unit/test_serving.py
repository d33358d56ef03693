"""Serving layer: paged KV-cache block manager + continuous batching.

Three tiers:

- pure-Python scheduler/block-manager/bucket tests (no device work —
  the tier-1 smoke coverage);
- ServingEngine integration on a tiny CPU model: the batch-invariance
  proof (greedy tokens under staggered continuous batching bit-match
  per-request ``generate()``), zero steady-state retraces pinned via the
  compile watchdog, and the HLO-byte-identical guard for configs without
  a ``serving`` block (heavy legs);
- the legacy ``generate()`` bucketing satellite (compile-cache keying).
"""

import numpy as np
import pytest

from deepspeed_tpu.serving.blocks import GARBAGE_BLOCK, BlockManager
from deepspeed_tpu.serving.config import (ServingConfig, blocks_for_tokens,
                                          bucket_for, resolve_buckets)
from deepspeed_tpu.serving.request import (FINISHED, QUEUED, RUNNING, SHED,
                                           Request)
from deepspeed_tpu.serving.scheduler import ContinuousBatchingScheduler


# ---------------------------------------------------------------------------
# pure-Python tier (runs in tier-1: no jax device work)
# ---------------------------------------------------------------------------
class TestBuckets:
    def test_powers_of_two_end_at_max_len(self):
        assert resolve_buckets([], 64, floor=8) == [8, 16, 32, 64]
        assert resolve_buckets([], 100, floor=8) == [8, 16, 32, 64, 100]

    def test_explicit_buckets_clipped_and_completed(self):
        assert resolve_buckets([4, 128, 16], 64, floor=8) == [4, 16, 64]

    def test_bucket_for(self):
        buckets = [8, 16, 64]
        assert bucket_for(1, buckets) == 8
        assert bucket_for(8, buckets) == 8
        assert bucket_for(9, buckets) == 16
        assert bucket_for(65, buckets) is None

    def test_blocks_for_tokens(self):
        assert blocks_for_tokens(1, 16) == 1
        assert blocks_for_tokens(16, 16) == 1
        assert blocks_for_tokens(17, 16) == 2

    def test_config_validation(self):
        with pytest.raises(ValueError):
            ServingConfig(shed_policy="drop")
        with pytest.raises(ValueError):
            ServingConfig(block_size=0)
        with pytest.raises(ValueError):
            ServingConfig(prompt_buckets=[0, 8])
        assert ServingConfig(prompt_buckets=[16, 8, 8]).prompt_buckets == \
            [8, 16]
        with pytest.raises(ValueError):
            ServingConfig(prefill_chunk_tokens=-1)
        with pytest.raises(ValueError):
            ServingConfig(kv_cache_dtype="fp8")
        cfg = ServingConfig()
        # the serving fast path defaults OFF: absent keys mean the PR 4
        # programs, byte-identical
        assert not cfg.prefix_cache and cfg.prefill_chunk_tokens == 0
        assert cfg.kv_cache_dtype == ""


class TestBlockManager:
    def test_garbage_block_never_allocated(self):
        mgr = BlockManager(num_blocks=4, block_size=8, max_blocks_per_seq=3)
        t1 = mgr.allocate("a", 24)  # 3 blocks
        assert GARBAGE_BLOCK not in t1[:3]
        assert mgr.num_free == 0

    def test_table_padded_with_garbage(self):
        mgr = BlockManager(num_blocks=8, block_size=8, max_blocks_per_seq=4)
        t = mgr.allocate("a", 9)  # 2 blocks
        assert t.shape == (4,) and t.dtype == np.int32
        assert (t[2:] == GARBAGE_BLOCK).all()
        assert len(set(t[:2])) == 2

    def test_release_and_reuse(self):
        mgr = BlockManager(num_blocks=4, block_size=8, max_blocks_per_seq=3)
        t1 = set(mgr.allocate("a", 24)[:3])
        assert mgr.release("a") == 3
        assert mgr.num_free == 3
        t2 = set(mgr.allocate("b", 24)[:3])
        assert t1 == t2  # freed blocks come back
        assert mgr.release("unknown") == 0  # shed request: no-op

    def test_exhaustion_and_double_alloc_raise(self):
        mgr = BlockManager(num_blocks=3, block_size=8, max_blocks_per_seq=2)
        mgr.allocate("a", 16)
        with pytest.raises(RuntimeError):
            mgr.allocate("b", 8)
        with pytest.raises(ValueError):
            mgr.allocate("a", 8)
        with pytest.raises(ValueError):  # > max_blocks_per_seq
            BlockManager(8, 8, 2).allocate("c", 100)


class TestBlockSharing:
    """Refcounted copy-on-write pool: the prefix-cache substrate."""

    def test_shared_blocks_release_by_refcount(self):
        mgr = BlockManager(num_blocks=8, block_size=8, max_blocks_per_seq=4)
        ta = mgr.allocate("a", 24)                      # 3 blocks
        mgr.allocate("b", 24, shared=list(ta[:2]))      # shares 2, takes 1
        assert mgr.ref_count(ta[0]) == 2 and mgr.is_shared(ta[0])
        assert mgr.num_free == 8 - 1 - 4                # 4 physical blocks
        assert mgr.release("a") == 3
        # shared blocks survive their co-owner; a's private tail frees
        assert mgr.ref_count(ta[0]) == 1
        assert mgr.num_free == 8 - 1 - 3
        assert mgr.release("b") == 3
        assert mgr.num_free == 8 - 1

    def test_cached_blocks_park_evictable_and_recycle_lru(self):
        evicted = []
        mgr = BlockManager(num_blocks=4, block_size=8, max_blocks_per_seq=3)
        mgr.on_evict = evicted.append
        t = mgr.allocate("a", 24)
        for b in t[:3]:
            mgr.mark_cached(b)
        mgr.release("a")
        # cached blocks are reclaimable-but-warm: counted free, not freed
        assert mgr.num_free == 3 and mgr.num_cached == 3
        mgr.touch([t[0]])  # LRU hit: t[0] becomes most recent
        # release parks deepest-first, so eviction recycles the chain
        # tail before its parents: t[2] then t[1]
        t2 = mgr.allocate("b", 16)
        assert evicted == [t[2], t[1]]
        assert set(t2[:2]) == {t[1], t[2]}
        assert mgr.num_cached == 1  # t[0] survived as the warmest

    def test_cow_pins_source_until_done(self):
        mgr = BlockManager(num_blocks=5, block_size=8, max_blocks_per_seq=4)
        t = mgr.allocate("a", 10)              # blocks for 10 tokens: 2
        mgr.mark_cached(t[0])
        mgr.mark_cached(t[1])
        mgr.release("a")
        tb = mgr.allocate("b", 20, shared=[int(t[0])], cow_src=int(t[1]))
        # the pending copy holds the source alive: not evictable, ref 1
        assert mgr.ref_count(t[1]) == 1
        assert tb[0] == t[0] and tb[1] not in (t[0], t[1])
        mgr.cow_done("b")
        assert mgr.ref_count(t[1]) == 0
        mgr.release("b")
        # release with a pending COW unpins too (cancel mid-admit)
        tc = mgr.allocate("c", 20, shared=[int(t[0])], cow_src=int(t[1]))
        assert tc is not None and mgr.ref_count(t[1]) == 1
        mgr.release("c")
        assert mgr.ref_count(t[1]) == 0
        assert mgr.num_free == 4

    def test_can_allocate_shared_discounts_pinned_evictables(self):
        mgr = BlockManager(num_blocks=3, block_size=8, max_blocks_per_seq=2)
        t = mgr.allocate("a", 16)
        for b in t[:2]:
            mgr.mark_cached(b)
        mgr.release("a")
        assert mgr.num_free == 2
        # sharing BOTH evictable blocks leaves nothing to take fresh
        assert not mgr.can_allocate_shared(17, shared=[int(t[0]),
                                                       int(t[1])])
        assert mgr.can_allocate_shared(16, shared=[int(t[0])])

    def test_drop_cached_returns_evictable_to_free_list(self):
        mgr = BlockManager(num_blocks=3, block_size=8, max_blocks_per_seq=2)
        t = mgr.allocate("a", 8)
        mgr.mark_cached(t[0])
        mgr.release("a")
        assert len(mgr._free) == 1 and len(mgr._evictable) == 1
        mgr.drop_cached(t[0])
        assert len(mgr._free) == 2 and mgr.num_cached == 0


class TestPrefixCache:
    def _pair(self, num_blocks=12, bs=4):
        from deepspeed_tpu.serving.prefix_cache import PrefixCache

        mgr = BlockManager(num_blocks, bs, max_blocks_per_seq=8)
        return mgr, PrefixCache(mgr)

    def test_match_caps_at_prompt_minus_one(self):
        mgr, pc = self._pair()
        prompt = list(range(8))  # exactly 2 full blocks
        t = mgr.allocate("a", 10)
        pc.insert(prompt, t)
        # an identical prompt must keep >= 1 tail token to prefill, so
        # only the FIRST block may match
        shared, cow, matched = pc.match(prompt)
        assert shared == [int(t[0])] and cow is None and matched == 4
        # an extended prompt matches both full blocks
        shared, cow, matched = pc.match(prompt + [9])
        assert shared == [int(t[0]), int(t[1])] and matched == 8

    def test_partial_tail_matches_as_cow(self):
        mgr, pc = self._pair()
        prompt = list(range(6))  # 1 full block + 2-token tail
        t = mgr.allocate("a", 8)
        pc.insert(prompt, t)
        shared, cow, matched = pc.match(prompt + [9, 10])
        assert shared == [int(t[0])]
        assert cow == int(t[1]) and matched == 6
        # a diverging tail shares only the full block
        shared, cow, matched = pc.match(list(range(4)) + [99, 98, 97])
        assert shared == [int(t[0])] and cow is None and matched == 4

    def test_eviction_prunes_subtree(self):
        mgr, pc = self._pair(num_blocks=6, bs=4)
        prompt = list(range(12))  # 3 full blocks
        t = mgr.allocate("a", 13)
        pc.insert(prompt, t)
        mgr.release("a")
        assert mgr.num_cached == 3
        mgr.allocate("b", 8)        # drains the free list, no eviction
        assert len(pc) == 3
        # make the chain's ROOT the LRU victim: its eviction orphans the
        # two descendant blocks, which must leave the trie AND return
        # their storage to the free list immediately
        mgr.touch([t[1], t[2]])
        mgr.allocate("c", 4)        # forces one eviction: the root block
        assert len(pc) == 0 and mgr.num_cached == 0
        assert mgr.owned("c") == [int(t[0])]
        assert set(mgr._free) == {int(t[1]), int(t[2])}
        shared, cow, matched = pc.match(prompt + [99])
        assert not shared and cow is None and matched == 0

    def test_insert_dedups_existing_chunks(self):
        mgr, pc = self._pair()
        p = list(range(8))
        ta = mgr.allocate("a", 9)
        pc.insert(p, ta)
        tb = mgr.allocate("b", 9)  # same prompt prefilled unshared
        added = pc.insert(p, tb)
        assert added == 0  # existing physical blocks keep the index
        shared, _, _ = pc.match(p + [1])
        assert shared == [int(ta[0]), int(ta[1])]


def _sched(clock, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("decode_slots", 2)
    kw.setdefault("default_max_new_tokens", 4)
    cfg = ServingConfig(**kw)
    blocks = BlockManager(kw.get("num_blocks", 17), cfg.block_size, 8)
    prefix = None
    if kw.get("prefix_cache"):
        from deepspeed_tpu.serving.prefix_cache import PrefixCache

        prefix = PrefixCache(blocks)
    return ContinuousBatchingScheduler(cfg, blocks, max_len=64,
                                       clock=clock,
                                       prefix_cache=prefix), blocks


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestScheduler:
    def test_fifo_admission_into_slots(self):
        clk = _Clock()
        sched, _ = _sched(clk)
        reqs = [Request(prompt=[1] * 4) for _ in range(3)]
        assert all(sched.submit(r) for r in reqs)
        admitted, shed = sched.admit()
        assert [r.request_id for _, r, _ in admitted] == \
            [reqs[0].request_id, reqs[1].request_id]
        assert not shed and reqs[2].state == QUEUED
        assert reqs[0].state == RUNNING and reqs[0].slot == 0
        # finishing slot 0 lets the third request splice in
        sched.finish(reqs[0], "eos")
        admitted, _ = sched.admit()
        assert len(admitted) == 1 and admitted[0][0] == 0
        assert admitted[0][1] is reqs[2]

    def test_queue_depth_shed(self):
        clk = _Clock()
        sched, _ = _sched(clk, max_queue_depth=2)
        r = [Request(prompt=[1]) for _ in range(3)]
        assert sched.submit(r[0]) and sched.submit(r[1])
        assert not sched.submit(r[2])
        assert r[2].state == SHED and r[2].finish_reason == "queue_full"
        assert sched.stats["shed_reasons"] == {"queue_full": 1}

    def test_too_long_shed(self):
        clk = _Clock()
        sched, _ = _sched(clk)
        long = Request(prompt=[1] * 80)  # > max_len 64
        assert not sched.submit(long)
        assert long.finish_reason == "too_long"
        over = Request(prompt=[1] * 32, max_new_tokens=40)  # cost 72 > 64
        assert not sched.submit(over)
        assert over.finish_reason == "too_long"

    def test_cancel_releases_queued_and_running(self):
        clk = _Clock()
        sched, blocks = _sched(clk)
        a = Request(prompt=[1] * 4, request_id="a")   # will be running
        b = Request(prompt=[1] * 4, request_id="b")
        c = Request(prompt=[1] * 4, request_id="c")   # stays queued
        assert all(sched.submit(r) for r in (a, b, c))
        sched.admit()
        free_before = blocks.num_free
        assert sched.cancel("a", "failover") is a
        assert a.state == SHED and a.finish_reason == "failover"
        assert sched.slots[a.slot] is None            # slot returned
        assert blocks.num_free > free_before          # blocks returned
        assert sched.cancel("c", "failover") is c     # queued leg
        assert c.state == SHED and "c" not in sched._live_ids
        assert sched.cancel("a", "failover") is None  # already gone
        assert sched.committed_tokens == \
            b.prompt_len + b.max_new_tokens
        assert sched.stats["shed_reasons"]["failover"] == 2

    def test_inflight_tokens_shed_policy(self):
        clk = _Clock()
        sched, _ = _sched(clk, max_inflight_tokens=20, shed_policy="shed")
        a = Request(prompt=[1] * 8, max_new_tokens=4)   # cost 12
        b = Request(prompt=[1] * 8, max_new_tokens=4)   # would total 24
        assert sched.submit(a)
        assert not sched.submit(b)
        assert b.finish_reason == "inflight_tokens"
        # capacity returns when a finishes
        sched.admit()
        sched.finish(a, "eos")
        c = Request(prompt=[1] * 8, max_new_tokens=4)
        assert sched.submit(c)

    def test_inflight_tokens_queue_policy_defers(self):
        clk = _Clock()
        sched, _ = _sched(clk, max_inflight_tokens=12, shed_policy="queue")
        a = Request(prompt=[1] * 8, max_new_tokens=4)   # cost 12
        b = Request(prompt=[1] * 8, max_new_tokens=4)
        assert sched.submit(a) and sched.submit(b)  # queue accepts both
        admitted, _ = sched.admit()
        assert len(admitted) == 1 and admitted[0][1] is a  # b deferred
        assert b.state == QUEUED
        sched.finish(a, "eos")
        admitted, _ = sched.admit()
        assert len(admitted) == 1 and admitted[0][1] is b

    def test_block_pool_backpressure_defers_not_drops(self):
        clk = _Clock()
        # 3 usable blocks; each request needs 2 (cost 12 tokens, bs 8)
        sched, blocks = _sched(clk, num_blocks=4)
        a = Request(prompt=[1] * 8, max_new_tokens=4)
        b = Request(prompt=[1] * 8, max_new_tokens=4)
        assert sched.submit(a) and sched.submit(b)
        admitted, _ = sched.admit()
        assert [r for _, r, _ in admitted] == [a]
        assert b.state == QUEUED  # waits for frees, never shed
        sched.finish(a, "eos")
        assert blocks.num_free == 3
        admitted, _ = sched.admit()
        assert [r for _, r, _ in admitted] == [b]

    def test_deadline_shed_at_admission(self):
        clk = _Clock()
        sched, _ = _sched(clk, deadline_ms=100.0)
        a = Request(prompt=[1] * 4)
        assert sched.submit(a)
        clk.t = 0.5  # 500ms later: blown
        admitted, shed = sched.admit()
        assert not admitted and shed == [a]
        assert a.state == SHED and a.finish_reason == "deadline"

    def test_per_request_deadline_overrides_default(self):
        clk = _Clock()
        sched, _ = _sched(clk, deadline_ms=1000.0)
        a = Request(prompt=[1] * 4, deadline_ms=10.0)
        assert sched.submit(a)
        clk.t = 0.05
        assert sched.expired(a, clk.t)

    def test_request_larger_than_pool_shed_not_deferred(self):
        """A request the pool can NEVER hold must shed at submit — admit()
        defers on allocation pressure, and waiting on frees that cannot
        suffice would spin step()/drain() forever."""
        clk = _Clock()
        sched, _ = _sched(clk, num_blocks=2)  # 1 usable block (0=garbage)
        big = Request(prompt=[1] * 8, max_new_tokens=4)   # needs 2 blocks
        assert not sched.submit(big)
        assert big.finish_reason == "too_long"
        small = Request(prompt=[1] * 4, max_new_tokens=2)  # fits: 1 block
        assert sched.submit(small)
        admitted, _ = sched.admit()
        assert [r for _, r, _ in admitted] == [small]

    def test_reset_stats_keeps_live_state(self):
        clk = _Clock()
        sched, _ = _sched(clk)
        a = Request(prompt=[1] * 4)
        sched.submit(a)
        sched.admit()
        sched.reset_stats()
        assert sched.stats["submitted"] == 0
        assert sched.pending and a.state == RUNNING  # live state untouched
        sched.finish(a, "eos")
        assert sched.stats["finished"] == 1

    def test_duplicate_request_id_shed_at_submit(self):
        """A duplicate id would collide in the block manager mid-admit
        and crash the serving loop — it must be shed at the door, and the
        id becomes reusable once the original finishes."""
        clk = _Clock()
        sched, _ = _sched(clk)
        a = Request(prompt=[1] * 4, request_id="x")
        dup = Request(prompt=[2] * 4, request_id="x")
        assert sched.submit(a)
        assert not sched.submit(dup)
        assert dup.finish_reason == "duplicate_id"
        sched.admit()
        sched.finish(a, "eos")
        again = Request(prompt=[3] * 4, request_id="x")
        assert sched.submit(again)

    def test_stats_and_committed_accounting(self):
        clk = _Clock()
        sched, _ = _sched(clk)
        a = Request(prompt=[1] * 4, max_new_tokens=4)
        sched.submit(a)
        assert sched.committed_tokens == 8
        sched.admit()
        sched.finish(a, "max_tokens")
        assert sched.committed_tokens == 0
        assert sched.stats["submitted"] == sched.stats["finished"] == 1
        assert not sched.pending

    def test_shed_timestamps_use_callers_timebase(self):
        """A shed under an injected `now` must stamp finish_ts from that
        same timebase — never from a live clock read that would mix
        fake-clock and wall-clock times in one record."""
        clk = _Clock()
        sched, _ = _sched(clk, max_queue_depth=1, deadline_ms=100.0)
        clk.t = 50.0  # a drifted live clock the shed must NOT consult
        a = Request(prompt=[1] * 4)
        assert sched.submit(a, now=2.0)
        b = Request(prompt=[1] * 4)
        assert not sched.submit(b, now=2.5)  # queue_full
        assert b.finish_ts == 2.5 and b.submit_ts == 2.5
        _, shed = sched.admit(now=3.0)  # a's 100ms deadline blew at 2.1
        assert shed == [a] and a.finish_ts == 3.0

    def test_gauges_track_queue_slots_and_commitment(self):
        clk = _Clock()
        sched, _ = _sched(clk)
        assert sched.gauges() == {
            "queue_depth": 0, "queue_capacity": 64, "slots_busy": 0,
            "slots_total": 2, "committed_tokens": 0}
        reqs = [Request(prompt=[1] * 4, max_new_tokens=4)
                for _ in range(3)]
        for r in reqs:
            sched.submit(r)
        assert sched.gauges()["queue_depth"] == 3
        assert sched.gauges()["committed_tokens"] == 24
        sched.admit()
        g = sched.gauges()
        assert g["queue_depth"] == 1 and g["slots_busy"] == 2
        sched.finish(reqs[0], "eos")
        g = sched.gauges()
        assert g["slots_busy"] == 1 and g["committed_tokens"] == 16


class TestSchedulerAccountingFuzz:
    """Satellite: randomized submit/admit/finish/shed sequences keep
    `committed_tokens`, `_live_ids`, and the block-pool free list
    mutually consistent — the admission state machine can never leak a
    token budget, a request id, or a cache block."""

    def _invariants(self, sched, blocks):
        live = list(sched.queue) + [r for r in sched.slots if r is not None]
        assert sched.committed_tokens == sum(
            r.prompt_len + r.max_new_tokens for r in live)
        assert sched._live_ids == {r.request_id for r in live}
        # every allocated block belongs to a RUNNING request, exactly
        allocated = blocks.num_blocks - 1 - blocks.num_free
        assert allocated == sum(
            blocks.blocks_needed(r.prompt_len + r.max_new_tokens)
            for r in sched.slots if r is not None)

    def test_random_walk_conserves_accounting(self):
        rng = np.random.default_rng(42)
        clk = _Clock()
        sched, blocks = _sched(clk, max_queue_depth=6, num_blocks=9,
                               max_inflight_tokens=80, deadline_ms=200.0)
        next_id = 0
        for step in range(600):
            op = rng.choice(["submit", "admit", "finish", "cancel",
                             "tick"])
            if op == "submit":
                if rng.random() < 0.15 and sched._live_ids:
                    rid = sorted(sched._live_ids)[0]  # duplicate id
                else:
                    rid, next_id = f"z-{next_id}", next_id + 1
                req = Request(
                    prompt=[1] * int(rng.integers(1, 80)),
                    max_new_tokens=int(rng.integers(1, 12)),
                    request_id=rid,
                    deadline_ms=float(rng.choice([0.0, 50.0, 500.0])))
                sched.submit(req, now=clk.t)
            elif op == "admit":
                sched.admit(now=clk.t)
            elif op == "finish":
                running = [r for r in sched.slots if r is not None]
                if running:
                    pick = running[int(rng.integers(len(running)))]
                    sched.finish(pick, "eos", now=clk.t)
            elif op == "cancel":
                if sched._live_ids:  # queued or running, either works
                    ids = sorted(sched._live_ids)
                    sched.cancel(ids[int(rng.integers(len(ids)))],
                                 "cancelled", now=clk.t)
            else:
                clk.t += float(rng.random() * 0.2)
            self._invariants(sched, blocks)
        # drain everything: accounting returns to zero
        clk.t += 10.0
        for _ in range(50):
            sched.admit(now=clk.t)
            for r in [r for r in sched.slots if r is not None]:
                sched.finish(r, "eos", now=clk.t)
        assert not sched.pending
        assert sched.committed_tokens == 0 and not sched._live_ids
        assert blocks.num_free == blocks.num_blocks - 1
        s = sched.stats
        assert s["submitted"] == s["finished"] + s["shed"] + \
            len(sched.queue)


class TestPrefixCowFuzz:
    """Satellite: the PR 6 accounting fuzz extended with COW ops —
    shared-prefix admits, release-with-refcount, LRU evictions under
    pool pressure — pinning refcount / free-list / `committed_tokens`
    mutual consistency. Host-only, tier-1."""

    def _invariants(self, sched, blocks, prefix):
        live = list(sched.queue) + [r for r in sched.slots if r is not None]
        assert sched.committed_tokens == sum(
            r.prompt_len + r.max_new_tokens for r in live)
        assert sched._live_ids == {r.request_id for r in live}
        # every physical block is in EXACTLY one state: free, parked
        # evictable, or live-referenced
        free = set(blocks._free)
        evictable = set(blocks._evictable)
        referenced = set(blocks._ref)
        assert not (free & evictable) and not (free & referenced) \
            and not (evictable & referenced)
        assert free | evictable | referenced == \
            set(range(1, blocks.num_blocks))
        # refcount == holders: owners listing the block + pending COW pins
        expect = {}
        for blocks_list in blocks._owned.values():
            for b in blocks_list:
                expect[b] = expect.get(b, 0) + 1
        for b in blocks._cow_pending.values():
            expect[b] = expect.get(b, 0) + 1
        assert blocks._ref == expect
        # evictable blocks are all cached; nothing cached sits on the
        # free list (a freed block must be unindexed)
        assert evictable <= blocks._cached
        assert not (free & blocks._cached)
        # the trie indexes exactly the cached blocks
        assert set(prefix._by_block) == blocks._cached
        # only RUNNING sequences own blocks
        assert set(blocks._owned) == {
            r.request_id for r in sched.slots if r is not None}

    def test_random_walk_with_prefix_sharing(self):
        rng = np.random.default_rng(7)
        clk = _Clock()
        sched, blocks = _sched(clk, max_queue_depth=6, num_blocks=12,
                               deadline_ms=200.0, prefix_cache=True)
        prefix = sched.prefix
        # prompt families with long common prefixes drive real sharing
        families = [list(rng.integers(1, 99, 40)) for _ in range(3)]
        next_id = 0
        pending_cow = {}  # request_id -> admitted but engine not done
        for step in range(800):
            op = rng.choice(["submit", "admit", "finish", "cancel", "tick"])
            if op == "submit":
                fam = families[int(rng.integers(len(families)))]
                cut = int(rng.integers(1, len(fam)))
                prompt = fam[:cut] + list(rng.integers(100, 200, int(
                    rng.integers(0, 6))))
                rid, next_id = f"z-{next_id}", next_id + 1
                sched.submit(Request(
                    prompt=prompt,
                    max_new_tokens=int(rng.integers(1, 10)),
                    request_id=rid,
                    deadline_ms=float(rng.choice([0.0, 50.0, 500.0]))),
                    now=clk.t)
            elif op == "admit":
                admitted, _ = sched.admit(now=clk.t)
                for _, r, table in admitted:
                    if rng.random() < 0.25:
                        # engine "crashed" between admit and prefill:
                        # the COW pin stays until finish/cancel releases
                        pending_cow[r.request_id] = table
                    else:
                        blocks.cow_done(r.request_id)
                        prefix.insert(r.prompt, table)
            elif op == "finish":
                running = [r for r in sched.slots if r is not None]
                if running:
                    pick = running[int(rng.integers(len(running)))]
                    pending_cow.pop(pick.request_id, None)
                    sched.finish(pick, "eos", now=clk.t)
            elif op == "cancel":
                if sched._live_ids:
                    ids = sorted(sched._live_ids)
                    rid = ids[int(rng.integers(len(ids)))]
                    pending_cow.pop(rid, None)
                    sched.cancel(rid, "cancelled", now=clk.t)
            else:
                clk.t += float(rng.random() * 0.2)
            self._invariants(sched, blocks, prefix)
        # drain everything: live accounting returns to zero, and the
        # pool partitions into free + warm evictable cache
        clk.t += 10.0
        for _ in range(60):
            admitted, _ = sched.admit(now=clk.t)
            for _, r, table in admitted:
                blocks.cow_done(r.request_id)
                prefix.insert(r.prompt, table)
            for r in [r for r in sched.slots if r is not None]:
                sched.finish(r, "eos", now=clk.t)
        assert not sched.pending
        assert sched.committed_tokens == 0 and not sched._live_ids
        assert not blocks._ref and not blocks._cow_pending
        assert blocks.num_free == blocks.num_blocks - 1
        assert len(blocks._free) + len(blocks._evictable) == \
            blocks.num_blocks - 1


class TestWatchdogTouch:
    def test_touch_refreshes_only_when_armed(self):
        """Per-decode-step progress keeps a saturated server alive
        between request completions, but never arms an unarmed watchdog
        (the first request's compile must stay untripped)."""
        from deepspeed_tpu.runtime.resilience.watchdog import HangWatchdog

        wd = HangWatchdog(timeout_secs=3600, abort=False)
        wd.touch()
        assert wd._last_progress is None  # not armed: no-op
        wd.notify(1)
        armed_at = wd._last_progress
        wd.touch()
        assert wd._last_progress >= armed_at  # armed: refreshed


class TestRequestRecord:
    def test_record_payload(self):
        r = Request(prompt=[1, 2, 3])
        r.submit_ts, r.admit_ts = 1.0, 1.2
        r.first_token_ts, r.finish_ts = 1.5, 2.5
        r.tokens = [5, 6, 7]
        r.state, r.finish_reason = FINISHED, "max_tokens"
        rec = r.record()
        # queue wait (submit -> slot) and TTFT (submit -> first token)
        # are distinct: the gap between them is prefill compile/compute
        assert rec["queue_ms"] == pytest.approx(200.0)
        assert rec["ttft_ms"] == 500.0
        assert rec["tokens_per_sec"] == 3.0
        assert rec["prompt_len"] == 3 and rec["new_tokens"] == 3

    def test_stream_callback_order(self):
        seen = []
        r = Request(prompt=[1],
                    stream=lambda req, tok, done: seen.append((tok, done)))
        r.emit_token(5, False)
        r.emit_token(6, True)
        assert seen == [(5, False), (6, True)]


# ---------------------------------------------------------------------------
# ServingEngine integration (tiny CPU model)
# ---------------------------------------------------------------------------
def _tiny_serving(serving=None, telemetry=None, seed=0):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    cfg = GPT2Config.tiny(dtype=jnp.float32)
    kwargs = {}
    if serving is not None:
        kwargs["serving"] = serving
    if telemetry is not None:
        kwargs["telemetry"] = telemetry
    engine = deepspeed_tpu.init_inference(GPT2LMHeadModel(cfg),
                                          dtype="fp32", seed=seed, **kwargs)
    return cfg, engine


_SERVING = {"block_size": 8, "decode_slots": 3,
            "default_max_new_tokens": 4}


@pytest.mark.heavy
class TestServingEngine:
    def test_batch_invariance_staggered_arrivals(self):
        """Acceptance proof: greedy tokens under continuous batching
        (staggered arrivals, paged cache, splicing into freed slots)
        bit-match per-request generate() output."""
        import jax.numpy as jnp

        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving=_SERVING)
        srv = ServingEngine(engine)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 256, n) for n in (5, 11, 3, 8, 16)]
        news = [6, 4, 5, 3, 4]
        reqs = []
        # staggered arrivals: 2 up front, the rest spliced in between
        # decode steps as slots free up
        reqs.append(srv.submit(prompts[0], max_new_tokens=news[0]))
        reqs.append(srv.submit(prompts[1], max_new_tokens=news[1]))
        srv.step()
        srv.step()
        reqs.append(srv.submit(prompts[2], max_new_tokens=news[2]))
        reqs.append(srv.submit(prompts[3], max_new_tokens=news[3]))
        srv.step()
        reqs.append(srv.submit(prompts[4], max_new_tokens=news[4]))
        srv.drain()

        _, ref = _tiny_serving()  # no serving block: pristine legacy engine
        ref.params = engine.params
        for req, p, n in zip(reqs, prompts, news):
            assert req.state == FINISHED, (req.state, req.finish_reason)
            out = ref.generate(jnp.asarray(p[None]), max_new_tokens=n,
                               do_sample=False)
            expect = [int(t) for t in out[0, len(p):]]
            assert req.tokens == expect, (req.request_id, req.tokens, expect)
        # every block returned to the pool
        assert srv.block_mgr.num_free == srv.num_blocks - 1
        assert not srv.pending

    def test_zero_steady_state_retraces(self):
        """Compile-watchdog-pinned: after the bucket set is warm, new
        arrivals/evictions/splices trigger ZERO recompiles."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(
            serving=_SERVING,
            telemetry={"enabled": True, "compile_watchdog": True,
                       "jsonl": False, "memory": False, "warmup_steps": 1})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(1)
        # warmup: touch every bucket once (8/16/32/64) + the decode program
        for n in (5, 13, 30, 60):
            srv.submit(rng.integers(1, 256, n), max_new_tokens=2)
        srv.drain()
        warm = {k: dict(v) for k, v in
                engine.telemetry.summary()["per_function"].items()}
        assert "serving.decode" in warm and "serving.prefill" in warm
        # steady state: mixed lengths, staggered, slots churn
        for i, n in enumerate((3, 7, 9, 20, 33, 50, 6, 15)):
            srv.submit(rng.integers(1, 256, n), max_new_tokens=3)
            srv.step()
        srv.drain()
        after = engine.telemetry.summary()["per_function"]
        for fam in ("serving.prefill", "serving.decode"):
            assert after[fam]["compiles"] == warm[fam]["compiles"], \
                (fam, warm[fam], after[fam])
            assert after[fam]["retraces_after_warm"] == \
                warm[fam]["retraces_after_warm"]

    def test_shed_deadline_streaming_and_telemetry(self):
        from deepspeed_tpu.serving import SHED as SHED_STATE
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving={
            **_SERVING, "decode_slots": 1, "max_queue_depth": 4,
            "max_inflight_tokens": 40, "shed_policy": "shed"})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(2)
        seen = []
        a = srv.submit(rng.integers(1, 256, 5), max_new_tokens=3,
                       stream=lambda r, t, d: seen.append((r.request_id,
                                                           t, d)))
        b = srv.submit(rng.integers(1, 256, 20), max_new_tokens=4)
        c = srv.submit(rng.integers(1, 256, 20), max_new_tokens=4)
        assert c.state == SHED_STATE  # inflight-token cap
        assert c.finish_reason == "inflight_tokens"
        d = srv.submit(rng.integers(1, 256, 4), max_new_tokens=2,
                       deadline_ms=0.0001)
        srv.drain()
        assert a.state == FINISHED and b.state == FINISHED
        assert d.state == SHED_STATE and d.finish_reason == "deadline"
        # streaming fired once per token, in order, done on the last
        assert [t for _, t, _ in seen] == a.tokens
        assert [done for _, _, done in seen] == [False, False, True]
        st = srv.stats()
        assert st["finished"] == 2 and st["shed"] == 2
        assert st["shed_rate"] == 0.5
        assert set(st["shed_reasons"]) == {"inflight_tokens", "deadline"}
        recs = {r["request_id"]: r for r in srv.records}
        assert recs[a.request_id]["ttft_ms"] is not None
        assert recs[a.request_id]["new_tokens"] == 3

    def test_eos_early_stop_frees_slot(self):
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving=_SERVING)
        srv = ServingEngine(engine)
        rng = np.random.default_rng(0)
        p = rng.integers(1, 256, 5)
        # run once to learn the greedy continuation, then use its first
        # token as the eos id: the request must stop after ONE token
        probe = srv.submit(p, max_new_tokens=3)
        srv.drain()
        eos = probe.tokens[0]
        req = srv.submit(p, max_new_tokens=5, eos_token_id=int(eos))
        srv.drain()
        assert req.state == FINISHED and req.finish_reason == "eos"
        assert req.tokens == [eos]
        assert srv.block_mgr.num_free == srv.num_blocks - 1

    def test_int8_engine_serves(self):
        from deepspeed_tpu.serving import ServingEngine

        import jax.numpy as jnp

        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
        from deepspeed_tpu.parallel.topology import reset_topology

        reset_topology()
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        engine = deepspeed_tpu.init_inference(
            GPT2LMHeadModel(cfg), dtype="int8", serving=_SERVING)
        srv = ServingEngine(engine)
        toks = srv.generate_batch([[5, 6, 7], [9, 10, 11, 12]],
                                  max_new_tokens=2)
        assert all(t is not None and len(t) == 2 for t in toks)

    def test_watchdog_brackets_balanced(self):
        """Per-request begin/heartbeat/abandon brackets: after a drain
        (incl. shed requests) the watchdog busy counter is zero, so an
        idle server can never be judged hung."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving={**_SERVING, "decode_slots": 1})
        engine._config.resilience = {}
        from deepspeed_tpu.runtime.resilience import Resilience

        engine.resilience = Resilience(
            {"enabled": True, "watchdog": {"enabled": True,
                                           "timeout_secs": 3600,
                                           "abort": False}},
            telemetry=engine.telemetry, name="inference", serving=True)
        srv = ServingEngine(engine)
        rng = np.random.default_rng(3)
        srv.submit(rng.integers(1, 256, 4), max_new_tokens=2)
        srv.submit(rng.integers(1, 256, 4), max_new_tokens=2,
                   deadline_ms=0.0001)  # will be shed at admission
        srv.drain()
        wd = engine.resilience.watchdog
        assert wd is not None and wd._busy == 0
        assert wd.last_step == 1  # one completed request heartbeat
        engine.resilience.close()


# ---------------------------------------------------------------------------
# serving fast path: prefix cache + chunked prefill + int8 KV (heavy)
# ---------------------------------------------------------------------------
@pytest.mark.heavy
class TestServingFastPath:
    def _ref_tokens(self, engine, prompt, n):
        import jax.numpy as jnp

        _, ref = _tiny_serving()
        ref.params = engine.params
        out = ref.generate(jnp.asarray(np.asarray(prompt)[None]),
                           max_new_tokens=n, do_sample=False)
        return [int(t) for t in out[0, len(prompt):]]

    def test_shared_prefix_physical_sharing_and_bitmatch(self):
        """Acceptance: two sequences sharing a system prompt physically
        share prefix blocks (asserted on BlockManager state), the second
        request prefills only the tail, and greedy output bit-matches an
        unshared run."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving={**_SERVING,
                                           "prefix_cache": True})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(0)
        system = rng.integers(1, 256, 16)  # exactly 2 full blocks
        p_a = np.concatenate([system, rng.integers(1, 256, 5)])
        p_b = np.concatenate([system, rng.integers(1, 256, 7)])
        p_c = np.concatenate([system, rng.integers(1, 256, 3)])
        a = srv.submit(p_a, max_new_tokens=4)
        srv.drain()  # populates the radix cache with a's prompt blocks
        sys_blocks = srv.block_mgr.owned(a.request_id)  # gone after drain
        b = srv.submit(p_b, max_new_tokens=4)
        c = srv.submit(p_c, max_new_tokens=4)
        srv.step()  # both admit + prefill their tails
        owned_b = srv.block_mgr.owned(b.request_id)
        owned_c = srv.block_mgr.owned(c.request_id)
        shared = set(owned_b) & set(owned_c)
        assert len(shared) == 2, (owned_b, owned_c)  # the 2 system blocks
        for blk in shared:
            assert srv.block_mgr.ref_count(blk) == 2  # both rows hold it
        # the second request's prefill processed ONLY the tail tokens
        assert b.prefix_hit_tokens == 16 and b.cached_len == 16
        assert b.blocks_shared == 2 and b.prefill_chunks == 1
        # tail chunks ran through the small chunk bucket, never a
        # whole-prompt program for the full 23-token prompt
        assert set(srv._chunk_fns) <= {8, 16}
        srv.drain()
        for req, p in ((a, p_a), (b, p_b), (c, p_c)):
            assert req.state == FINISHED
            assert req.tokens == self._ref_tokens(engine, p, 4), \
                req.request_id
        # released shared blocks parked warm (evictable), not freed
        assert srv.block_mgr.num_free == srv.num_blocks - 1
        assert srv.block_mgr.num_cached > 0
        assert not sys_blocks  # a's ownership ended at its finish

    def test_partial_tail_copy_on_write_bitmatch(self):
        """A prompt extending a cached prompt's partial last block maps
        it via COW: the copy is private, the donor's cached rows stay
        intact, and tokens bit-match the unshared run."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving={**_SERVING,
                                           "prefix_cache": True})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(1)
        p1 = rng.integers(1, 256, 18)                  # 2 blocks + 2 tail
        p2 = np.concatenate([p1, rng.integers(1, 256, 6)])
        a = srv.submit(p1, max_new_tokens=3)
        srv.drain()
        b = srv.submit(p2, max_new_tokens=3)
        srv.drain()
        # full blocks shared + the partial tail block copied-on-write
        assert b.prefix_hit_tokens == 18
        assert b.blocks_shared == 3 and b.cow is not None
        assert b.tokens == self._ref_tokens(engine, p2, 3)
        # the donor prompt still matches its own cache entries afterward
        c = srv.submit(np.concatenate([p1, rng.integers(1, 256, 2)]),
                       max_new_tokens=3)
        srv.drain()
        assert c.prefix_hit_tokens == 18

    def test_a_decode_rows_write_block_is_never_shared(self):
        """What GPT-2's paged decode call leans on (it rewrites the strip
        of a busy row's LAST block that takes the step's row, and no other
        block): at every decode step, every decode-ready row appends to a
        block that it alone holds. Whole prompt blocks are shared and
        never appended to; a shared partial tail is copied on write at
        admission, before any step appends."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving={**_SERVING,
                                           "prefix_cache": True})
        srv = ServingEngine(engine)
        bs = _SERVING["block_size"]
        rng = np.random.default_rng(4)
        system = rng.integers(1, 256, 2 * bs)       # two whole blocks
        tail = rng.integers(1, 256, 3)              # ... and a partial one
        donor = srv.submit(np.concatenate([system, tail]), max_new_tokens=2)
        srv.drain()
        reqs = [srv.submit(np.concatenate([system, tail, more]),
                           max_new_tokens=bs + 3)   # over a block boundary
                for more in (rng.integers(1, 256, 2), rng.integers(1, 256, 4),
                             np.zeros(0, np.int64))]
        seen = 0
        while srv.pending:
            srv.step()
            for slot, req in srv._decode_ready():
                block = int(srv._tables[slot][req.length // bs])
                assert block != 0, (req.request_id, req.length)
                assert srv.block_mgr.ref_count(block) == 1, (
                    req.request_id, req.length, block)
                seen += 1
        assert seen > 3 * bs and donor.state == FINISHED
        assert all(r.prefix_hit_tokens >= 2 * bs for r in reqs)
        assert any(r.cow is not None or r.blocks_shared == 3 for r in reqs)
        srv.destroy()

    def test_chunked_prefill_bitmatch_and_interleave(self):
        """Chunked prefill: a long prompt advances one budgeted chunk
        per step while decodes continue; a short request admitted behind
        it reaches its first token BEFORE the long prefill completes
        (the TTFT bound), and every token bit-matches generate()."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving={**_SERVING, "decode_slots": 2,
                                           "prefill_chunk_tokens": 8})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(2)
        long_p = rng.integers(1, 256, 33)   # 5 chunks of 8
        short_p = rng.integers(1, 256, 5)   # 1 chunk
        a = srv.submit(long_p, max_new_tokens=3)
        b = srv.submit(short_p, max_new_tokens=3)
        short_first_step, steps = None, 0
        while srv.pending and steps < 64:
            srv.step()
            steps += 1
            if short_first_step is None and b.tokens:
                short_first_step = steps
                assert not a.tokens  # long prompt still mid-prefill
        assert a.prefill_chunks == 5 and b.prefill_chunks == 1
        assert short_first_step is not None and short_first_step < steps
        assert a.tokens == self._ref_tokens(engine, long_p, 3)
        assert b.tokens == self._ref_tokens(engine, short_p, 3)
        # ONE chunk program serves every prompt length
        assert set(srv._chunk_fns) == {8}
        assert len(srv._prefill_fns) == 0  # the bucket ladder is gone

    def test_chunked_prefill_zero_steady_state_retraces(self):
        """Acceptance: steady-state chunked-prefill serving holds the
        zero-retrace compile-watchdog pin — chunk + decode programs warm
        once, then arbitrary mixed traffic compiles nothing."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(
            serving={**_SERVING, "prefix_cache": True,
                     "prefill_chunk_tokens": 8},
            telemetry={"enabled": True, "compile_watchdog": True,
                       "jsonl": False, "memory": False, "warmup_steps": 1})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(3)
        base = rng.integers(1, 256, 20)
        # warmup: fresh prompt, shared-prefix admit (drives the COW
        # program too), chunked long prompt
        srv.submit(base, max_new_tokens=2)
        srv.drain()
        srv.submit(np.concatenate([base, rng.integers(1, 256, 4)]),
                   max_new_tokens=2)
        srv.submit(rng.integers(1, 256, 40), max_new_tokens=2)
        srv.drain()
        warm = {k: dict(v) for k, v in
                engine.telemetry.summary()["per_function"].items()}
        assert "serving.chunk" in warm and "serving.decode" in warm
        assert "serving.cow" in warm
        for i, n in enumerate((3, 21, 9, 40, 33, 6)):
            srv.submit(rng.integers(1, 256, n), max_new_tokens=3)
            srv.submit(np.concatenate([base[:16],
                                       rng.integers(1, 256, i + 1)]),
                       max_new_tokens=2)
            srv.step()
        srv.drain()
        after = engine.telemetry.summary()["per_function"]
        for fam in ("serving.chunk", "serving.decode", "serving.cow"):
            assert after[fam]["compiles"] == warm[fam]["compiles"], \
                (fam, warm[fam], after[fam])
            assert after[fam]["retraces_after_warm"] == \
                warm[fam]["retraces_after_warm"]

    def test_decode_hlo_byte_identical_with_fast_path_off(self):
        """Acceptance (zero-overhead pin, PR 2-6 convention): with the
        prefix_cache / kv_cache_dtype keys absent, the compiled decode
        program is byte-identical to one built by a prefix-cache-enabled
        engine (the cache is pure host bookkeeping), and the chunk/COW
        programs simply do not exist."""
        import jax

        import jax.numpy as jnp

        from deepspeed_tpu.serving import ServingEngine

        texts = []
        for extra in ({}, {"prefix_cache": True}):
            _, engine = _tiny_serving(serving={**_SERVING, **extra})
            srv = ServingEngine(engine)
            fn = srv._build_decode()
            tokens = jnp.zeros((srv.config.decode_slots, 1), jnp.int32)
            tables = jnp.zeros((srv.config.decode_slots,
                                srv.blocks_per_seq), jnp.int32)
            lengths = jnp.zeros((srv.config.decode_slots,), jnp.int32)
            lowered = fn.lower(engine.params, srv.cache, tokens, tables,
                               lengths, jax.random.PRNGKey(0))
            texts.append(lowered.compile().as_text())
            srv.destroy()
        assert texts[0] == texts[1]
        # feature-off serving never touches the fast-path programs
        _, engine = _tiny_serving(serving=_SERVING)
        srv = ServingEngine(engine)
        srv.submit(np.arange(1, 10), max_new_tokens=3)
        srv.drain()
        assert srv._chunk_fns == {} and srv._cow_fn is None
        assert srv.prefix is None

    def test_int8_kv_greedy_agreement_short_decode(self):
        """Satellite: int8 KV blocks vs f32 KV — greedy tokens agree on
        short decodes (quantization noise stays under every argmax
        margin at this scale), and the int8 cache pytree carries the
        scale side pools."""
        from deepspeed_tpu.serving import ServingEngine

        import jax

        _, engine = _tiny_serving(serving=_SERVING)
        srv = ServingEngine(engine)
        _, engine8 = _tiny_serving(serving={**_SERVING,
                                            "kv_cache_dtype": "int8"})
        engine8.params = engine.params
        srv8 = ServingEngine(engine8)
        leaves = jax.tree_util.tree_leaves_with_path(srv8.cache)
        names = {jax.tree_util.keystr(p) for p, _ in leaves}
        assert any("key_scale" in n for n in names)
        assert any("value_scale" in n for n in names)
        rng = np.random.default_rng(4)
        prompts = [rng.integers(1, 256, n) for n in (5, 11, 17)]
        toks = srv.generate_batch(prompts, max_new_tokens=4)
        toks8 = srv8.generate_batch(prompts, max_new_tokens=4)
        assert toks == toks8, (toks, toks8)


# ---------------------------------------------------------------------------
# the pool's one resident form: [layers, blocks, block_size, lanes]
# ---------------------------------------------------------------------------
def _pool_engine(scan_layers=True, serving=None):
    """A tiny engine whose block stack is scanned or unrolled."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel.topology import reset_topology

    reset_topology()
    cfg = GPT2Config.tiny(dtype=jnp.float32, scan_layers=scan_layers)
    kwargs = {} if serving is None else {"serving": serving}
    return cfg, deepspeed_tpu.init_inference(
        GPT2LMHeadModel(cfg), dtype="fp32", seed=3, **kwargs)


@pytest.mark.heavy
class TestOneResidentPool:
    @pytest.mark.parametrize("sampled", [False, True],
                             ids=["greedy", "keyed"])
    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scanned", "unrolled"])
    def test_served_tokens_are_generates(self, scan_layers, sampled):
        """A scanned stack (the pool rides the layer scan as carry) and an
        unrolled one (``LoopBlocks`` threads the same stacked pool through
        its layers at a static index) serve, staggered, exactly the tokens
        ``generate()`` produces through the append cache — the path this
        change does not touch — for greedy and for keyed sampling. (The
        paged kernel under the same two stacks:
        ``test_decode_attention.py::test_paged_model_steps_kernel_matches_dense``.)"""
        import jax.numpy as jnp

        from deepspeed_tpu.serving import ServingEngine

        serving = dict(_SERVING)
        if sampled:
            serving["sampling"] = {"enabled": True}
        cfg, engine = _pool_engine(scan_layers, serving)
        srv = ServingEngine(engine)
        pools = dict(_cache_leaves(srv.cache))
        assert {n: l.shape for n, l in pools.items()} == {
            n: (cfg.n_layer, srv.num_blocks, 8, cfg.n_embd)
            for n in ("key_pool", "value_pool")}
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, 256, n) for n in (5, 11, 3, 9)]
        news = [5, 3, 4, 3]
        knobs = [dict(do_sample=True, seed=11 * (i + 1), temperature=0.9,
                      top_p=0.9) if sampled and i % 2 == 0 else {}
                 for i in range(len(prompts))]
        reqs = [srv.submit(p, max_new_tokens=n, **k)
                for p, n, k in zip(prompts[:2], news, knobs)]
        srv.step()
        srv.step()
        reqs += [srv.submit(p, max_new_tokens=n, **k)
                 for p, n, k in zip(prompts[2:], news[2:], knobs[2:])]
        srv.drain()
        _, ref = _pool_engine(scan_layers)
        ref.params = engine.params
        for req, p, n, k in zip(reqs, prompts, news, knobs):
            assert req.state == FINISHED, (req.state, req.finish_reason)
            out = ref.generate(jnp.asarray(p[None]), max_new_tokens=n,
                               **(k or {"do_sample": False}))
            assert req.tokens == [int(t) for t in out[0, len(p):]]
        assert srv.block_mgr.num_free == srv.num_blocks - 1

    @pytest.mark.parametrize("kv", ["", "int8"])
    def test_cow_copies_one_block_of_every_layer(self, kv):
        """The cow program copies block ``src`` onto ``dst`` on the pool's
        block axis, in every leaf (int8: the scale rows too) and every
        layer, and touches no other block."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _pool_engine(serving={**_SERVING, "prefix_cache": True,
                                          "kv_cache_dtype": kv})
        srv = ServingEngine(engine)
        before = _random_pool(srv)
        srv._cow_copy(3, 5)
        after = {n: np.asarray(l) for n, l in _cache_leaves(srv.cache)}
        assert len(after) == (4 if kv else 2)
        for name, old in before.items():
            want = old.copy()
            want[:, 5] = old[:, 3]
            np.testing.assert_array_equal(after[name], want)

    @pytest.mark.parametrize("kv", ["", "int8"])
    def test_export_import_round_trips_blocks(self, kv):
        """``export_sequence`` gathers a sequence's blocks on the block
        axis (all layers of a block together; a scale row's padding lanes
        stay home) and ``import_sequence`` scatters them onto another
        pool's freshly allocated blocks: the same rows, in every leaf and
        layer, and the moved request finishes with the tokens it would
        have produced at home."""
        from deepspeed_tpu.serving import ServingEngine

        serving = {**_SERVING, "kv_cache_dtype": kv}
        cfg, e0 = _pool_engine(serving=serving)
        _, e1 = _pool_engine(serving=serving)
        e1.params = e0.params
        src, dst, home = (ServingEngine(e0), ServingEngine(e1),
                          ServingEngine(e0))
        prompt = np.arange(1, 14)
        expect = home.generate_batch([prompt], max_new_tokens=6)[0]
        dst.generate_batch([np.arange(3, 9)], max_new_tokens=2)  # dirty it
        req = src.submit(prompt, max_new_tokens=6)
        for _ in range(2):
            src.step()
        # (the export first fetches the step in flight: a third decode
        # token, 16 tokens pooled)
        export = src.export_sequence(req.request_id)
        assert export["length"] == 16 and src._flight is None
        assert export["blocks"] == 2 and len(export["rows"]) == (
            4 if kv else 2)
        lanes = {"key_pool": cfg.n_embd, "value_pool": cfg.n_embd,
                 "key_scale": cfg.n_head, "value_scale": cfg.n_head}
        for (name, _), chunks in zip(_cache_leaves(src.cache),
                                     export["rows"]):
            assert [c.shape for c in chunks] == [
                (cfg.n_layer, 2, 8, lanes[name])], name
        moved = dst.import_sequence(export)
        assert moved is not None
        src_blocks = src.block_mgr.owned(req.request_id)[:2]
        dst_blocks = dst.block_mgr.owned(moved.request_id)[:2]
        got = dict(_cache_leaves(dst.cache))
        for name, leaf in _cache_leaves(src.cache):
            np.testing.assert_array_equal(
                np.asarray(got[name])[:, dst_blocks],
                np.asarray(leaf)[:, src_blocks])
        assert src.migrate_out(req.request_id)
        dst.drain()
        assert moved.tokens == expect


def _cache_leaves(cache):
    """``[(leaf name, array)]`` of a serving cache."""
    import jax

    return [(path[-1].key, leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(cache)[0]]


def _random_pool(srv):
    """Fill every leaf of ``srv.cache`` with distinct numbers; returns
    them as numpy by leaf name."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(9)
    vals = {n: rng.integers(-100, 100, l.shape).astype(l.dtype)
            for n, l in _cache_leaves(srv.cache)}
    srv.cache = jax.tree_util.tree_map_with_path(
        lambda p, l: jax.device_put(jnp.asarray(vals[p[-1].key]),
                                    l.sharding), srv.cache)
    return vals


# ---------------------------------------------------------------------------
# speculative decoding: the k-token verify program (heavy)
# ---------------------------------------------------------------------------
_SPEC = {**_SERVING, "speculative": {"num_speculative_tokens": 3}}


class _AdversarialProposer:
    """Deterministic mixed-quality proposer: cycles between a full-junk
    window, a loop-guess with a poisoned tail (partial accept), and no
    proposal at all — every accept/reject commit path runs."""

    name = "adversarial"

    def __init__(self):
        self.rng = np.random.default_rng(9)
        self.n = 0

    def propose(self, req, k):
        self.n += 1
        if self.n % 3 == 0:
            return [int(self.rng.integers(1, 256)) for _ in range(k)]
        if self.n % 3 == 1 and req.tokens:
            return [int(req.tokens[-1])] * (k - 1) + [255]
        return []


@pytest.mark.heavy
class TestSpeculativeDecoding:
    def _ref_tokens(self, engine, prompt, n):
        import jax.numpy as jnp

        _, ref = _tiny_serving()
        ref.params = engine.params
        out = ref.generate(jnp.asarray(np.asarray(prompt)[None]),
                           max_new_tokens=n, do_sample=False)
        return [int(t) for t in out[0, len(prompt):]]

    def test_bit_exact_staggered_mixed_accept_reject(self):
        """THE acceptance proof: speculative decode emits the identical
        token stream as non-speculative generate() for every request,
        under staggered continuous batching and an adversarial proposer
        that forces full-accept, partial-accept, full-reject and
        no-proposal verify steps."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving=_SPEC)
        srv = ServingEngine(engine)
        srv._proposer = _AdversarialProposer()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 256, n) for n in (5, 11, 3, 8, 16)]
        news = [6, 4, 5, 3, 8]
        reqs = [srv.submit(prompts[0], max_new_tokens=news[0]),
                srv.submit(prompts[1], max_new_tokens=news[1])]
        srv.step()
        srv.step()
        for p, n in zip(prompts[2:], news[2:]):
            reqs.append(srv.submit(p, max_new_tokens=n))
            srv.step()
        srv.drain()
        for req, p, n in zip(reqs, prompts, news):
            assert req.state == FINISHED, (req.state, req.finish_reason)
            assert req.tokens == self._ref_tokens(engine, p, n), \
                req.request_id
        st = srv.stats()["speculative"]
        # the adversarial mix really drove both branches
        assert st["draft_tokens"] > 0
        assert 0 < st["acceptance_rate"] < 1, st
        # pool fully clean: every window closed, every block returned
        assert srv.block_mgr.num_free == srv.num_blocks - 1
        assert not srv.block_mgr._spec_base

    def test_prompt_lookup_acceptance_and_trace_spans(self):
        """Prompt lookup on a repetitive workload accepts drafts (the
        speedup's substrate), per-request records carry the speculation
        fields, and the request trace gains draft/verify/spec_commit
        legs."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(
            serving=_SPEC,
            telemetry={"enabled": True, "jsonl": False, "memory": False,
                       "compile_watchdog": False,
                       "tracing": {"enabled": True}})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(1)
        motif = rng.integers(1, 256, 4)
        prompt = np.tile(motif, 5)[:18]
        req = srv.submit(prompt, max_new_tokens=8)
        srv.drain()
        assert req.state == FINISHED
        assert req.tokens == self._ref_tokens(engine, prompt, 8)
        assert req.draft_tokens > 0 and req.accepted_tokens > 0
        rec = req.record()
        assert rec["draft_tokens"] == req.draft_tokens
        assert rec["acceptance_rate"] == pytest.approx(
            req.accepted_tokens / req.draft_tokens, abs=1e-3)
        spans = {e["name"] for e in engine.telemetry.tail(200)
                 if e["kind"] == "span"}
        assert {"draft", "verify", "spec_commit"} <= spans, spans
        # fewer verify dispatches than tokens: the win, measured
        assert srv._spec_steps < len(req.tokens)

    def test_zero_steady_state_retraces_with_verify_program(self):
        """Acceptance: the verify program (k static, proposals
        right-padded) compiles once — steady-state speculative traffic
        holds the compile-watchdog zero-retrace pin."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(
            serving=_SPEC,
            telemetry={"enabled": True, "compile_watchdog": True,
                       "jsonl": False, "memory": False, "warmup_steps": 1})
        srv = ServingEngine(engine)
        rng = np.random.default_rng(2)
        for n in (5, 13, 30, 60):
            srv.submit(rng.integers(1, 256, n), max_new_tokens=3)
        srv.drain()
        warm = {k: dict(v) for k, v in
                engine.telemetry.summary()["per_function"].items()}
        assert "serving.verify" in warm
        assert "serving.decode" not in warm  # verify REPLACES decode
        for i, n in enumerate((3, 7, 9, 20, 33, 50, 6, 15)):
            srv.submit(rng.integers(1, 256, n), max_new_tokens=4)
            srv.step()
        srv.drain()
        after = engine.telemetry.summary()["per_function"]
        for fam in ("serving.verify", "serving.prefill"):
            assert after[fam]["compiles"] == warm[fam]["compiles"], \
                (fam, warm[fam], after[fam])
            assert after[fam]["retraces_after_warm"] == \
                warm[fam]["retraces_after_warm"]

    def test_decode_hlo_byte_identical_without_speculative(self):
        """Acceptance (zero-overhead pin): with the speculative block
        absent OR disabled, the compiled decode program is byte-identical
        — and an enabled engine still lowers the identical decode
        program (speculation only swaps which program the step loop
        dispatches)."""
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.serving import ServingEngine

        texts = []
        for extra in ({}, {"speculative": {"enabled": False}},
                      {"speculative": {"num_speculative_tokens": 3}}):
            _, engine = _tiny_serving(serving={**_SERVING, **extra})
            srv = ServingEngine(engine)
            fn = srv._build_decode()
            tokens = jnp.zeros((srv.config.decode_slots, 1), jnp.int32)
            tables = jnp.zeros((srv.config.decode_slots,
                                srv.blocks_per_seq), jnp.int32)
            lengths = jnp.zeros((srv.config.decode_slots,), jnp.int32)
            lowered = fn.lower(engine.params, srv.cache, tokens, tables,
                               lengths, jax.random.PRNGKey(0))
            texts.append(lowered.compile().as_text())
            srv.destroy()
        assert texts[0] == texts[1] == texts[2]
        # feature-off serving never builds the verify program and a
        # disabled block behaves exactly like an absent one
        _, engine = _tiny_serving(
            serving={**_SERVING, "speculative": {"enabled": False}})
        srv = ServingEngine(engine)
        srv.submit(np.arange(1, 10), max_new_tokens=3)
        srv.drain()
        assert srv._verify_fn is None and srv._proposer is None
        assert srv._decode_fn is not None

    def test_draft_model_proposer_end_to_end(self):
        """The draft-model path: a second engine with the SAME params is
        a perfect draft (its full-context greedy tokens ARE the
        target's), so EVERY proposal accepts and the stream still
        bit-matches. Exercises the .generate duck-typing plumbing."""
        from deepspeed_tpu.serving import ServingEngine

        _, draft_engine = _tiny_serving(serving={"block_size": 8})
        _, engine = _tiny_serving(serving={
            **_SERVING,
            "speculative": {"proposer": "draft_model",
                            "num_speculative_tokens": 2}})
        engine.params = draft_engine.params
        srv = ServingEngine(engine, draft_model=draft_engine)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 256, n) for n in (5, 9)]
        reqs = [srv.submit(p, max_new_tokens=4) for p in prompts]
        srv.drain()
        for req, p in zip(reqs, prompts):
            assert req.state == FINISHED
            assert req.tokens == self._ref_tokens(engine, p, 4)
        st = srv.stats()["speculative"]
        assert st["proposer"] == "draft_model"
        assert st["draft_tokens"] > 0
        assert st["acceptance_rate"] == 1.0, st  # the perfect draft

    def test_chaos_seam_between_verify_and_commit_is_replayable(self):
        """A fault at the serving.spec_commit seam (the ChaosReplica
        kill point) loses the whole window — nothing was emitted, host
        state is the pre-step state, and simply stepping again produces
        the identical stream. The engine-side half of the router's
        exactly-once contract."""
        from deepspeed_tpu.runtime.resilience import chaos
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving=_SPEC)
        srv = ServingEngine(engine)
        rng = np.random.default_rng(4)
        prompt = rng.integers(1, 256, 6)
        req = srv.submit(prompt, max_new_tokens=5)
        srv.step()  # admit + prefill + first verify step
        emitted_before = list(req.tokens)
        with chaos.io_errors("serving.spec_commit", at_call=1,
                             exc=chaos.ReplicaCrashed):
            with pytest.raises(chaos.ReplicaCrashed):
                srv.step()
        # the killed window emitted nothing; its ledger windows may stay
        # open but granted nothing (worst-case reservation), so a retry
        # re-speculates from the SAME committed base
        assert req.tokens == emitted_before
        for rid, base in srv.block_mgr._spec_base.items():
            assert base == len(srv.block_mgr._owned[rid])
        srv.drain()  # retry from the same committed state
        assert req.state == FINISHED
        assert req.tokens == self._ref_tokens(engine, prompt, 5)

    def test_int8_kv_speculative_agreement(self):
        """Speculation composes with int8 paged KV: the verify program
        writes the identical quantized rows sequential decode would, so
        spec and non-spec int8 engines agree token for token."""
        from deepspeed_tpu.serving import ServingEngine

        _, engine = _tiny_serving(serving={**_SERVING,
                                           "kv_cache_dtype": "int8"})
        srv = ServingEngine(engine)
        _, engine_s = _tiny_serving(serving={**_SPEC,
                                             "kv_cache_dtype": "int8"})
        engine_s.params = engine.params
        srv_s = ServingEngine(engine_s)
        rng = np.random.default_rng(5)
        motif = rng.integers(1, 256, 4)
        prompts = [np.tile(motif, 4)[:13], rng.integers(1, 256, 7)]
        toks = srv.generate_batch(prompts, max_new_tokens=4)
        toks_s = srv_s.generate_batch(prompts, max_new_tokens=4)
        assert toks == toks_s, (toks, toks_s)


# ---------------------------------------------------------------------------
# legacy generate() bucketing satellite + zero-drift guard
# ---------------------------------------------------------------------------
@pytest.mark.heavy
class TestLegacyGenerateBucketing:
    def test_bucketed_cache_keying_and_token_parity(self):
        """Satellite: prompt lengths 5/6/7 share ONE padded bucket-8
        program (vs one each before); tokens identical to the unbucketed
        engine."""
        import jax.numpy as jnp

        _, legacy = _tiny_serving()
        _, bucketed = _tiny_serving(serving={"block_size": 8})
        bucketed.params = legacy.params
        rng = np.random.default_rng(1)
        for L in (5, 6, 7):
            p = jnp.asarray(rng.integers(1, 256, (2, L)), jnp.int32)
            a = legacy.generate(p, max_new_tokens=4)
            b = bucketed.generate(p, max_new_tokens=4)
            assert a.shape == b.shape and (a == b).all(), L
        assert len(legacy._generate_cache) == 3
        assert len(bucketed._generate_cache) == 1  # the retrace-count win
        # an exact-bucket prompt keeps the faster unpadded program
        p = jnp.asarray(rng.integers(1, 256, (2, 8)), jnp.int32)
        assert (legacy.generate(p, max_new_tokens=4)
                == bucketed.generate(p, max_new_tokens=4)).all()
        assert len(bucketed._generate_cache) == 2

    def test_bucketing_respects_model_window(self):
        """A prompt whose bucket would overflow the window keeps the
        exact-length program instead of failing."""
        import jax.numpy as jnp

        _, bucketed = _tiny_serving(serving={"block_size": 8})
        rng = np.random.default_rng(2)
        p = jnp.asarray(rng.integers(1, 256, (1, 61)), jnp.int32)
        out = bucketed.generate(p, max_new_tokens=3)  # 61→64 + 3 > 64
        assert out.shape == (1, 64)

    def test_hlo_byte_identical_without_serving_block(self):
        """Acceptance: the compiled generate program of a config WITHOUT
        a serving block is byte-identical to the same program built by a
        serving-enabled engine — the serving layer only changes dispatch
        keying, never the compiled artifact."""
        import jax
        import jax.numpy as jnp

        _, plain = _tiny_serving()
        _, served = _tiny_serving(serving={"block_size": 8})
        served.params = plain.params
        ids = jnp.asarray(np.arange(1, 9)[None], jnp.int32)
        rng = jax.random.PRNGKey(0)
        texts = []
        for eng in (plain, served):
            fn = eng._build_generate(8, 4, False, 0, 0.0, False)
            lowered = fn.lower(eng.params, ids, None, rng,
                               jnp.asarray(1.0, jnp.float32),
                               jnp.asarray(-1, jnp.int32))
            texts.append(lowered.compile().as_text())
        assert texts[0] == texts[1]

    def test_no_bucketing_when_block_absent(self):
        import jax.numpy as jnp

        _, legacy = _tiny_serving()
        rng = np.random.default_rng(3)
        for L in (5, 6, 7):
            legacy.generate(jnp.asarray(rng.integers(1, 256, (1, L)),
                                        jnp.int32), max_new_tokens=2)
        assert len(legacy._generate_cache) == 3  # one program per length

    def test_profile_model_time_deprecation_and_stream(self):
        """Satellite: use_cuda_events warns + is ignored; model_times
        entries are mirrored as telemetry ``model_time`` events."""
        import jax.numpy as jnp

        _, engine = _tiny_serving(
            telemetry={"enabled": True, "jsonl": False, "memory": False,
                       "compile_watchdog": False})
        with pytest.warns(DeprecationWarning):
            engine.profile_model_time(use_cuda_events=True)
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            engine.profile_model_time()  # bare call: no warning
        engine.forward(jnp.ones((1, 4), jnp.int32))
        engine.generate(jnp.ones((1, 4), jnp.int32), max_new_tokens=2)
        times = engine.model_times()
        assert len(times) == 2
        events = [e for e in engine.telemetry.tail(50)
                  if e["kind"] == "model_time"]
        assert [e["name"] for e in events] == ["forward", "generate"]
        assert engine.model_times() == []  # drained


# ---------------------------------------------------------------------------
# tooling: serving / prefix-cache section of the telemetry report
# ---------------------------------------------------------------------------
class TestTelemetryReportServingSection:
    def _write_events(self, tmp_path):
        from deepspeed_tpu.telemetry.events import dumps, make_event

        evs = [
            make_event("serving", "request.finish", 1, 0,
                       {"prompt_len": 20, "prefix_hit_tokens": 0,
                        "blocks_shared": 0, "prefill_chunks": 3,
                        "draft_tokens": 12, "accepted_tokens": 9,
                        "acceptance_rate": 0.75}),
            make_event("serving", "request.finish", 2, 0,
                       {"prompt_len": 20, "prefix_hit_tokens": 16,
                        "blocks_shared": 2, "prefill_chunks": 1}),
            make_event("serving", "request.shed", 3, 0,
                       {"reason": "queue_full"}),
            make_event("serving", "step.gauges", 4, 0,
                       {"free_blocks": 5, "cached_blocks": 3,
                        "queue_depth": 0}),
        ]
        path = tmp_path / "telemetry.jsonl"
        path.write_text("\n".join(dumps(e) for e in evs) + "\n")
        return str(path)

    def test_aggregate_and_render(self, tmp_path):
        from tools.telemetry_report import aggregate, render

        from deepspeed_tpu.telemetry.events import load_events

        path = self._write_events(tmp_path)
        agg = aggregate(load_events(path))["serving"]
        assert agg["finished"] == 2 and agg["shed"] == 1
        assert agg["prefix_hit_tokens"] == 16
        assert agg["prompt_tokens"] == 40
        assert agg["hit_requests"] == 1
        assert agg["blocks_shared"] == 2
        assert agg["prefill_chunks"] == 4
        assert agg["last_gauges"]["cached_blocks"] == 3
        # speculation column: drafts/accepted roll up, speculating
        # requests are counted apart from non-speculating ones
        assert agg["draft_tokens"] == 12 and agg["accepted_tokens"] == 9
        assert agg["spec_requests"] == 1
        text = render(path)
        assert "serving: 2 finished, 1 shed, 4 prefill chunks" in text
        assert "1/2 requests hit" in text
        assert "16/40 prompt tokens served from cache (40.0%)" in text
        assert "speculation: 1/2 requests speculated, " \
            "9/12 draft tokens accepted (75.0%)" in text
        assert "5 free blocks, 3 cached" in text
        md = render(path, markdown=True)
        assert "### serving:" in md
        assert "draft tokens accepted" in md
        import json as _json
        from tools.telemetry_report import aggregate as _agg

        from deepspeed_tpu.telemetry.events import load_events as _load

        payload = _json.loads(_json.dumps(_agg(_load(path))["serving"]))
        assert payload["draft_tokens"] == 12  # --json carries the column

    def test_empty_stream_renders_no_serving_section(self, tmp_path):
        from tools.telemetry_report import render

        path = tmp_path / "telemetry.jsonl"
        path.write_text("")
        assert "prefix cache" not in render(str(path))
