"""Paged KV-cache gate (DESIGN.md §8): the dense-equivalence differential
harness plus allocator property tests.

Three tiers:

* ``BlockAllocator`` unit + property tests — freelist/refcount/reservation
  invariants under arbitrary admit/grow/release interleavings (hypothesis
  when installed, a deterministic randomized sweep always);
* ``PagedCachePool`` park/restore — raw round-trips bit-exact into fresh
  pages, int8 parking is idempotent after the first lossy pass;
* the engine differential: a paged ``ServeEngine`` must produce tokens
  and (recorded) mixture logprobs equal to the DENSE engine — the oracle
  pinned against the sequential reference elsewhere — across block sizes,
  ragged prompt lengths, prefix-share patterns, EOS/budget slot recycling,
  mid-batch page reuse, and (in the multidevice child) a sharded mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import util

from repro import configs
from repro.models import get_model, init_params
from repro.serve.engine import (
    BlockAllocator,
    PagedCachePool,
    Request,
    ServeEngine,
    synthetic_trace,
)
from repro.serve.sampling import SamplingParams

given, settings, st = util.import_hypothesis()


def tiny_cfg():
    return configs.get_config("qwen3-0.6b", smoke=True).replace(
        vocab_size=64, d_model=32, num_layers=2, num_heads=2, num_kv_heads=1,
        head_dim=16, d_ff=48,
    )


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    model = get_model(cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    members = jax.vmap(lambda k: init_params(model.param_specs(cfg), k))(keys)
    return cfg, model, members


def _requests(lens, max_new=5, stagger=1, vocab=64, seed=0, shared_every=0):
    """Ragged request list; every ``shared_every``-th request reuses the
    first prompt of its length (prefix-share pattern)."""
    rng = np.random.default_rng(seed)
    first: dict[int, np.ndarray] = {}
    reqs = []
    for i, L in enumerate(lens):
        p = rng.integers(0, vocab, size=int(L)).astype(np.int32)
        if L not in first:
            first[L] = p
        elif shared_every and i % shared_every == 0:
            p = first[L].copy()
        reqs.append(Request(rid=i, prompt=p, max_new=max_new,
                            arrival_step=i * stagger))
    return reqs


def _run(cfg, model, members, reqs, **kw):
    eng = ServeEngine(cfg, model, members, record_logprobs=True, **kw)
    rep = eng.run([Request(r.rid, r.prompt.copy(), r.max_new, r.arrival_step)
                   for r in reqs])
    return eng, rep


def _assert_equal_reports(dense, paged, atol=2e-5):
    assert len(dense.results) == len(paged.results)
    for a, b in zip(dense.results, paged.results):
        assert a.rid == b.rid
        np.testing.assert_array_equal(a.tokens, b.tokens, err_msg=f"rid {a.rid}")
        assert a.hit_eos == b.hit_eos and a.truncated == b.truncated
        np.testing.assert_allclose(a.logprobs, b.logprobs, atol=atol,
                                   err_msg=f"rid {a.rid}")


# ---------------------------------------------------------------------------
# BlockAllocator
# ---------------------------------------------------------------------------


class TestBlockAllocator:
    def _alloc(self, **kw):
        base = dict(num_blocks=17, block_size=4, max_seq=32, num_slots=4)
        base.update(kw)
        return BlockAllocator(**base)

    def test_sink_reserved_and_conservation(self):
        a = self._alloc()
        assert a.free_blocks == 16  # page 0 excluded
        row = a.admit(0, np.arange(6, dtype=np.int32), 4)
        assert 0 not in row[row != 0]
        a.check()
        a.release(0)
        assert a.free_blocks == 16
        a.check()

    def test_admit_maps_prompt_blocks_and_reserves_growth(self):
        a = self._alloc()
        a.admit(0, np.arange(6, dtype=np.int32), 8)  # 2 blocks now
        assert int((a.tables[0] != 0).sum()) == 2
        assert a.ctx[0] == 6
        # worst case 6 + 8 - 1 = 13 positions -> 4 blocks, 2 reserved
        assert a.reserved_blocks == 2
        a.check()

    def test_ensure_decode_block_draws_down_reservation(self):
        a = self._alloc()
        a.admit(0, np.arange(4, dtype=np.int32), 5)  # ctx = 4 (block boundary)
        used0, res0 = a.used_blocks, a.reserved_blocks
        a.ensure_decode_block(0)  # position 4 -> new block
        assert a.used_blocks == used0 + 1 and a.reserved_blocks == res0 - 1
        a.ensure_decode_block(0)  # idempotent: same block
        assert a.used_blocks == used0 + 1
        a.check()

    def test_admission_gate_is_exhaustion_proof(self):
        """Every request that passes can_admit decodes to its full max_new
        without ever raising pool-exhausted — the reservation accounting
        charges worst-case growth up front."""
        a = self._alloc(num_blocks=9)  # 8 usable pages, tight
        rng = np.random.default_rng(0)
        live = {}
        admitted = rejected = 0
        for i in range(40):
            if live and rng.random() < 0.4:
                slot = rng.choice(list(live))
                for _ in range(live.pop(slot)):
                    a.ensure_decode_block(slot)
                    a.advance(slot)
                a.release(slot)
            else:
                slot = next((s for s in range(4) if s not in live), None)
                plen, mn = int(rng.integers(1, 9)), int(rng.integers(1, 8))
                if slot is None or not a.can_admit(np.arange(plen), mn):
                    rejected += 1
                    continue
                a.admit(slot, np.arange(plen, dtype=np.int32), mn)
                live[slot] = mn
                admitted += 1
            a.check()
        assert admitted and rejected  # the gate actually bit both ways

    def test_prefix_sharing_refcounts(self):
        a = self._alloc()
        prompt = np.arange(8, dtype=np.int32)  # 2 full blocks
        r0 = a.admit(0, prompt, 4)
        r1 = a.admit(1, prompt.copy(), 4)
        np.testing.assert_array_equal(r0[:2], r1[:2])  # shared pages
        assert a.prefix_hits == 1
        assert all(a.refcount[b] == 2 for b in r0[:2])
        a.release(0)
        assert all(a.refcount[b] == 1 for b in r1[:2])  # survivor keeps them
        a.check()
        a.release(1)
        assert a.free_blocks == 16
        a.check()

    def test_partial_tail_block_not_shared(self):
        a = self._alloc()
        prompt = np.arange(6, dtype=np.int32)  # 1 full + 1 partial block
        r0 = a.admit(0, prompt, 4)
        r1 = a.admit(1, prompt.copy(), 4)
        assert r0[0] == r1[0] and r0[1] != r1[1]
        a.check()

    def test_prefix_entry_dies_with_last_sharer(self):
        a = self._alloc()
        prompt = np.arange(4, dtype=np.int32)
        a.admit(0, prompt, 2)
        a.release(0)
        r1 = a.admit(1, prompt.copy(), 2)  # entry gone -> fresh pages, no hit
        assert a.prefix_hits == 0 and a.prefix_queries == 2
        assert a.refcount[r1[0]] == 1
        a.check()

    def test_sharing_disabled(self):
        a = self._alloc(prefix_sharing=False)
        prompt = np.arange(8, dtype=np.int32)
        r0, r1 = a.admit(0, prompt, 2), a.admit(1, prompt.copy(), 2)
        assert not set(r0[r0 != 0]) & set(r1[r1 != 0])
        assert a.prefix_queries == 0
        a.check()

    def test_version_isolates_prefix_keys(self):
        a = self._alloc()
        prompt = np.arange(8, dtype=np.int32)
        r0 = a.admit(0, prompt, 2, version=0)
        r1 = a.admit(1, prompt.copy(), 2, version=1)  # refreshed members
        assert not set(r0[:2]) & set(r1[:2])
        a.check()

    def test_invalidate_version_drops_stale_entries(self):
        """Promotion-time eager invalidation (the engine calls this on every
        registry version bump): superseded entries vanish immediately, a
        same-prompt re-admit at the old version misses, live sharers keep
        their pages and free them exactly once."""
        a = self._alloc()
        prompt = np.arange(8, dtype=np.int32)  # 2 full blocks
        r0 = a.admit(0, prompt, 2, version=0)
        a.admit(1, prompt.copy(), 2, version=0)  # sharer of the v0 entry
        assert a.prefix_hits == 1
        dropped = a.invalidate_version(1)
        assert dropped == 1 and a.prefix_invalidated == 1
        assert not a._prefix and not a._block_prefix  # no stale residue
        a.check()
        # a v0 re-admit can no longer hit the dead entry
        r2 = a.admit(2, prompt.copy(), 2, version=0)
        assert a.prefix_hits == 1  # still just the pre-invalidation hit
        assert not set(r0[:2]) & set(r2[:2])
        # sharers of the invalidated entry still refcount their pages...
        assert all(a.refcount[b] == 2 for b in r0[:2])
        a.release(0)
        assert all(a.refcount[b] == 1 for b in r0[:2])
        a.check()
        # ...and the pages are freed exactly once, by the last sharer
        a.release(1)
        a.release(2)
        assert a.free_blocks == 16
        a.check()
        # invalidating the current version's own entries is a no-op
        a.admit(0, prompt.copy(), 2, version=1)
        assert a.invalidate_version(1) == 0
        a.check()

    def test_oversized_request_refused(self):
        a = self._alloc()
        assert not a.can_admit(np.arange(30), 8)  # 37 positions > max_seq
        with pytest.raises(ValueError, match="blocks_per_slot"):
            a.admit(0, np.arange(30, dtype=np.int32), 8)

    def test_double_admit_and_bad_release(self):
        a = self._alloc()
        a.admit(0, np.arange(4, dtype=np.int32), 2)
        with pytest.raises(ValueError, match="already admitted"):
            a.admit(0, np.arange(4, dtype=np.int32), 2)
        with pytest.raises(ValueError, match="non-admitted"):
            a.release(3)


class TestAllocatorProperties:
    """Arbitrary operation interleavings preserve every invariant in
    ``BlockAllocator.check``.  The hypothesis variant explores adversarial
    schedules; the deterministic sweep below always runs (tests/util.py
    convention — property modules must not vanish without hypothesis)."""

    @staticmethod
    def _interleave(a: BlockAllocator, ops, lens, max_news):
        """ops: ints; even -> try admit, odd -> advance-or-release."""
        live: dict[int, int] = {}
        for k, op in enumerate(ops):
            if op % 2 == 0:
                slot = next((s for s in range(a.num_slots) if s not in live), None)
                plen = lens[k % len(lens)]
                mn = max_news[k % len(max_news)]
                if slot is not None and a.can_admit(np.arange(plen), mn):
                    a.admit(slot, np.arange(plen, dtype=np.int32), mn)
                    # admit emits the first token; max_new - 1 decode
                    # writes follow, which is what the reservation covers
                    live[slot] = mn - 1
            elif live:
                slot = sorted(live)[op % len(live)]
                if live[slot] > 0 and op % 3:
                    a.ensure_decode_block(slot)
                    a.advance(slot)
                    live[slot] -= 1
                else:
                    a.release(slot)
                    del live[slot]
            a.check()
        for slot in list(live):
            a.release(slot)
        a.check()
        assert a.free_blocks == a.num_blocks - 1  # everything returned

    def test_deterministic_interleavings(self):
        rng = np.random.default_rng(7)
        for trial in range(8):
            a = BlockAllocator(
                num_blocks=int(rng.integers(5, 20)), block_size=int(rng.integers(1, 6)),
                max_seq=16, num_slots=int(rng.integers(1, 5)),
                prefix_sharing=bool(trial % 2),
            )
            self._interleave(
                a, rng.integers(0, 100, size=30).tolist(),
                lens=[1, 3, 4, 8], max_news=[1, 2, 5],
            )

    @given(
        ops=st.lists(st.integers(0, 99), min_size=1, max_size=60),
        num_blocks=st.integers(3, 24),
        block_size=st.integers(1, 5),
        num_slots=st.integers(1, 5),
        sharing=st.booleans(),
    )
    @settings(max_examples=60, deadline=None)
    def test_hypothesis_interleavings(self, ops, num_blocks, block_size,
                                      num_slots, sharing):
        a = BlockAllocator(num_blocks=num_blocks, block_size=block_size,
                           max_seq=16, num_slots=num_slots,
                           prefix_sharing=sharing)
        self._interleave(a, ops, lens=[1, 2, 5, 8], max_news=[1, 3, 6])


# ---------------------------------------------------------------------------
# PagedCachePool park / restore
# ---------------------------------------------------------------------------


class TestPagedCachePool:
    def _pool(self, setup, **kw):
        cfg, model, _ = setup
        return PagedCachePool(cfg, model, num_members=2, num_slots=2,
                              max_seq=32, block_size=8, **kw)

    def _fill_random(self, pool, seed=7):
        pool.caches = jax.tree.map(
            lambda x: jax.random.normal(jax.random.PRNGKey(seed), x.shape, x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            pool.caches,
        )

    @staticmethod
    def _gather(pool, slot):
        """One slot's pages in LOGICAL block order (restore relocates)."""
        row = pool.tables[slot]
        idx = jnp.asarray(row[row != 0], jnp.int32)
        return jax.tree.map(
            lambda leaf: np.asarray(jnp.take(leaf, idx, axis=leaf.ndim - 4)),
            pool.caches,
        )

    def test_raw_roundtrip_bit_exact(self, setup):
        pool = self._pool(setup)
        slot = pool.acquire()
        pool.admit_blocks(slot, np.arange(9, dtype=np.int32), 4)
        self._fill_random(pool)
        before = self._gather(pool, slot)
        parked = pool.park(slot)
        assert pool.active_slots == 0 and pool.alloc.used_blocks == 0
        slot2 = pool.restore(parked, max_new=4)
        pool.alloc.check()
        assert pool.alloc.ctx[slot2] == 9
        after = self._gather(pool, slot2)
        jax.tree.map(np.testing.assert_array_equal, before, after)

    def test_int8_roundtrip_idempotent(self, setup):
        pool = self._pool(setup, compress_parked=True)
        slot = pool.acquire()
        pool.admit_blocks(slot, np.arange(9, dtype=np.int32), 4)
        self._fill_random(pool)
        orig = self._gather(pool, slot)
        slot = pool.restore(pool.park(slot), max_new=4)
        once = self._gather(pool, slot)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=0.05), orig, once
        )
        slot = pool.restore(pool.park(slot), max_new=4)
        twice = self._gather(pool, slot)
        # second lossy pass lands on the same quantization points: bit-exact
        jax.tree.map(np.testing.assert_array_equal, once, twice)

    def test_restore_reserves_remaining_growth(self, setup):
        pool = self._pool(setup)
        slot = pool.acquire()
        pool.admit_blocks(slot, np.arange(8, dtype=np.int32), 9)  # 1 block now
        parked = pool.park(slot)
        slot2 = pool.restore(parked, max_new=9)
        # 8 + 9 - 1 = 16 positions -> 2 blocks total, 1 held, 1 re-reserved
        assert pool.alloc._reserved[slot2] == 1
        pool.alloc.check()

    def test_stats_report_paged_memory(self, setup):
        pool = self._pool(setup)
        slot = pool.acquire()
        pool.admit_blocks(slot, np.arange(16, dtype=np.int32), 2)
        s = pool.stats()
        assert s["paged"] and s["bytes_per_page"] > 0
        assert s["bytes_used"] == s["blocks_used"] * s["bytes_per_page"]
        assert s["bytes_high_water"] >= s["bytes_used"]
        assert s["bytes_total"] == (s["num_blocks"] - 1) * s["bytes_per_page"]

    def test_unsupported_model_refused(self, setup):
        cfg, model, _ = setup
        import dataclasses

        windowed = cfg.replace(pattern=(dataclasses.replace(cfg.pattern[0], window=8),))
        with pytest.raises(ValueError, match="sliding-window"):
            PagedCachePool(windowed, model, num_members=1, num_slots=1,
                           max_seq=16, block_size=8)


# ---------------------------------------------------------------------------
# engine differential: paged == dense
# ---------------------------------------------------------------------------


class TestPagedEngineDifferential:
    @pytest.mark.parametrize("block_size", [4, 8, 16])
    def test_ragged_lengths_match_dense(self, setup, block_size):
        cfg, model, members = setup
        reqs = _requests((3, 8, 5, 13, 16, 7), max_new=6, stagger=1, seed=1)
        _, dense = _run(cfg, model, members, reqs, num_slots=2, max_seq=32)
        eng, paged = _run(cfg, model, members, reqs, num_slots=2, max_seq=32,
                          paged=True, block_size=block_size)
        _assert_equal_reports(dense, paged)
        assert eng.decode_trace_count == 1, paged.trace_counts
        eng.pool.alloc.check()
        assert eng.pool.alloc.used_blocks == 0  # all pages returned

    @pytest.mark.parametrize("sharing", [True, False])
    def test_prefix_share_patterns_match_dense(self, setup, sharing):
        cfg, model, members = setup
        # every other request repeats an earlier prompt -> live page sharing
        reqs = _requests((8, 8, 16, 8, 16, 8), max_new=5, stagger=1, seed=2,
                         shared_every=2)
        _, dense = _run(cfg, model, members, reqs, num_slots=3, max_seq=32)
        eng, paged = _run(cfg, model, members, reqs, num_slots=3, max_seq=32,
                          paged=True, block_size=8, prefix_sharing=sharing)
        _assert_equal_reports(dense, paged)
        st = eng.pool.stats()
        if sharing:
            assert st["prefix_hits"] > 0  # the pattern actually shared
        else:
            assert st["prefix_queries"] == 0
        eng.pool.alloc.check()

    def test_eos_recycling_matches_dense(self, setup):
        """Slots finish at different ticks (EOS + ragged budgets), freeing
        pages that later admissions reuse mid-batch."""
        cfg, model, members = setup
        reqs = _requests((5, 9, 4, 12, 6, 8, 10), max_new=7, stagger=2, seed=3)
        kw = dict(num_slots=2, max_seq=32, eos_id=3)
        _, dense = _run(cfg, model, members, reqs, **kw)
        eng, paged = _run(cfg, model, members, reqs, paged=True, block_size=4, **kw)
        _assert_equal_reports(dense, paged)
        assert eng.decode_trace_count == 1

    # pools of two requests' worst-case growth, for three slots
    @pytest.mark.parametrize("block_size, num_blocks", [(4, 10), (8, 6)])
    def test_kernel_path_matches_gather_path(self, setup, block_size, num_blocks):
        """The paged Pallas kernel, what TPU serving runs (here interpreted,
        through ``use_flash_kernel``), against the gather path: a ragged,
        staggered trace whose slots finish at different ticks (EOS, ragged
        budgets) and free pages that a tight pool hands to later admissions
        mid-batch; done and free slots read no page."""
        cfg, model, members = setup
        reqs = _requests((5, 9, 4, 12, 6, 8, 10, 3), max_new=7, stagger=2, seed=12)
        reqs = [Request(r.rid, r.prompt, 4 + r.rid % 4, r.arrival_step) for r in reqs]
        kw = dict(num_slots=3, max_seq=32, eos_id=3, paged=True, block_size=block_size,
                  num_blocks=num_blocks)
        _, gather = _run(cfg, model, members, reqs, **kw)
        eng, kernel = _run(cfg.replace(use_flash_kernel=True), model, members, reqs, **kw)
        _assert_equal_reports(gather, kernel)
        assert eng.decode_trace_count == 1
        assert eng.page_waits > 0  # the pool was tight: pages were reused
        eng.pool.alloc.check()
        assert eng.pool.alloc.used_blocks == 0

    def test_tight_pool_defers_admission_but_completes(self, setup):
        """A page pool too small for all slots at once: head-of-line waits
        for completions, every request still finishes, and the admission
        gate never lets decode hit pool exhaustion."""
        cfg, model, members = setup
        reqs = _requests((8, 8, 8, 8), max_new=5, stagger=0, seed=4)
        # 7 usable pages; each request needs 3 worst-case -> 2 concurrent max
        eng, paged = _run(cfg, model, members, reqs, num_slots=3, max_seq=32,
                          paged=True, block_size=4, num_blocks=8)
        assert sorted(r.rid for r in paged.results) == [0, 1, 2, 3]
        assert all(r.num_tokens == 5 for r in paged.results)
        _, dense = _run(cfg, model, members, reqs, num_slots=3, max_seq=32)
        for a, b in zip(dense.results, paged.results):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        eng.pool.alloc.check()

    def test_impossible_request_raises_not_deadlocks(self, setup):
        cfg, model, members = setup
        reqs = _requests((8,), max_new=5)
        eng = ServeEngine(cfg, model, members, num_slots=2, max_seq=32,
                          paged=True, block_size=4, num_blocks=3)
        with pytest.raises(ValueError, match="can never fit"):
            eng.run(reqs)

    def test_truncation_recycles_pages(self, setup):
        cfg, model, members = setup
        reqs = _requests((6, 11), max_new=10, stagger=0, seed=5)
        eng, rep = _run(cfg, model, members, reqs, num_slots=2, max_seq=32,
                        paged=True, block_size=8)
        # rerun with a hard step cap: in-flight requests truncate, pages free
        eng2 = ServeEngine(cfg, model, members, num_slots=2, max_seq=32,
                           paged=True, block_size=8, record_logprobs=True)
        rep2 = eng2.run([Request(r.rid, r.prompt.copy(), r.max_new, r.arrival_step)
                         for r in reqs], max_steps=4)
        assert all(r.truncated for r in rep2.results)
        assert eng2.pool.alloc.used_blocks == 0
        eng2.pool.alloc.check()
        # truncated prefixes match the untruncated run (same tokens early on)
        by_rid = {r.rid: r for r in rep.results}
        for r in rep2.results:
            np.testing.assert_array_equal(r.tokens, by_rid[r.rid].tokens[: r.num_tokens])

    def test_recycled_blocks_mid_batch_regression(self, setup):
        """Satellite regression: a done slot keeps computing until its slot
        is re-admitted, and its garbage decode writes MUST land in the sink
        page — not in pages recycled to a still-live request.  A tiny pool
        forces immediate reuse of freed pages while the other slot decodes."""
        cfg, model, members = setup
        reqs = _requests((4, 8, 4, 4), max_new=(3), stagger=0, seed=6)
        reqs = [Request(r.rid, r.prompt, 3 + 2 * (r.rid % 2), r.arrival_step)
                for r in reqs]
        kw = dict(num_slots=2, max_seq=16)
        _, dense = _run(cfg, model, members, reqs, **kw)
        eng, paged = _run(cfg, model, members, reqs, paged=True, block_size=4,
                          num_blocks=9, **kw)
        _assert_equal_reports(dense, paged)
        eng.pool.alloc.check()

    def test_sampled_fused_select_matches_unfused(self, setup):
        """The fused mixture+selection kernel is a drop-in: identical token
        draws (Gumbel-argmax identity, same key) on the paged engine."""
        cfg, model, members = setup
        reqs = _requests((7, 13, 9, 16), max_new=5, stagger=2, seed=8)
        sp = SamplingParams(temperature=0.9, top_k=8)
        kw = dict(num_slots=2, max_seq=32, paged=True, block_size=8,
                  sampling=sp, seed=11)
        _, a = _run(cfg, model, members, reqs, fused_select=False, **kw)
        _, b = _run(cfg, model, members, reqs, fused_select=True, **kw)
        for x, y in zip(a.results, b.results):
            np.testing.assert_array_equal(x.tokens, y.tokens)
            np.testing.assert_allclose(x.logprobs, y.logprobs, atol=1e-5)

    def test_paged_memory_beats_dense_at_equal_tokens(self, setup):
        """The acceptance axis the bench records: for the same trace, the
        paged pool's high-water bytes stay below the dense pool's static
        footprint (which pays max_seq for every slot up front)."""
        cfg, model, members = setup
        reqs = _requests((8, 8, 8, 8, 8, 8), max_new=4, stagger=1, seed=9,
                         shared_every=2)
        deng, dense = _run(cfg, model, members, reqs, num_slots=3, max_seq=32)
        peng, paged = _run(cfg, model, members, reqs, num_slots=3, max_seq=32,
                           paged=True, block_size=8)
        assert dense.total_tokens == paged.total_tokens
        dense_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(deng.pool.caches)
        )
        assert peng.pool.stats()["bytes_high_water"] < dense_bytes


# ---------------------------------------------------------------------------
# mesh-sharded paged engine (multidevice child only)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
class TestShardedPagedServeEngine:
    """DESIGN.md §7 x §8: the paged engine under a device mesh — tokens
    identical to the unsharded paged run (itself pinned to dense above),
    and still exactly one compiled decode program across block-table churn."""

    def test_mesh_paged_matches_unsharded_one_program(self, setup):
        util.require_devices(util.MULTIDEVICE_DEVICES)
        from repro.launch.mesh import make_engine_mesh

        cfg, model, members = setup
        reqs = _requests((5, 9, 7, 12, 6), max_new=5, stagger=1, seed=10)
        kw = dict(num_slots=2, max_seq=32, paged=True, block_size=8)
        _, rep0 = _run(cfg, model, members, reqs, **kw)
        eng, rep1 = _run(cfg, model, members, reqs,
                         mesh=make_engine_mesh(2, 4), **kw)
        assert eng.decode_trace_count == 1, rep1.trace_counts
        _assert_equal_reports(rep0, rep1)
        eng.pool.alloc.check()

    def test_mesh_paged_keeps_the_gather_path(self, setup):
        """Where the kernel path is on, the member-sharded pools of a mesh
        keep the gather path (the kernel is a custom call GSPMD cannot
        partition): one compiled decode program, no kernel in it, and
        tokens equal to the unsharded run on the kernel."""
        util.require_devices(util.MULTIDEVICE_DEVICES)
        from repro.launch.mesh import make_engine_mesh

        cfg, model, members = setup
        kcfg = cfg.replace(use_flash_kernel=True)
        reqs = _requests((5, 9, 7, 12, 6), max_new=5, stagger=1, seed=10)
        kw = dict(num_slots=2, max_seq=32, paged=True, block_size=8)
        _, rep0 = _run(kcfg, model, members, reqs, **kw)
        eng, rep1 = _run(kcfg, model, members, reqs,
                         mesh=make_engine_mesh(2, 4), **kw)
        assert eng.decode_trace_count == 1, rep1.trace_counts
        _assert_equal_reports(rep0, rep1)
        tables = np.asarray(eng.pool.tables)
        args = (eng._members(), eng.pool.caches, eng._tokens, eng._done, eng._budget,
                jnp.asarray(tables), jnp.asarray(eng.pool.ctx), jax.random.PRNGKey(0))
        assert "pallas_call" not in str(jax.make_jaxpr(eng._decode_paged_fn)(*args))
        eng.pool.alloc.check()
