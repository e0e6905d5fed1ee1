"""Paged KV block pool + radix prefix index (DESIGN.md §15).

Quick tier. The invariants the prefix-reuse path leans on:

  - pool accounting is exact: LIFO allocation is deterministic, every
    release returns blocks at refcount zero, stale (pre-reset) handles
    no-op, and ``check_no_leaks`` catches both directions of drift;
  - the data plane round-trips bitwise: ``publish`` then ``gather_blocks``
    reproduces the source cache row's bytes (cache dtype == pool dtype,
    so a pooled key IS the key a dense prefill would recompute);
  - the Pallas scalar-prefetch gather equals the ``jnp.take`` oracle —
    data movement, nothing to drift;
  - the radix index keeps paths complete prefixes, evicts LRU
    unreferenced leaves only, and its checkpoint restore rebuilds the
    pool's accounting to exactly one ref per node.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.core import lm_skiplora as SL
from repro.core.kv_pool import (
    DEFAULT_BLOCK,
    KVBlockPool,
    KVPoolExhausted,
    gather_blocks,
    get_default_block,
    set_default_block,
)
from repro.core.prefix_index import RadixPrefixIndex
from repro.core.runtime import SessionRuntime
from repro.kernels.flash_attn.paged import paged_gather, paged_gather_ref
from repro.models.lm import init_lm, init_serve_caches


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config("stablelm-1.6b"))


def fill_random(tree, seed=0):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, l.shape).astype(l.dtype)
        for k, l in zip(keys, leaves)
    ])


def toks(n, seed=0, vocab=50):
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(
        np.int32
    )


class TestPoolAccounting:
    def test_alloc_is_deterministic_lifo(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=6, block=4)
        assert pool.alloc(2) == [0, 1] and pool.alloc(1) == [2]
        pool.deref([1])
        assert pool.alloc(1) == [1]          # freed block reused first
        pool.check_no_leaks(3)

    def test_exhaustion_raises_and_leaves_state_intact(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=2, block=4)
        pool.alloc(1)
        with pytest.raises(KVPoolExhausted):
            pool.alloc(2)
        assert pool.n_free() == 1            # the failed alloc took nothing
        pool.alloc(1)
        pool.check_no_leaks(2)

    def test_ref_and_deref_guard_free_blocks(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=2, block=4)
        with pytest.raises(RuntimeError, match="unallocated"):
            pool.ref([0])
        ids = pool.alloc(1)
        pool.ref(ids)
        pool.deref(ids)
        pool.deref(ids)                      # back to free now
        with pytest.raises(RuntimeError, match="deref of free"):
            pool.deref(ids)

    def test_check_no_leaks_catches_held_count_drift(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=2, block=4)
        pool.alloc(1)
        with pytest.raises(RuntimeError, match="leak"):
            pool.check_no_leaks(0)

    def test_stale_generation_release_noops(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=2, block=4)
        ids, gen = pool.alloc(1), pool.generation
        pool.reset()
        pool.deref(ids, generation=gen)      # handle predates the reset
        assert pool.counters["stale_release"] == 1
        pool.check_no_leaks(0)


class TestPoolDataPlane:
    def test_publish_then_gather_roundtrips_bitwise(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=8, block=4)
        caches = fill_random(init_serve_caches(cfg, 2, 8), seed=1)
        ids = pool.alloc(2)
        pool.publish(caches, 1, ids, [0, 1])
        tables = jnp.asarray([ids], jnp.int32)
        out = gather_blocks(pool.data, tables, block=4)
        for got, src in zip(jax.tree.leaves(out), jax.tree.leaves(caches)):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(src[..., 1:2, 0:8, :, :])
            )
        # the serve-path kernel routing must agree (oracle off-TPU)
        kout = gather_blocks(pool.data, tables, block=4, use_kernel=True)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(kout)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_pallas_gather_interpret_matches_oracle(self):
        key = jax.random.key(3)
        pool = jax.random.normal(key, (6, 4, 2, 8), jnp.float32)
        tables = jnp.asarray([[3, 0, 5], [1, 1, 2]], jnp.int32)
        ref = paged_gather_ref(pool, tables)
        out = paged_gather(pool, tables, interpret=True)
        assert ref.shape == (2, 12, 2, 8)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_copy_block_cow(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=4, block=4)
        caches = fill_random(init_serve_caches(cfg, 1, 4), seed=2)
        src = pool.alloc(1)[0]
        pool.publish(caches, 0, [src], [0])
        assert pool.copy_block(src) == src   # exclusive: no copy
        pool.ref([src])                      # now shared
        dst = pool.copy_block(src)
        assert dst != src
        assert pool.refs[src] == 1 and pool.refs[dst] == 1  # ref moved
        assert pool.counters["cow_copies"] == 1
        for leaf in jax.tree.leaves(pool.data):
            np.testing.assert_array_equal(
                np.asarray(jnp.take(leaf, src, axis=-4)),
                np.asarray(jnp.take(leaf, dst, axis=-4)),
            )

    def test_loaded_twin_survives_source_publish(self, cfg):
        # publish donates the data plane, so ``load_state`` must copy what
        # it is given: a later publish into the source pool may neither
        # delete nor change the twin's blocks.
        pool = KVBlockPool(cfg, n_blocks=4, block=4)
        ids = pool.alloc(1)
        pool.publish(fill_random(init_serve_caches(cfg, 1, 4), seed=4), 0,
                     ids, [0])
        twin = KVBlockPool(cfg, n_blocks=4, block=4)
        twin.load_state(pool.state_arrays(), pool.state_meta())
        before = [np.asarray(x) for x in jax.tree.leaves(twin.data)]
        pool.publish(fill_random(init_serve_caches(cfg, 1, 4), seed=5), 0,
                     ids, [0])
        for got, want in zip(jax.tree.leaves(twin.data), before):
            np.testing.assert_array_equal(np.asarray(got), want)

    def test_load_state_rejects_geometry_mismatch(self, cfg):
        pool = KVBlockPool(cfg, n_blocks=4, block=4)
        other = KVBlockPool(cfg, n_blocks=2, block=4)
        with pytest.raises(ValueError, match="identically-sized"):
            other.load_state(pool.state_arrays(), pool.state_meta())


class TestRadixIndex:
    def test_match_insert_and_tail_token_cap(self, cfg):
        idx = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=8, block=4))
        t = toks(10, seed=4)
        assert idx.match("a", t) == []
        created = idx.insert("a", t)         # 2 full blocks of 10 tokens
        assert [slot for _, slot in created] == [0, 1]
        assert idx.match("a", t) == [bid for bid, _ in created]
        # exact-multiple prompt: the last block is capped out so >= 1
        # tail token survives for the tail prefill
        assert idx.match("a", t[:8]) == [created[0][0]]
        assert idx.match("b", t) == []       # tenant-scoped
        idx.pool.check_no_leaks(idx.n_nodes())

    def test_insert_dedupes_shared_prefix(self, cfg):
        idx = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=8, block=4))
        shared = toks(8, seed=5)
        a = np.concatenate([shared, toks(4, seed=6)])
        b = np.concatenate([shared, toks(4, seed=7)])
        idx.insert("t", a)
        created = idx.insert("t", b)         # only b's distinct tail block
        assert [slot for _, slot in created] == [2]
        assert idx.n_nodes() == 4

    def test_lru_eviction_skips_referenced_blocks(self, cfg):
        idx = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=2, block=4))
        a, b = toks(5, seed=8), toks(5, seed=9)
        (bid_a, _), = idx.insert("t", a)
        (bid_b, _), = idx.insert("t", b)
        handle = idx.acquire([bid_a])        # in-flight pin on a
        idx.match("t", a)                    # and a is also most recent
        c = toks(5, seed=10)
        created = idx.insert("t", c)         # pool full: must evict b
        assert [bid for bid, _ in created] == [bid_b]
        assert idx.match("t", b) == [] and idx.match("t", a) == [bid_a]
        # every block pinned: nothing evictable -> insert stops cleanly
        # (d's first block dedupes onto a's node, its second can't alloc)
        pin_c = idx.acquire([bid for bid, _ in created])
        d = np.concatenate([a[:4], toks(5, seed=11)])
        assert idx.insert("t", d) == []
        assert idx.counters["insert_stopped"] == 1
        idx.release(handle)
        idx.release(pin_c)
        idx.pool.check_no_leaks(idx.n_nodes())

    def test_drop_tenant_releases_only_that_scope(self, cfg):
        idx = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=8, block=4))
        idx.insert("a", toks(8, seed=12))
        idx.insert("b", toks(8, seed=13))
        assert idx.drop_tenant("a") == 2
        assert idx.match("a", toks(8, seed=12)) == []
        assert len(idx.match("b", toks(9, seed=13)[:9])) >= 1
        idx.pool.check_no_leaks(idx.n_nodes())

    def test_reset_makes_outstanding_handles_stale(self, cfg):
        idx = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=4, block=4))
        (bid, _), = idx.insert("t", toks(5, seed=14))
        handle = idx.acquire([bid])
        idx.reset()
        idx.release(handle)                  # stale: must not corrupt refs
        assert idx.pool.counters["stale_release"] == 1
        idx.pool.check_no_leaks(0)

    def test_state_roundtrip_rebuilds_refs_exactly(self, cfg):
        idx = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=8, block=4))
        shared = toks(8, seed=15)
        a = np.concatenate([shared, toks(4, seed=16)])
        b = np.concatenate([shared, toks(4, seed=17)])
        idx.insert("t", a)
        idx.insert("u", b)
        idx2 = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=8, block=4))
        idx2.load_state(idx.state())
        assert idx2.match("t", a) == idx.match("t", a)
        assert idx2.match("u", b) == idx.match("u", b)
        assert idx2.n_nodes() == idx.n_nodes()
        idx2.pool.check_no_leaks(idx2.n_nodes())

    def test_load_state_rejects_orphans_and_ragged_paths(self, cfg):
        idx = RadixPrefixIndex(KVBlockPool(cfg, n_blocks=8, block=4))
        orphan = [{"tenant": "t", "tokens": list(range(8)), "block": 0,
                   "used": 1}]              # 2-block path with no parent
        with pytest.raises(ValueError, match="before its parent"):
            idx.load_state(orphan)
        ragged = [{"tenant": "t", "tokens": list(range(6)), "block": 0,
                   "used": 1}]
        with pytest.raises(ValueError, match="not a multiple"):
            idx.load_state(ragged)
        dup = [
            {"tenant": "t", "tokens": [0, 1, 2, 3], "block": 2, "used": 1},
            {"tenant": "u", "tokens": [9, 8, 7, 6], "block": 2, "used": 2},
        ]
        with pytest.raises(ValueError, match="claimed twice"):
            idx.load_state(dup)


class TestAutotuneKVBlock:
    def test_fake_timer_picks_winner_and_cache_short_circuits(self, cfg):
        from repro.kernels.autotune import (
            AutotuneCache, apply_kv_block, tune_kv_block,
        )

        # candidates sweep in sorted order (4, 8, 16); make 16 fastest
        seen = iter([3e-3, 2e-3, 1e-3])

        def fake_timer(fn):
            jax.block_until_ready(fn())      # still exercise the round-trip
            return next(seen)

        cache = AutotuneCache()
        choice = tune_kv_block(cfg, config="test", seq=16, batch=2,
                               cache=cache, device="fake", timer=fake_timer)
        assert choice.tm == 16
        assert choice.time_s == 1e-3
        assert choice.default_time_s == 2e-3     # DEFAULT_BLOCK == 8's time
        assert DEFAULT_BLOCK == 8

        def boom(fn):
            raise AssertionError("cache hit must not re-time")

        again = tune_kv_block(cfg, config="test", seq=16, batch=2,
                              cache=cache, device="fake", timer=boom)
        assert (again.tm, again.time_s) == (choice.tm, choice.time_s)
        try:
            apply_kv_block(choice)
            assert get_default_block() == 16
        finally:
            set_default_block(None)
        assert get_default_block() == DEFAULT_BLOCK


class TestRuntimeCheckpoint:
    def test_session_state_roundtrips_pool_and_radix(self, cfg):
        params = init_lm(jax.random.key(0), cfg)
        sl = SL.SkipLoRAConfig(rank=4, mode="full", cache_dtype="float32")

        def runtime():
            return SessionRuntime(cfg, sl, params, max_tenants=2,
                                  samples_per_tenant=4, seq=8, lr=1e-2)

        rt = runtime()
        pool = rt.kv_pool(0, block=4, n_blocks=8)
        idx = rt.prefix_index(0)
        t = toks(10, seed=18)
        created = idx.insert("t0", t)
        caches = fill_random(init_serve_caches(cfg, 1, 8), seed=19)
        pool.publish(caches, 0, [bid for bid, _ in created],
                     [slot for _, slot in created])
        arrays, meta = rt.session_state()

        rt2 = runtime()
        rt2.load_session_state(arrays, meta)
        pool2, idx2 = rt2.kv_pool(0), rt2.prefix_index(0)
        assert (pool2.n_blocks, pool2.block) == (8, 4)
        np.testing.assert_array_equal(pool2.refs, pool.refs)
        assert pool2.free == pool.free
        assert idx2.match("t0", t) == idx.match("t0", t)
        for a, b in zip(jax.tree.leaves(pool.data),
                        jax.tree.leaves(pool2.data)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        rt2.check_prefix_no_leaks()
