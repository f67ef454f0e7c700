"""Radix prefix cache: cross-request KV reuse for the v2 ragged engine.

Contract under test: with the cache on, a request whose prompt shares a
block-aligned prefix with earlier (retired) traffic produces tokens
BIT-IDENTICAL to the uncached path while prefilling only its unshared
suffix and allocating only suffix blocks (asserted via allocator
accounting); eviction reclaims unreferenced cached blocks under
pressure; hash-chain collisions are isolated by exact token comparison;
the DS_PREFIX_CACHE kill switch restores stock behavior bit-for-bit;
shared blocks survive one owner being cancelled mid-decode; and a warm
cache never shrinks gateway admission capacity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.prefix_cache import (PrefixCacheManager,
                                                     RadixPrefixIndex,
                                                     prefix_cache_enabled)
from deepspeed_tpu.inference.v2.prefix_cache import radix_index as radix_index_mod
from deepspeed_tpu.inference.v2.ragged import (BlockedAllocator, BlockedKVCache,
                                               DSStateManager, KVCacheHandleError)
from deepspeed_tpu.models import build_llama

BS = 8  # KV block size used throughout


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(model_and_params, prefix=True, num_kv_blocks=0, max_context=64,
                n_seqs=4, batch=64):
    model, params = model_and_params
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=BS,
        num_kv_blocks=num_kv_blocks,
        prefix_cache=PrefixCacheConfig(enabled=prefix),
        state_manager=DSStateManagerConfig(max_ragged_batch_size=batch,
                                           max_ragged_sequence_count=n_seqs,
                                           max_tracked_sequences=n_seqs,
                                           max_context=max_context))
    return InferenceEngineV2(model=model, config=cfg, params=params,
                             dtype=jnp.float32)


def run_one(engine, uid, prompt, max_new=4, budget=48, max_burst=1):
    sched = DynamicSplitFuseScheduler(engine, token_budget=budget,
                                      max_burst=max_burst)
    sched.add_request(uid, prompt, max_new_tokens=max_new)
    out = sched.run_to_completion()[uid]
    return out, sched.requests[uid]


PROMPT = (np.arange(1, 25) % 250).astype(np.int32)          # 24 tokens = 3 blocks
SUFFIX = (np.arange(100, 108) % 250).astype(np.int32)       # 8-token unshared tail


# ---------------------------------------------------------------------- index
class TestRadixIndex:

    def test_match_insert_refcount_evict(self):
        idx = RadixPrefixIndex(block_size=4)
        toks = list(range(12))
        n0 = idx.insert_child(idx.root, tuple(toks[0:4]), 10)
        n1 = idx.insert_child(n0, tuple(toks[4:8]), 11)
        assert idx.num_nodes == 2 and idx.evictable_blocks == 2

        path = idx.match(toks, max_blocks=3)  # only 2 chunks cached
        assert [n.block_id for n in path] == [10, 11]
        for n in path:
            idx.incref(n)
        assert idx.evictable_blocks == 0
        # referenced nodes never evict
        assert idx.evict(2) == []

        idx.decref(n1)
        # n1 is now a ref-0 leaf; n0 still referenced
        assert idx.evict(2) == [11]
        assert idx.num_nodes == 1 and idx.evictions == 1
        idx.decref(n0)
        # cascade: n0 became an evictable leaf
        assert idx.evict(1) == [10]
        assert idx.num_nodes == 0

    def test_lru_order_and_protect(self):
        idx = RadixPrefixIndex(block_size=2)
        a = idx.insert_child(idx.root, (1, 2), 5)
        b = idx.insert_child(idx.root, (3, 4), 6)
        idx.touch(a)  # a most-recently used -> b evicts first
        assert idx.evict(1) == [6]
        assert idx.evict(1, protect={a}) == []

    def test_hash_chain_collision_isolation(self, monkeypatch):
        # force every chained key to collide: lookups must still resolve
        # by exact token content, never by hash alone
        monkeypatch.setattr(radix_index_mod, "_chunk_key", lambda p, c: 7)
        idx = RadixPrefixIndex(block_size=4)
        idx.insert_child(idx.root, (0, 1, 2, 3), 21)
        idx.insert_child(idx.root, (9, 9, 9, 9), 22)
        bucket = idx.root.children[7]
        assert len(bucket) == 2  # both live in one collision bucket
        assert [n.block_id for n in idx.match([0, 1, 2, 3], 1)] == [21]
        assert [n.block_id for n in idx.match([9, 9, 9, 9], 1)] == [22]
        assert idx.match([0, 1, 2, 9], 1) == []


# -------------------------------------------------------------------- manager
class TestPrefixCacheManager:

    def _pool(self, num_blocks=10):
        return BlockedKVCache(2, num_blocks, 4, 2, 4, dtype=jnp.float32)

    def test_acquire_caps_one_short_of_prompt(self):
        cache = self._pool()
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        pc = PrefixCacheManager(cache)
        mgr.attach_prefix_cache(pc)
        # seed: a retired sequence that wrote 8 tokens (2 full blocks)
        d = mgr.get_or_create_sequence(1)
        mgr.allocate_for(d, 8)
        d.advance(8)
        d.tokens = list(range(8))
        mgr.flush_sequence(1)
        assert pc.cached_blocks == 2 and pc.evictable_blocks == 2

        # an 8-token prompt identical to the cached content may only
        # match 1 block: the last prompt token must be recomputed
        d2 = mgr.get_or_create_sequence(2, prompt_tokens=list(range(8)))
        assert d2.cached_tokens == 4 and d2.shared_blocks == 1
        assert d2.seen_tokens == 4 and d2.tokens == [0, 1, 2, 3]
        assert pc.evictable_blocks == 1  # leased block is pinned
        mgr.flush_sequence(2)
        assert pc.evictable_blocks == 2

    def test_duplicate_retire_frees_private_copy(self):
        cache = self._pool()
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        pc = PrefixCacheManager(cache)
        mgr.attach_prefix_cache(pc)
        for uid in (1, 2):  # two sequences with identical content
            d = mgr.get_or_create_sequence(uid)
            mgr.allocate_for(d, 8)
            d.advance(8)
            d.tokens = list(range(8))
        free_before = cache.free_blocks
        mgr.flush_sequence(1)   # adopts 2 blocks into the trie
        mgr.flush_sequence(2)   # same content: private copies are freed
        assert pc.cached_blocks == 2
        assert cache.free_blocks == free_before + 2

    def test_eviction_under_pressure(self):
        cache = self._pool(num_blocks=6)  # null + 5 usable
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        pc = PrefixCacheManager(cache)
        mgr.attach_prefix_cache(pc)
        d = mgr.get_or_create_sequence(1)
        mgr.allocate_for(d, 16)  # 4 blocks
        d.advance(16)
        d.tokens = list(range(16))
        mgr.flush_sequence(1)
        assert pc.cached_blocks == 4 and cache.free_blocks == 1
        # allocating 3 blocks must reclaim 2 cached ones (LRU leaves)
        d2 = mgr.get_or_create_sequence(2)
        mgr.allocate_for(d2, 12)
        assert d2.cur_allocated_blocks == 3
        assert pc.index.evictions == 2 and pc.cached_blocks == 2

    def test_max_cached_blocks_cap(self):
        cache = self._pool()
        pc = PrefixCacheManager(cache, max_cached_blocks=1)
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        mgr.attach_prefix_cache(pc)
        d = mgr.get_or_create_sequence(1)
        mgr.allocate_for(d, 12)
        d.advance(12)
        d.tokens = list(range(12))
        free_before = cache.free_blocks
        mgr.flush_sequence(1)
        # cap 1: first chunk cached, older entries evicted to stay at 1,
        # everything else freed
        assert pc.cached_blocks == 1
        assert cache.free_blocks == free_before + 2

    def test_env_kill_switch(self, monkeypatch):
        cfg = PrefixCacheConfig(enabled=True)
        monkeypatch.setenv("DS_PREFIX_CACHE", "0")
        assert not prefix_cache_enabled(cfg)
        monkeypatch.setenv("DS_PREFIX_CACHE", "1")
        assert prefix_cache_enabled(PrefixCacheConfig(enabled=False))
        monkeypatch.delenv("DS_PREFIX_CACHE")
        assert prefix_cache_enabled(cfg)
        assert not prefix_cache_enabled(PrefixCacheConfig(enabled=False))


# ----------------------------------------------------------- engine-level e2e
class TestPrefixCacheEngine:

    def test_exact_match_reuse_bit_identical_suffix_only(self, model_and_params,
                                                         monkeypatch):
        """The acceptance contract: with DS_PREFIX_CACHE=1, warm cache ->
        identical tokens, only suffix tokens prefilled, only suffix
        blocks allocated."""
        ref_engine = make_engine(model_and_params, prefix=False)
        prompt_b = np.concatenate([PROMPT, SUFFIX])
        want_a, _ = run_one(ref_engine, 1, PROMPT)
        want_b, ref_req = run_one(ref_engine, 2, prompt_b)
        assert ref_req.prefix_cached_tokens == 0

        # the env var force-enables over a disabled config
        monkeypatch.setenv("DS_PREFIX_CACHE", "1")
        engine = make_engine(model_and_params, prefix=False)
        got_a, _ = run_one(engine, 1, PROMPT)
        assert got_a == want_a  # cold run: cache changes nothing
        # A retired: its 3 full prompt blocks are now cached
        assert engine.prefix_cache.cached_blocks >= 3
        free_before = engine.free_blocks

        sched = DynamicSplitFuseScheduler(engine, token_budget=48, max_burst=1)
        req = sched.add_request(2, prompt_b, max_new_tokens=4)
        sched.step()  # prefill step (suffix fits one budget)
        desc = engine.state_manager.query(2)
        # matched the whole 24-token shared prefix; prefilled 8-suffix only
        assert req.prefix_cached_tokens == 24
        assert desc.cached_tokens == 24 and desc.shared_blocks == 3
        assert desc.seen_tokens == 32
        # allocator accounting: exactly ONE private block was allocated
        # for the 8-token suffix — the prefix cost nothing
        assert free_before - engine.free_blocks == 1
        while sched.has_work:
            sched.step()
        assert sched.requests[2].generated == want_b  # bit-identical tokens
        stats = engine.prefix_cache.stats()
        assert stats["tokens_saved"] == 24 and stats["hit_rate"] > 0

    def test_partial_block_boundary(self, model_and_params):
        """Prompt length not a multiple of block_size: only the full
        leading blocks are shared; the partial tail stays private."""
        engine = make_engine(model_and_params, prefix=True)
        prompt_a = PROMPT[:13]  # 1 full block + 5-token partial
        run_one(engine, 1, prompt_a, max_new=3)
        # retired with seen=15 -> 1 full block cached, partial freed
        assert engine.prefix_cache.cached_blocks == 1

        ref_engine = make_engine(model_and_params, prefix=False)
        prompt_b = np.concatenate([prompt_a, SUFFIX[:3]])  # 16 tokens
        want, _ = run_one(ref_engine, 2, prompt_b, max_new=3)
        got, req = run_one(engine, 2, prompt_b, max_new=3)
        assert req.prefix_cached_tokens == 8  # the one full block
        assert got == want

    def test_kill_switch_parity_logits_identical(self, model_and_params,
                                                 monkeypatch):
        """DS_PREFIX_CACHE=0 beats config enabled=True, and the cached
        path's decode logits match the uncached path's."""
        monkeypatch.setenv("DS_PREFIX_CACHE", "0")
        off = make_engine(model_and_params, prefix=True)
        assert off.prefix_cache is None
        monkeypatch.delenv("DS_PREFIX_CACHE")
        on = make_engine(model_and_params, prefix=True)
        assert on.prefix_cache is not None

        prompt_b = np.concatenate([PROMPT, SUFFIX])

        def decode_logits(engine):
            rows = []

            def sample(logits):
                rows.append(np.asarray(logits, np.float32))
                return int(np.argmax(logits))

            sched = DynamicSplitFuseScheduler(engine, token_budget=48,
                                              sample_fn=sample)
            sched.add_request(1, PROMPT, max_new_tokens=4)
            sched.run_to_completion()
            sched2 = DynamicSplitFuseScheduler(engine, token_budget=48,
                                               sample_fn=sample)
            sched2.add_request(2, prompt_b, max_new_tokens=4)
            toks = sched2.run_to_completion()[2]
            return toks, np.stack(rows)

        toks_off, logits_off = decode_logits(off)
        toks_on, logits_on = decode_logits(on)
        assert toks_on == toks_off  # bit-identical sampled tokens
        np.testing.assert_allclose(logits_on, logits_off, rtol=0, atol=1e-5)

    def test_cancel_shared_prefix_mid_decode(self, model_and_params):
        """Regression (scheduler lifecycle): cancelling one of two
        sequences sharing a cached prefix must DECREF the shared blocks,
        not free them — the survivor keeps decoding correctly."""
        ref_engine = make_engine(model_and_params, prefix=False)
        prompt_b = np.concatenate([PROMPT, SUFFIX])
        prompt_c = np.concatenate([PROMPT, SUFFIX[::-1]])
        want_c, _ = run_one(ref_engine, 3, prompt_c, max_new=6)

        engine = make_engine(model_and_params, prefix=True)
        run_one(engine, 1, PROMPT)  # warm the cache
        sched = DynamicSplitFuseScheduler(engine, token_budget=48, max_burst=4)
        sched.add_request(2, prompt_b, max_new_tokens=6)
        sched.add_request(3, prompt_c, max_new_tokens=6)
        sched.step()  # prefill both (suffixes share the cached prefix)
        assert engine.state_manager.query(2).shared_blocks == 3
        assert engine.state_manager.query(3).shared_blocks == 3
        sched.step()  # at least one decode round for both
        sched.cancel(2)
        # the shared blocks must still be cached (C holds a lease)
        assert engine.prefix_cache.cached_blocks >= 3
        while sched.has_work:
            sched.step()
        assert sched.requests[3].generated == want_c

    def test_suspend_resume_with_shared_prefix(self, model_and_params):
        """Preemption of a sequence leasing cached blocks: the trie keeps
        them (other requests can still hit), the resumed sequence gets
        private copies and finishes identically."""
        ref_engine = make_engine(model_and_params, prefix=False)
        prompt_b = np.concatenate([PROMPT, SUFFIX])
        want, _ = run_one(ref_engine, 2, prompt_b, max_new=6)

        engine = make_engine(model_and_params, prefix=True)
        run_one(engine, 1, PROMPT)
        cached_before = engine.prefix_cache.cached_blocks
        sched = DynamicSplitFuseScheduler(engine, token_budget=48, max_burst=1)
        sched.add_request(2, prompt_b, max_new_tokens=6)
        sched.step()  # prefill
        sched.step()  # one decode
        sched.pause(2)
        # the shared prefix stayed cached through the suspend
        assert engine.prefix_cache.cached_blocks >= cached_before
        assert engine.is_suspended(2)
        sched.unpause(2)
        while sched.has_work:
            sched.step()
        assert sched.requests[2].generated == want


# -------------------------------------------------------------------- gateway
class TestGatewayWarmCache:

    def test_admission_counts_evictable_as_capacity(self, model_and_params):
        from deepspeed_tpu.serving import ServingConfig, ServingGateway
        engine = make_engine(model_and_params, prefix=True, num_kv_blocks=8,
                             max_context=48, n_seqs=2)
        shared = PROMPT[:16]
        run_one(engine, 1000, shared, max_new=4, budget=32)  # warm the cache
        assert engine.evictable_blocks >= 2
        free_now, evictable = int(engine.free_blocks), int(engine.evictable_blocks)

        gw = ServingGateway(engine, config=ServingConfig(token_budget=32,
                                                         max_burst=1))
        try:
            # a warm cache must not shrink admission capacity: usable
            # counts reclaimable cached blocks, not just the free list
            assert gw.gate.usable_blocks == free_now + evictable
            # footprint 6 blocks > free list (5) but <= usable (7): this
            # submit would be RequestTooLargeError without the credit
            prompt = np.concatenate([shared, SUFFIX])
            need = gw.gate.footprint(len(prompt), 24)
            assert free_now < need <= gw.gate.usable_blocks
            handle = gw.submit(prompt, max_new_tokens=24)
            toks = handle.result(timeout=120)
            assert len(toks) == 24
            snap = gw.snapshot()
            pc = snap["external"]["Serve/PrefixCache"]
            assert pc["tokens_saved"] >= 16 and pc["hit_rate"] > 0
            events = dict((tag, val) for tag, val, _ in gw.metrics.events())
            assert "Serve/PrefixCache/hit_rate" in events
        finally:
            if gw.state == "running":
                gw.drain()


# ------------------------------------------------------- satellite: allocator
class TestAllocatorAndHandles:

    def test_set_backed_double_free(self):
        alloc = BlockedAllocator(8)
        blocks = alloc.allocate(4)
        alloc.free(blocks[:2])
        with pytest.raises(ValueError, match="double free"):
            alloc.free(blocks[:1])       # already free
        with pytest.raises(ValueError, match="double free"):
            alloc.free([int(blocks[2])] * 2)  # duplicate within one call
        with pytest.raises(ValueError, match="invalid block id"):
            alloc.free([99])
        # failed batches must not have mutated the free list
        assert alloc.free_blocks == 6

    def test_allocation_order_deterministic(self):
        alloc = BlockedAllocator(6)
        assert alloc.allocate(3).tolist() == [0, 1, 2]
        alloc.free([1])
        alloc.free([0])
        # FIFO free list: blocks come back in the order they were freed
        assert alloc.allocate(5).tolist() == [3, 4, 5, 1, 0]

    def test_kv_free_accepts_any_iterable(self):
        cache = BlockedKVCache(2, 8, 4, 2, 4, dtype=jnp.float32)
        blocks = cache.reserve(3)
        cache.free(int(b) for b in blocks)  # a generator, no len()
        assert cache.free_blocks == 7

    def test_restore_validates_handle(self):
        cache = BlockedKVCache(2, 8, 4, 2, 4, dtype=jnp.float32)
        handle = cache.offload(cache.reserve(2))
        bad_shape = {"k": handle["k"][:, :, :2], "v": handle["v"]}
        with pytest.raises(KVCacheHandleError, match="shape"):
            cache.restore(bad_shape)
        bad_dtype = {"k": np.asarray(handle["k"], np.float16),
                     "v": np.asarray(handle["v"], np.float16)}
        with pytest.raises(KVCacheHandleError, match="dtype"):
            cache.restore(bad_dtype)
        with pytest.raises(KVCacheHandleError, match="dict"):
            cache.restore({"k": handle["k"]})
        blocks = cache.restore(handle)  # the untampered handle round-trips
        assert len(blocks) == 2
        with pytest.raises(KVCacheHandleError, match="invalid block id"):
            cache.offload([99])

    def test_offload_restore_keep_wire_shape_from_flattened_pool(self):
        """The prefix cache's suspend path copies blocks through
        ``offload(keep=...)`` / ``restore``: the handle is 5-D
        ``[L, n, bs, Hkv, Dh]`` as before although the pool is stored
        ``[L, NB, bs, Hkv*Dh]``, and the kept block is not freed."""
        cache = BlockedKVCache(2, 8, 4, 2, 4, dtype=jnp.float32)
        assert cache.k.shape == (2, 8, 4, 8)
        blocks = [int(b) for b in cache.reserve(3)]
        rows = np.arange(2 * 3 * 4 * 8, dtype=np.float32).reshape(2, 3, 4, 8)
        cache.k = cache.k.at[:, jnp.asarray(blocks)].set(rows)
        handle = cache.offload(blocks, keep=blocks[:1])
        assert handle["k"].shape == handle["v"].shape == (2, 3, 4, 2, 4)
        np.testing.assert_array_equal(handle["k"].reshape(2, 3, 4, 8), rows)
        assert cache.free_blocks == 8 - 1 - 1  # the null block and the kept one stay owned
        back = cache.restore(handle)
        assert cache.k.shape == (2, 8, 4, 8)
        np.testing.assert_array_equal(cache.gather(back)["k"], handle["k"])
