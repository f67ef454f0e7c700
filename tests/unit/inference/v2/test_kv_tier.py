"""Tiered KV cache: host-RAM spill tier behind the radix prefix cache.

Contract under test: blocks the trie evicts under pressure DEMOTE to a
byte-budgeted host store instead of dropping; a later prompt whose trie
match continues into a demoted chain PROMOTES it back through the
donated restore scatter with KV bit-identical to what was spilled (bf16
/ fp32 tiers), so outputs match the never-evicted run token for token;
int8 tier storage is opt-in, bounded by absmax/127/2 per group, and
measured per block; ``match_len`` counts both tiers for routing;
``offload(keep=)`` rejects keep ids outside the block set; empty-handle
``restore`` is a no-op; the ``DS_KV_TIER`` kill switch restores stock
behavior; and DS_SANITIZE catches records whose stored chain key no
longer re-derives from their identity."""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig,
                                        DynamicSplitFuseScheduler,
                                        InferenceEngineV2, KVTierConfig,
                                        PrefixCacheConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kv_tier import (HostKVStore, TierManager,
                                                dequantize_handle,
                                                handle_nbytes, kv_tier_bytes,
                                                kv_tier_enabled,
                                                kv_tier_quantized,
                                                quantize_handle)
from deepspeed_tpu.inference.v2.kv_tier.quant import (concat_handles,
                                                      slice_handle)
from deepspeed_tpu.inference.v2.prefix_cache import PrefixCacheManager
from deepspeed_tpu.inference.v2.ragged import (BlockedKVCache, DSStateManager,
                                               KVCacheHandleError)
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.utils.sanitize import (KVTierCorruptionError,
                                          check_kv_tier_store)

BS = 8  # engine-level KV block size


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(model_and_params, tier=True, tier_bytes=1 << 20,
                quantize=False, prefix=True, num_kv_blocks=0, max_context=64,
                n_seqs=4, batch=64):
    model, params = model_and_params
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=BS,
        num_kv_blocks=num_kv_blocks,
        prefix_cache=PrefixCacheConfig(enabled=prefix),
        kv_tier=KVTierConfig(enabled=tier, host_bytes=tier_bytes,
                             quantize=quantize),
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


PROMPT = (np.arange(1, 25) % 250).astype(np.int32)      # 24 tokens = 3 blocks
PROMPT_B = (np.arange(50, 74) % 250).astype(np.int32)   # disjoint 24 tokens
SUFFIX = (np.arange(100, 108) % 250).astype(np.int32)   # 8-token tail


def small_pool(num_blocks=10, block_size=4):
    # [num_layers=2, blocks, block_size, n_kv_heads=2, head_dim=4], fp32
    return BlockedKVCache(2, num_blocks, block_size, 2, 4, dtype=jnp.float32)


def fill_blocks(cache, blocks):
    """Write distinct deterministic KV into ``blocks`` and return the
    host copy for later bit-compare."""
    shape = (cache.num_layers, len(blocks), cache.block_size,
             cache.n_kv_heads, cache.head_dim)
    rng = np.random.default_rng(sum(blocks) + len(blocks))
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    ids = jnp.asarray(blocks)
    flat = shape[:3] + (-1,)  # the pool stores [L, NB, bs, Hkv*Dh]
    cache.k = cache.k.at[:, ids].set(jnp.asarray(k).reshape(flat))
    cache.v = cache.v.at[:, ids].set(jnp.asarray(v).reshape(flat))
    return {"k": k, "v": v}


# ----------------------------------------------------------------- quant unit
class TestQuantHandles:

    def _rand_handle(self, n=3, seed=0, L=2, bs=4, H=2, D=4, scale=10.0):
        rng = np.random.default_rng(seed)
        shape = (L, n, bs, H, D)
        return {"k": (rng.standard_normal(shape) * scale).astype(np.float32),
                "v": (rng.standard_normal(shape) * scale).astype(np.float32)}

    @pytest.mark.parametrize("seed,group_size", [(0, 0), (1, 0), (2, 8),
                                                 (3, 16), (4, 32)])
    def test_roundtrip_error_within_per_group_bound(self, seed, group_size):
        """Symmetric int8 with scale=absmax/127 and round-to-nearest:
        every element lands within scale/2 of the original, per group."""
        handle = self._rand_handle(seed=seed)
        q = quantize_handle(handle, group_size=group_size)
        back = dequantize_handle(q, jnp.float32)
        L, n, bs, H, D = handle["k"].shape
        slab = bs * H * D
        gs = group_size or slab
        for name in ("k", "v"):
            orig = handle[name].reshape(L, n, slab // gs, gs)
            got = np.asarray(back[name]).reshape(L, n, slab // gs, gs)
            absmax = np.abs(orig).max(axis=-1, keepdims=True)
            bound = absmax / 127.0 / 2.0 + 1e-5
            assert (np.abs(got - orig) <= bound).all()

    def test_reported_error_is_the_measured_max(self):
        handle = self._rand_handle(seed=7)
        q = quantize_handle(handle)
        back = dequantize_handle(q, jnp.float32)
        err = np.maximum(
            np.abs(np.asarray(back["k"]) - handle["k"]).max(axis=(0, 2, 3, 4)),
            np.abs(np.asarray(back["v"]) - handle["v"]).max(axis=(0, 2, 3, 4)))
        assert np.allclose(np.asarray(q["quant_error"]), err, atol=1e-6)
        assert (np.asarray(q["quant_error"]) > 0).all()  # lossy, never silent

    def test_quantized_layout_and_nbytes(self):
        handle = self._rand_handle(n=4)
        q = quantize_handle(handle)
        L, n, bs, H, D = handle["k"].shape
        assert q["k"].dtype == np.int8 and q["k"].shape == handle["k"].shape
        assert q["k_scales"].shape == (L, n, 1)  # default group = whole slab
        assert q["k_scales"].dtype == np.float32
        assert q["quantized"] is True
        # int8 carriers: ~4x smaller than the fp32 originals (+ scales)
        assert handle_nbytes(q) < handle_nbytes(handle) / 3
        g = quantize_handle(handle, group_size=8)
        assert g["k_scales"].shape == (L, n, bs * H * D // 8)

    def test_slice_concat_preserve_format(self):
        handle = self._rand_handle(n=4, seed=5)
        q = quantize_handle(handle)
        parts = [slice_handle(q, i, i + 1) for i in range(4)]
        assert all(p["quantized"] for p in parts)
        assert parts[2]["quant_error"].shape == (1,)
        whole = concat_handles(parts)
        assert whole["quantized"] is True
        np.testing.assert_array_equal(np.asarray(whole["k"]), q["k"])
        np.testing.assert_array_equal(np.asarray(whole["k_scales"]),
                                      q["k_scales"])
        # plain (unquantized) handles ride the same helpers
        plain = concat_handles([slice_handle(handle, 0, 2),
                                slice_handle(handle, 2, 4)])
        assert "quantized" not in plain
        np.testing.assert_array_equal(np.asarray(plain["v"]), handle["v"])

    def test_zero_and_empty_blocks(self):
        zeros = {"k": np.zeros((2, 2, 4, 2, 4), np.float32),
                 "v": np.zeros((2, 2, 4, 2, 4), np.float32)}
        q = quantize_handle(zeros)
        assert (np.asarray(q["quant_error"]) == 0).all()
        back = dequantize_handle(q, jnp.float32)
        assert (np.asarray(back["k"]) == 0).all()
        empty = {"k": np.zeros((2, 0, 4, 2, 4), np.float32),
                 "v": np.zeros((2, 0, 4, 2, 4), np.float32)}
        qe = quantize_handle(empty)
        assert qe["k"].shape == empty["k"].shape
        assert qe["k_scales"].shape == (2, 0, 1)


# ------------------------------------------------------------- pool offload
class TestPoolOffloadRestore:

    def test_gather_reads_without_freeing(self):
        cache = small_pool()
        blocks = cache.reserve(3)
        want = fill_blocks(cache, blocks)
        free_before = cache.free_blocks
        handle = cache.gather(blocks)
        assert cache.free_blocks == free_before  # gather never frees
        np.testing.assert_array_equal(handle["k"], want["k"])
        np.testing.assert_array_equal(handle["v"], want["v"])

    def test_gather_rejects_bad_ids_and_empty(self):
        cache = small_pool()
        with pytest.raises(KVCacheHandleError):
            cache.gather([cache.num_blocks])
        with pytest.raises(KVCacheHandleError):
            cache.gather([-1])
        empty = cache.gather([])
        assert empty["k"].shape[1] == 0

    def test_offload_keep_must_be_subset(self):
        """Regression: a keep id outside the offload set would stay
        allocated with nobody holding it — a permanent pool leak."""
        cache = small_pool()
        blocks = cache.reserve(3)
        free_before = cache.free_blocks
        with pytest.raises(KVCacheHandleError, match="not in the offloaded"):
            cache.offload(blocks, keep=[blocks[0], 9])
        # the failed call must not have freed anything
        assert cache.free_blocks == free_before
        handle = cache.offload(blocks, keep=[blocks[0]])
        assert cache.free_blocks == free_before + 2  # kept block still owned
        assert handle["k"].shape[1] == 3

    def test_restore_empty_handle_is_noop(self):
        cache = small_pool()
        free_before = cache.free_blocks
        handle = cache.gather([])
        assert cache.restore(handle) == []
        assert cache.free_blocks == free_before  # no reservation happened

    def test_restore_single_block_roundtrip_bit_identical(self):
        cache = small_pool()
        (block,) = cache.reserve(1)
        want = fill_blocks(cache, [block])
        handle = cache.offload([block])
        new = cache.restore(handle)
        assert len(new) == 1
        got = cache.gather(new)
        np.testing.assert_array_equal(got["k"], want["k"])
        np.testing.assert_array_equal(got["v"], want["v"])

    def test_quantized_restore_matches_host_dequant_exactly(self):
        """The jitted in-scatter dequant and the host dequant are the
        same math: restoring an int8 handle must land exactly the host
        dequant values (fp32 pool), within the per-group bound of the
        original."""
        cache = small_pool()
        blocks = cache.reserve(3)
        orig = fill_blocks(cache, blocks)
        q = quantize_handle(cache.gather(blocks))
        host = dequantize_handle(q, jnp.float32)
        new = cache.restore(q)
        got = cache.gather(new)
        np.testing.assert_array_equal(got["k"], np.asarray(host["k"]))
        np.testing.assert_array_equal(got["v"], np.asarray(host["v"]))
        bound = np.abs(orig["k"]).max() / 127.0 / 2.0 + 1e-5
        assert np.abs(got["k"] - orig["k"]).max() <= bound

    @pytest.mark.parametrize("quantized", [False, True])
    def test_handles_keep_the_wire_shape_from_the_flattened_pool(self, quantized):
        """The pool is stored ``[L, NB, bs, Hkv*Dh]``; what leaves and
        enters it stays ``[L, n, bs, Hkv, Dh]`` — plain and int8 alike —
        and a round trip through either lands in the flattened pool."""
        cache = small_pool()
        assert cache.k.shape == (2, 10, 4, 2 * 4)
        blocks = cache.reserve(3)
        orig = fill_blocks(cache, blocks)
        handle = cache.gather(blocks)
        assert handle["k"].shape == handle["v"].shape == (2, 3, 4, 2, 4)
        if quantized:
            handle = quantize_handle(handle)
            assert handle["k"].shape == (2, 3, 4, 2, 4) and handle["k"].dtype == np.int8
            assert handle["k_scales"].shape == (2, 3, 1)
        new = cache.restore(handle)
        assert cache.k.shape == cache.v.shape == (2, 10, 4, 2 * 4)
        got = cache.gather(new)
        assert got["k"].shape == (2, 3, 4, 2, 4)
        want = dequantize_handle(handle, jnp.float32) if quantized else orig
        np.testing.assert_array_equal(got["k"], np.asarray(want["k"]))
        np.testing.assert_array_equal(got["v"], np.asarray(want["v"]))
        with pytest.raises(KVCacheHandleError, match=r"n_kv_heads=2, head_dim=4"):
            cache.restore({"k": got["k"].reshape(2, 3, 4, 8), "v": got["v"].reshape(2, 3, 4, 8)})

    def test_validate_rejects_malformed_quantized_handles(self):
        cache = small_pool()
        blocks = cache.reserve(2)
        fill_blocks(cache, blocks)
        q = quantize_handle(cache.gather(blocks))
        # int8 carrier with the quantized marker stripped -> dtype error
        bad = {"k": q["k"], "v": q["v"]}
        with pytest.raises(KVCacheHandleError, match="dtype"):
            cache.restore(bad)
        # missing scales
        bad = dict(q)
        del bad["k_scales"]
        with pytest.raises(KVCacheHandleError, match="k_scales"):
            cache.restore(bad)
        # scale count that does not divide the slab
        bad = dict(q)
        bad["k_scales"] = np.zeros((2, 2, 3), np.float32)
        with pytest.raises(KVCacheHandleError, match="k_scales"):
            cache.restore(bad)
        # wrong scale dtype
        bad = dict(q)
        bad["k_scales"] = np.asarray(q["k_scales"], np.float64)
        with pytest.raises(KVCacheHandleError, match="float32"):
            cache.restore(bad)
        # fp32 values claiming to be quantized
        bad = dict(q)
        bad["k"] = np.asarray(q["k"], np.float32)
        with pytest.raises(KVCacheHandleError, match="dtype"):
            cache.restore(bad)


# --------------------------------------------------------------- host store
class TestHostKVStore:

    def _handle(self, nbytes=64):
        return {"k": np.zeros(nbytes // 8), "v": np.zeros(nbytes // 8)}

    def test_put_peek_pop_and_one_tier_ownership(self):
        store = HostKVStore(1 << 20)
        assert store.put("root", (1, 2), self._handle(), 64)
        assert store.contains("root", (1, 2))
        rec = store.peek("root", (1, 2))
        assert rec["tokens"] == (1, 2) and rec["nbytes"] == 64
        popped = store.pop("root", (1, 2))
        assert popped is rec
        assert len(store) == 0 and store.bytes_resident == 0
        assert store.pop("root", (1, 2)) is None  # gone: one tier only
        s = store.stats()
        assert s["promotions"] == 1 and s["demotions"] == 1

    def test_lru_byte_budget_evicts_oldest(self):
        store = HostKVStore(300)
        for i in range(3):
            assert store.put("r", (i,), self._handle(), 100)
        store.peek("r", (0,))  # touch refreshes (0,) -> (1,) is oldest
        assert store.put("r", (3,), self._handle(), 100)
        assert not store.contains("r", (1,))
        assert store.contains("r", (0,)) and store.contains("r", (3,))
        assert store.bytes_resident == 300 and store.evictions == 1

    def test_single_block_over_budget_is_rejected(self):
        store = HostKVStore(100)
        assert not store.put("r", (1,), self._handle(), 101)
        assert len(store) == 0 and store.bytes_resident == 0

    def test_reinsert_refreshes_not_duplicates(self):
        store = HostKVStore(1 << 20)
        store.put("r", (1,), self._handle(), 100)
        store.put("r", (1,), self._handle(), 60)
        assert len(store) == 1 and store.bytes_resident == 60

    def test_routing_probe_does_not_skew_hit_rate(self):
        store = HostKVStore(1 << 20)
        store.put("r", (1,), self._handle(), 64)
        store.peek("r", (1,), touch=False)
        store.contains("r", (9,))
        assert store.stats()["lookups"] == 0
        store.peek("r", (1,))
        store.peek("r", (9,))
        s = store.stats()
        assert s["lookups"] == 2 and s["hits"] == 1


# ----------------------------------------------------------------- sanitizer
class TestTierSanitizer:

    def _store_with_record(self):
        store = HostKVStore(1 << 20)
        store.put("root", (1, 2, 3, 4), {"k": np.zeros(4), "v": np.zeros(4)},
                  64)
        return store

    def test_clean_store_passes(self):
        check_kv_tier_store(self._store_with_record())

    def test_forged_chain_key_raises(self):
        store = self._store_with_record()
        rec = store.peek("root", (1, 2, 3, 4))
        rec["key"] = "forged"
        with pytest.raises(KVTierCorruptionError, match="identity"):
            check_kv_tier_store(store)

    def test_byte_accounting_drift_raises(self):
        store = self._store_with_record()
        store.bytes_resident += 1
        with pytest.raises(KVTierCorruptionError, match="bytes_resident"):
            check_kv_tier_store(store)

    def test_ds_sanitize_checks_every_mutation(self, monkeypatch):
        monkeypatch.setenv("DS_SANITIZE", "1")
        store = self._store_with_record()  # sampled at construction
        rec = store.peek("root", (1, 2, 3, 4))
        rec["key"] = "forged"
        with pytest.raises(KVTierCorruptionError):
            store.put("root", (9, 9, 9, 9), {"k": np.zeros(4)}, 32)


# --------------------------------------------------- tier manager + manager
class TestTierManager:

    def _setup(self, num_blocks=10, tier_bytes=1 << 20, quantize=False):
        cache = small_pool(num_blocks)
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        pc = PrefixCacheManager(cache)
        mgr.attach_prefix_cache(pc)
        tier = TierManager(pc, tier_bytes, quantize=quantize, prefetch=False)
        pc.attach_tier(tier)
        return cache, mgr, pc, tier

    def _seed_chain(self, cache, mgr, tokens, uid=1):
        """Retire one sequence so its full blocks land in the trie, and
        return the original KV content of those blocks."""
        d = mgr.get_or_create_sequence(uid)
        mgr.allocate_for(d, len(tokens))
        d.advance(len(tokens))
        d.tokens = list(tokens)
        full = len(tokens) // cache.block_size
        want = fill_blocks(cache, [int(b) for b in d.blocks[:full]])
        mgr.flush_sequence(uid)
        return want

    def test_eviction_demotes_instead_of_dropping(self):
        cache, mgr, pc, tier = self._setup()
        self._seed_chain(cache, mgr, list(range(12)))  # 3 cached blocks
        pc.ensure_free(cache.free_blocks + 3)
        assert pc.cached_blocks == 0
        s = tier.stats()
        assert s["blocks_resident"] == 3 and s["demoted_blocks"] == 3
        assert s["bytes_resident"] > 0

    def test_match_len_counts_both_tiers(self):
        cache, mgr, pc, tier = self._setup()
        self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        lookups_before = tier.store.stats()["lookups"]
        # 13 tokens -> 3 matchable blocks, all of them now tier-2
        assert pc.match_len(list(range(13))) == 12
        assert pc.match_len(list(range(8))) == 4   # capped one short
        assert pc.match_len(list(range(50, 60))) == 0
        # routing probes never look like tier traffic
        assert tier.store.stats()["lookups"] == lookups_before

    def test_acquire_promotes_bit_identical_and_attributes_hit(self):
        cache, mgr, pc, tier = self._setup()
        want = self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        assert pc.cached_blocks == 0 and len(tier.store) == 3

        blocks, cached = pc.acquire(2, list(range(13)))
        assert cached == 12 and len(blocks) == 3
        got = cache.gather(blocks)
        np.testing.assert_array_equal(got["k"], want["k"])
        np.testing.assert_array_equal(got["v"], want["v"])
        # one-tier ownership: promoted records left the store
        assert len(tier.store) == 0
        assert pc.tier2_hits == 1 and pc.tier2_tokens_saved == 12
        s = tier.stats()
        assert s["promoted_blocks"] == 3 and s["tier2_hit_rate"] > 0
        # second acquire of the same prefix is a pure tier-1 hit
        pc.release_lease(2)
        _, cached2 = pc.acquire(3, list(range(13)))
        assert cached2 == 12 and pc.tier2_hits == 1  # flag consumed once

    def test_promotion_evicts_other_blocks_for_room(self):
        """Pool too full to restore: promotion demotes OTHER ref-0
        blocks (never the matched path) and promotes what fits."""
        cache, mgr, pc, tier = self._setup(num_blocks=5)  # null + 4
        self._seed_chain(cache, mgr, list(range(12)))     # 3 cached
        pc.ensure_free(cache.free_blocks + 3)             # all demoted
        self._seed_chain(cache, mgr, list(range(50, 62)), uid=2)  # refill
        assert cache.free_blocks == 1 and pc.cached_blocks == 3
        blocks, cached = pc.acquire(3, list(range(13)))
        assert cached == 12 and len(blocks) == 3
        # the promotion displaced seq-2's chain into tier-2
        assert tier.store.stats()["demotions"] >= 5

    def test_partial_promotion_unclaims_tail(self):
        """When even eviction cannot make room for the whole chain, the
        head promotes and the tail goes back to the store."""
        cache, mgr, pc, tier = self._setup(num_blocks=5)
        self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        # pin every pool block in a live (unretired) sequence: nothing
        # is evictable, only today's free block remains
        d = mgr.get_or_create_sequence(5)
        mgr.allocate_for(d, 12)
        assert cache.free_blocks == 1
        blocks, cached = pc.acquire(6, list(range(13)))
        assert cached == 4 and len(blocks) == 1  # head only
        assert len(tier.store) == 2              # tail back in tier-2

    def test_quantized_tier_reports_error_and_stays_in_bound(self):
        cache, mgr, pc, tier = self._setup(quantize=True)
        want = self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        s = tier.stats()
        assert s["quantized"] == 1 and s["quant_error_max"] > 0
        rec = tier.store.peek("k", (0,), touch=False)  # no such record
        assert rec is None
        blocks, cached = pc.acquire(2, list(range(13)))
        assert cached == 12
        got = cache.gather(blocks)
        for name in ("k", "v"):
            bound = np.abs(want[name]).max() / 127.0 / 2.0 + 1e-5
            assert np.abs(got[name] - want[name]).max() <= bound
        # quantized restore is NOT bit-identical -- the point of bf16
        # being the default
        assert (got["k"] != want["k"]).any()

    def test_store_budget_limits_resident_blocks(self):
        cache, mgr, pc, tier = self._setup(tier_bytes=1)  # nothing fits
        self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        assert len(tier.store) == 0          # every demotion was rejected
        _, cached = pc.acquire(2, list(range(13)))
        assert cached == 0                   # and nothing can promote

    def test_prefetch_stages_chain_and_claim_prefers_staged(self):
        cache, mgr, pc, tier = self._setup()
        tier.prefetch_enabled = True
        self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        prompt = list(range(13))
        tier.prefetch(prompt)
        tier.wait_prefetch(prompt, timeout=10.0)
        s = tier.stats()
        assert s["prefetched_blocks"] == 3
        assert s["prefetch_wait_ms"] >= 0 and s["prefetch_timeouts"] == 0
        blocks, cached = pc.acquire(2, prompt)
        assert cached == 12
        assert tier.stats()["stage_hits"] == 3
        tier.shutdown()

    def test_prefetch_dedups_and_skips_tiny_prompts(self):
        cache, mgr, pc, tier = self._setup()
        tier.prefetch_enabled = True
        tier.prefetch([1, 2, 3])           # <= block_size: nothing to do
        assert len(tier._inflight) == 0
        self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        prompt = list(range(13))
        tier.prefetch(prompt)
        tier.prefetch(prompt)              # dedup: one fence, one pass
        with tier._lock:
            assert len(tier._inflight) == 1
        tier.wait_prefetch(prompt, timeout=10.0)
        assert tier.stats()["prefetch_waits"] == 1
        tier.wait_prefetch(prompt)         # fence consumed: returns at once
        assert tier.stats()["prefetch_waits"] == 1
        tier.shutdown()

    def test_wait_prefetch_released_even_when_staging_fails(self):
        cache, mgr, pc, tier = self._setup()
        tier.prefetch_enabled = True
        self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        # break staging: the worker must still set the fence event
        tier._stage_prompt = lambda prompt: (_ for _ in ()).throw(
            RuntimeError("boom"))
        prompt = list(range(13))
        tier.prefetch(prompt)
        t0 = threading.Event()  # noqa: F841 (readability anchor)
        tier.wait_prefetch(prompt, timeout=10.0)
        s = tier.stats()
        assert s["prefetch_errors"] == 1 and s["prefetch_timeouts"] == 0
        tier.shutdown()

    def test_shutdown_releases_inflight_fences(self):
        cache, mgr, pc, tier = self._setup()
        tier.prefetch_enabled = True
        self._seed_chain(cache, mgr, list(range(12)))
        pc.ensure_free(cache.free_blocks + 3)
        ev = threading.Event()
        with tier._lock:
            tier._inflight[(99,)] = ev
        tier.shutdown()
        assert ev.is_set()
        assert len(tier.store) == 0


# ------------------------------------------------------------- kill switches
class TestKillSwitch:

    def test_env_tri_state(self, monkeypatch):
        on, off = KVTierConfig(enabled=True), KVTierConfig(enabled=False)
        monkeypatch.setenv("DS_KV_TIER", "0")
        assert not kv_tier_enabled(on)
        monkeypatch.setenv("DS_KV_TIER", "1")
        assert kv_tier_enabled(off)
        monkeypatch.delenv("DS_KV_TIER")
        assert kv_tier_enabled(on) and not kv_tier_enabled(off)

    def test_bytes_and_quant_overrides(self, monkeypatch):
        cfg = KVTierConfig(host_bytes=123, quantize=True)
        assert kv_tier_bytes(cfg) == 123
        monkeypatch.setenv("DS_KV_TIER_BYTES", "456")
        assert kv_tier_bytes(cfg) == 456
        monkeypatch.setenv("DS_KV_TIER_QUANT", "0")
        assert not kv_tier_quantized(cfg)
        monkeypatch.delenv("DS_KV_TIER_QUANT")
        assert kv_tier_quantized(cfg)
        assert not kv_tier_quantized(KVTierConfig())  # opt-in only

    def test_tier_requires_prefix_cache(self, model_and_params):
        engine = make_engine(model_and_params, tier=True, prefix=False)
        assert engine.kv_tier is None  # warned + skipped, not crashed
        engine.destroy()

    def test_disabled_tier_engine_matches_prefix_only(self, model_and_params,
                                                      monkeypatch):
        """DS_KV_TIER=0 beats config enabled=True and restores the
        prefix-cache-only pipeline bit for bit."""
        monkeypatch.setenv("DS_KV_TIER", "0")
        off = make_engine(model_and_params, tier=True)
        assert off.kv_tier is None
        assert off.prefix_cache is not None and off.prefix_cache.tier is None
        monkeypatch.delenv("DS_KV_TIER")
        ref = make_engine(model_and_params, tier=False)
        prompt_b = np.concatenate([PROMPT, SUFFIX])
        for uid, prompt in ((1, PROMPT), (2, prompt_b)):
            want, _ = run_one(ref, uid, prompt)
            got, _ = run_one(off, uid, prompt)
            assert got == want
        assert off.prefix_cache.stats()["tier2_hits"] == 0
        ref.destroy()
        off.destroy()

    def test_env_forces_tier_on_over_config(self, model_and_params,
                                            monkeypatch):
        monkeypatch.setenv("DS_KV_TIER", "1")
        engine = make_engine(model_and_params, tier=False)
        assert engine.kv_tier is not None
        assert engine.prefix_cache.tier is engine.kv_tier
        engine.destroy()


# ----------------------------------------------------------- engine-level e2e
class TestKVTierEngine:

    def test_demote_promote_bit_identical_tokens(self, model_and_params):
        """The acceptance contract: blocks evicted from a too-small HBM
        pool come back from the host tier, the returning request skips
        its restored prefix, and its tokens match a never-cached run
        bit for bit."""
        ref = make_engine(model_and_params, tier=False, prefix=False)
        prompt_a2 = np.concatenate([PROMPT, SUFFIX])
        want_a, _ = run_one(ref, 1, PROMPT)
        want_a2, _ = run_one(ref, 2, prompt_a2)
        want_b, _ = run_one(ref, 3, PROMPT_B)

        # null + 5 usable blocks: A's 4-block run fits, but B's arrival
        # must evict (= demote) A's cached chain
        engine = make_engine(model_and_params, tier=True, num_kv_blocks=6)
        got_a, _ = run_one(engine, 1, PROMPT)
        assert got_a == want_a
        assert engine.prefix_cache.cached_blocks == 3
        got_b, _ = run_one(engine, 3, PROMPT_B)
        assert got_b == want_b
        tier_stats = engine.kv_tier.stats()
        assert tier_stats["demoted_blocks"] >= 2  # pressure spilled A

        # the routing probe sees the demoted chain before admission
        assert engine.prefix_match_len(prompt_a2) == 24

        got_a2, req = run_one(engine, 2, prompt_a2)
        assert got_a2 == want_a2                   # bit-identical restore
        assert req.prefix_cached_tokens == 24      # prefill skipped 3 blocks
        pc_stats = engine.prefix_cache.stats()
        assert pc_stats["tier2_hits"] == 1
        assert pc_stats["tier2_tokens_saved"] >= 16
        tier_stats = engine.kv_tier.stats()
        assert tier_stats["promoted_blocks"] >= 2
        assert tier_stats["tier2_hit_rate"] > 0
        ref.destroy()
        engine.destroy()

    def test_scheduler_admission_kicks_prefetch(self, model_and_params):
        """add_request fires the async prefetch; the acquire-side fence
        waits for staging, so promotion consumes staged device copies."""
        engine = make_engine(model_and_params, tier=True, num_kv_blocks=6)
        run_one(engine, 1, PROMPT)
        run_one(engine, 2, PROMPT_B)     # evicts/demotes A's chain
        assert len(engine.kv_tier.store) >= 2
        got, req = run_one(engine, 3, np.concatenate([PROMPT, SUFFIX]))
        assert req.prefix_cached_tokens == 24
        s = engine.kv_tier.stats()
        assert s["prefetched_blocks"] >= 1   # worker staged the chain
        assert s["stage_hits"] >= 1          # promotion used a staged copy
        assert s["prefetch_waits"] >= 1      # the fence was exercised
        assert s["prefetch_timeouts"] == 0
        engine.destroy()

    def test_quantized_engine_flags_metrics_not_silent(self, model_and_params,
                                                       monkeypatch):
        monkeypatch.setenv("DS_KV_TIER_QUANT", "1")
        engine = make_engine(model_and_params, tier=True, num_kv_blocks=6)
        run_one(engine, 1, PROMPT)
        run_one(engine, 2, PROMPT_B)
        s = engine.kv_tier.stats()
        assert s["quantized"] == 1
        assert s["demoted_blocks"] >= 2 and s["quant_error_max"] > 0
        engine.destroy()


# ------------------------------------------- cross-process handoff symmetry
class TestCrossProcessHandoff:
    """``export_chain`` on replica A / ``import_chain`` on replica B is
    the demote/promote pair made symmetric across processes: the
    chained-key identities are replica-independent, the KV crosses the
    boundary bit-identical (fp32) or within the quant bound (int8), and
    a forged or truncated record is rejected by chained-key
    re-derivation before anything is adopted."""

    def _stack(self, num_blocks=10, quantize=False):
        cache = small_pool(num_blocks)
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        pc = PrefixCacheManager(cache)
        mgr.attach_prefix_cache(pc)
        tier = TierManager(pc, 1 << 20, quantize=quantize, prefetch=False)
        pc.attach_tier(tier)
        return cache, mgr, pc, tier

    def _seed(self, cache, mgr, tokens, uid=1):
        d = mgr.get_or_create_sequence(uid)
        mgr.allocate_for(d, len(tokens))
        d.advance(len(tokens))
        d.tokens = list(tokens)
        full = len(tokens) // cache.block_size
        want = fill_blocks(cache, [int(b) for b in d.blocks[:full]])
        mgr.flush_sequence(uid)
        return want

    TOKENS = list(range(12))      # 3 full blocks at block_size 4
    PROBE = list(range(13))       # one past the chain: export needs it

    def test_export_import_bit_identical_fp32(self):
        cache_a, mgr_a, pc_a, tier_a = self._stack()
        want = self._seed(cache_a, mgr_a, self.TOKENS)
        record = tier_a.export_chain(self.PROBE)
        assert record is not None and len(record["entries"]) == 3
        assert tier_a.stats()["exported_blocks"] == 3
        # replica independence: a separately built, identically seeded
        # stack derives the exact same chained keys
        cache_a2, mgr_a2, _, tier_a2 = self._stack()
        self._seed(cache_a2, mgr_a2, self.TOKENS)
        record2 = tier_a2.export_chain(self.PROBE)
        assert [e["key"] for e in record["entries"]] == \
            [e["key"] for e in record2["entries"]]

        cache_b, mgr_b, pc_b, tier_b = self._stack()
        assert tier_b.import_chain(record) == 3
        assert len(tier_b.store) == 3
        assert tier_b.stats()["imported_blocks"] == 3
        assert pc_b.match_len(self.PROBE) == 12
        blocks, cached = pc_b.acquire(2, self.PROBE)
        assert cached == 12 and len(blocks) == 3
        got = cache_b.gather(blocks)
        np.testing.assert_array_equal(got["k"], want["k"])
        np.testing.assert_array_equal(got["v"], want["v"])

    def test_export_import_int8_replicas_agree(self):
        """The same int8 record adopted by two decode replicas promotes
        to bit-equal KV on both (the record is the ground truth), and
        both stay within the symmetric-quant bound of the original."""
        cache_a, mgr_a, pc_a, tier_a = self._stack(quantize=True)
        want = self._seed(cache_a, mgr_a, self.TOKENS)
        record = tier_a.export_chain(self.PROBE)
        assert record["quantized"] is True
        assert all(e["handle"].get("quantized") for e in record["entries"])

        got = {}
        for name in ("b", "c"):
            cache_x, _, pc_x, tier_x = self._stack(quantize=True)
            assert tier_x.import_chain(record) == 3
            blocks, cached = pc_x.acquire(2, self.PROBE)
            assert cached == 12
            got[name] = cache_x.gather(blocks)
        for field in ("k", "v"):
            np.testing.assert_array_equal(got["b"][field], got["c"][field])
            bound = np.abs(want[field]).max() / 127.0 / 2.0 + 1e-5
            assert np.abs(got["b"][field] - want[field]).max() <= bound

    def test_forged_record_rejected_nothing_adopted(self):
        cache_a, mgr_a, _, tier_a = self._stack()
        self._seed(cache_a, mgr_a, self.TOKENS)
        record = tier_a.export_chain(self.PROBE)
        record["entries"][1]["tokens"] = (9, 9, 9, 9)  # identity forged
        _, _, _, tier_b = self._stack()
        with pytest.raises(KVTierCorruptionError, match="forged or corrupt"):
            tier_b.import_chain(record)
        assert len(tier_b.store) == 0
        assert tier_b.stats()["import_rejects"] == 1
        assert tier_b.stats()["imported_blocks"] == 0

    def test_torn_record_rejected_nothing_adopted(self):
        cache_a, mgr_a, _, tier_a = self._stack()
        self._seed(cache_a, mgr_a, self.TOKENS)
        # missing field (torn serialization)
        rec = tier_a.export_chain(self.PROBE)
        del rec["entries"][2]["handle"]
        _, _, _, tier_b = self._stack()
        with pytest.raises(KVTierCorruptionError, match="torn or truncated"):
            tier_b.import_chain(rec)
        # truncated block (short tokens)
        rec = tier_a.export_chain(self.PROBE)
        rec["entries"][0]["tokens"] = rec["entries"][0]["tokens"][:2]
        with pytest.raises(KVTierCorruptionError, match="truncated"):
            tier_b.import_chain(rec)
        # broken chain (entry dropped from the middle)
        rec = tier_a.export_chain(self.PROBE)
        del rec["entries"][1]
        with pytest.raises(KVTierCorruptionError, match="breaks the chain"):
            tier_b.import_chain(rec)
        assert len(tier_b.store) == 0
        assert tier_b.stats()["import_rejects"] == 3

    def test_engine_level_export_import_continues(self, model_and_params):
        """Engine A prefills, exports; engine B imports and serves the
        same prompt bit-identically with the prefill skipped past the
        imported span."""
        a = make_engine(model_and_params)
        want, _ = run_one(a, 1, PROMPT)
        record = a.export_prefix(PROMPT)
        assert record is not None
        assert len(record["entries"]) == (len(PROMPT) - 1) // BS  # 2 blocks

        b = make_engine(model_and_params)
        assert b.import_prefix(record) == 2
        assert b.prefix_match_len(PROMPT) == 16
        got, req = run_one(b, 7, PROMPT)
        assert got == want                       # bit-identical continuation
        assert req.prefix_cached_tokens == 16    # prefill skipped the span
        assert b.kv_tier.stats()["imported_blocks"] == 2
        a.destroy()
        b.destroy()
