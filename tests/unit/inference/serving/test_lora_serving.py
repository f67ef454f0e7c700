"""Multi-tenant LoRA serving: segmented kernel, AdapterStore, routing.

Four layers, mirroring ``test_refresh.py``:

- **Kernel tests** (CPU): the interpret-mode Pallas path agrees with
  the identical-math jnp fallback; base-slot rows contribute exactly
  nothing; a token's delta is independent of its batchmates (the
  arithmetic half of cross-tenant isolation).
- **Store tests**: registration, bind/release leases, LRU
  eviction/promotion round-trips through the host tier, capacity
  rejection when every hot slot is leased, rank-bucket validation.
- **Publication tests** on real files under ``tmp_path``: adapter
  rollout/rollback rides the WeightPublisher commit protocol — forged
  and torn publications are rejected typed with nothing adopted, and
  adopting onto a HOT adapter hot-swaps its slab rows in place without
  retracing the serving program.
- **Real-engine tests** over the v2 ragged engine: per-adapter streams
  bit-identical to solo runs under mixed batches (including
  heterogeneous ranks), and the ``DS_LORA=0`` kill switch rebuilding
  the exact pre-LoRA pipeline — outputs byte-identical, burst program
  keys unchanged.

Plus the gateway/fleet routing seams on the deterministic FakeEngine:
unknown adapters rejected typed at submit, bind failures at admission
fail the handle typed (capacity released), and the router places
adapter-affine with a prefetch kick on miss.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig,
                                        InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import LoRAServingConfig
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.ops.pallas.lora_matmul import (apply_lora_delta,
                                                  lora_delta_pallas,
                                                  lora_delta_ref,
                                                  segment_tokens)
from deepspeed_tpu.serving import ServingConfig
from deepspeed_tpu.serving.fleet import FleetConfig, FleetRouter, GatewayReplica
from deepspeed_tpu.serving.lora import (AdapterCapacityError, AdapterStore,
                                        UnknownAdapterError,
                                        lora_serving_enabled)
from deepspeed_tpu.utils.sanitize import WeightPublicationError
from unit.inference.serving.test_admission import (FakeEngine, make_gateway,
                                                   pump_until)


# ======================================================================
# kernel (CPU: interpret-mode Pallas vs jnp reference)
# ======================================================================
def _rand_case(seed=0, T=13, K=16, N=24, G=4, r=3):
    rs = np.random.RandomState(seed)
    x = rs.randn(T, K).astype(np.float32)
    slots = rs.randint(0, G, T).astype(np.int32)
    a = rs.randn(G, K, r).astype(np.float32) * 0.1
    b = rs.randn(G, r, N).astype(np.float32) * 0.1
    scales = rs.rand(G).astype(np.float32) + 0.5
    a[0] = 0.0
    b[0] = 0.0
    scales[0] = 0.0  # slot 0 = base
    return (jnp.asarray(x), jnp.asarray(slots), jnp.asarray(a),
            jnp.asarray(b), jnp.asarray(scales))


class TestSegmentedKernel:

    def test_interpret_matches_reference(self):
        x, slots, a, b, scales = _rand_case()
        ref = lora_delta_ref(x, slots, a, b, scales)
        ker = lora_delta_pallas(x, slots, a, b, scales, tm=8,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_base_slot_contributes_exactly_nothing(self):
        x, _, a, b, scales = _rand_case()
        slots = jnp.zeros(x.shape[0], jnp.int32)
        for impl in ("jnp", "interpret"):
            d = apply_lora_delta(x, slots, a, b, scales, impl=impl)
            assert np.array_equal(np.asarray(d), np.zeros_like(d))

    def test_row_independence_bitwise(self):
        """Each token's delta is bit-identical whatever shares the batch
        with it - other tenants' rows or the base slot's - the arithmetic
        half of the cross-tenant-isolation guarantee. Batches of one
        shape are compared: two *shapes* of one matmul (T rows, one row)
        are not added in one order on every backend, so the solo row is
        held to float32 rounding, not to its bits."""
        x, slots, a, b, scales = _rand_case(seed=3)
        T, G = x.shape[0], a.shape[0]
        others = jnp.asarray(np.random.RandomState(4).randn(*x.shape), x.dtype)

        def leaks(delta):
            """Does any row of ``delta``'s result move with its neighbours?"""
            mixed = np.asarray(delta(x, slots, a, b, scales))
            for t in range(T):
                own = jnp.arange(T) == t
                for xs, ss in ((others, (slots + 1) % G), (jnp.zeros_like(x), slots * 0)):
                    beside = delta(jnp.where(own[:, None], x, xs), jnp.where(own, slots, ss),
                                   a, b, scales)
                    if not np.array_equal(mixed[t], np.asarray(beside)[t]):
                        return True
            return False

        assert not leaks(lora_delta_ref)
        # the control: a neighbour's activations reaching a row by 1e-6 of their sum is seen
        assert leaks(lambda x, *rest: lora_delta_ref(x, *rest) + 1e-6 * jnp.sum(x[:, :1]))
        mixed = np.asarray(lora_delta_ref(x, slots, a, b, scales))
        for t in range(T):
            solo = np.asarray(lora_delta_ref(x[t:t + 1], slots[t:t + 1], a, b, scales))
            np.testing.assert_allclose(solo[0], mixed[t], rtol=1e-5, atol=1e-6)

    def test_segmentation_layout_is_static_and_grouped(self):
        slots = jnp.asarray([2, 0, 1, 2, 0, 2], jnp.int32)
        order, dst, tile_groups, Mp = segment_tokens(slots, 3, tm=4)
        assert Mp % 4 == 0 and tile_groups.shape[0] == Mp // 4
        # sorted rows land in slot order; each tile owned by one slot
        sorted_slots = np.asarray(slots)[np.asarray(order)]
        assert list(sorted_slots) == sorted(sorted_slots)


# ======================================================================
# AdapterStore (no engine)
# ======================================================================
DIMS = {"q_proj": (8, 8), "v_proj": (8, 8)}


def small_store(tmp_path=None, **kw):
    kw.setdefault("n_hot", 2)
    kw.setdefault("max_rank", 4)
    return AdapterStore(DIMS, num_layers=2,
                        publish_root=str(tmp_path) if tmp_path else None,
                        prefetch=False, **kw)


def mk_layers(seed, r, L=2):
    rs = np.random.RandomState(seed)
    return {s: (rs.randn(L, din, r).astype(np.float32),
                rs.randn(L, r, dout).astype(np.float32))
            for s, (din, dout) in DIMS.items()}


class TestAdapterStore:

    def test_register_bind_release_lease_cycle(self):
        st = small_store()
        assert st.register(101, mk_layers(1, 4), alpha=8.0) == 4
        assert st.known(101) and not st.has_adapter(101)
        slot = st.bind(uid=1, adapter_id=101)
        assert slot > 0 and st.has_adapter(101)
        assert st.slot_of(1) == slot
        assert st.bind(uid=1, adapter_id=101) == slot  # idempotent re-bind
        assert st.stats()["leases"] == 1
        st.release(1)
        assert st.stats()["leases"] == 0 and st.slot_of(1) == 0
        # base binds are slot 0, no lease
        assert st.bind(uid=2, adapter_id=0) == 0
        assert st.stats()["leases"] == 0

    def test_eviction_promotion_round_trip(self):
        st = small_store()
        for aid in (101, 102, 103):
            st.register(aid, mk_layers(aid, 2), alpha=4.0)
        s1 = st.bind(1, 101)
        st.bind(2, 102)
        st.release(1)  # 101 unleased: evictable
        s3 = st.bind(3, 103)  # hot set full -> evicts 101
        assert st.evictions == 1 and s3 == s1
        assert st.hot_set() == [102, 103]
        # round trip: re-binding 101 promotes it back from the host
        # tier with the original (padded) slab rows
        st.release(3)
        slot = st.bind(4, 101)
        a, b, scales = st.slabs()
        want_a, want_b = mk_layers(101, 2)["q_proj"]
        got_a = np.asarray(a["q_proj"][:, slot])
        assert np.array_equal(got_a[:, :, :2], want_a)
        assert np.array_equal(got_a[:, :, 2:], np.zeros_like(got_a[:, :, 2:]))
        assert np.array_equal(np.asarray(b["q_proj"][:, slot])[:, :2], want_b)
        assert float(scales[slot]) == pytest.approx(4.0 / 2)

    def test_capacity_rejection_carries_miss_hints(self):
        st = small_store()
        for aid in (101, 102, 103):
            st.register(aid, mk_layers(aid, 2), alpha=4.0)
        st.bind(1, 101)
        st.bind(2, 102)  # both slots leased
        with pytest.raises(AdapterCapacityError) as ei:
            st.bind(3, 103)
        err = ei.value
        assert err.retry_elsewhere and err.reason == "adapter_capacity"
        assert err.details["adapter_id"] == 103
        assert err.details["leased_slots"] == 2

    def test_unknown_and_overrank_rejected(self):
        st = small_store()
        with pytest.raises(UnknownAdapterError) as ei:
            st.bind(1, 999)
        assert not ei.value.retry_elsewhere
        with pytest.raises(ValueError, match="rank 8 exceeds"):
            st.register(101, mk_layers(1, 8), alpha=8.0)
        with pytest.raises(ValueError, match="positive"):
            st.register(0, mk_layers(1, 2), alpha=8.0)

    def test_invalidate_drops_hot_and_leases(self):
        st = small_store()
        st.register(101, mk_layers(1, 2), alpha=4.0)
        st.bind(1, 101)
        st.invalidate()  # base weight refresh
        assert not st.has_adapter(101) and st.stats()["leases"] == 0
        assert st.known(101)  # host payload survives; re-promotion works
        assert st.bind(2, 101) > 0


# ======================================================================
# publications (real files, WeightPublisher commit protocol)
# ======================================================================
class TestAdapterPublications:

    def test_publish_adopt_and_rollback(self, tmp_path):
        st = small_store(tmp_path)
        m = st.publish(101, mk_layers(1, 2), alpha=4.0)
        assert m["weight_version"] == 1
        st.publish(101, mk_layers(2, 2), alpha=4.0)
        assert st.adopt(101) == 2
        assert st.version_of(101) == 2
        # rollback = adopt the previous version
        assert st.adopt(101, version=1) == 1
        assert st.version_of(101) == 1

    def test_lazy_adopt_from_disk_on_bind(self, tmp_path):
        st = small_store(tmp_path)
        st.publish(101, mk_layers(1, 2), alpha=4.0)
        assert st.known(101)  # disk tier only
        assert st.bind(1, 101) > 0  # bind validates + adopts + promotes
        assert st.version_of(101) == 1

    def test_forged_publication_rejected_typed_nothing_adopted(self, tmp_path):
        st = small_store(tmp_path)
        st.publish(101, mk_layers(1, 2), alpha=4.0)
        st.adopt(101)
        st.publish(101, mk_layers(2, 2), alpha=4.0)
        # bit-flip v2's payload: same size, broken sha256
        import os
        payload = os.path.join(str(tmp_path), "adapter_000101",
                               "v00000002", "payload.bin")
        with open(payload, "r+b") as fd:
            fd.seek(10)
            byte = fd.read(1)
            fd.seek(10)
            fd.write(bytes([byte[0] ^ 0xFF]))
        with pytest.raises(WeightPublicationError):
            st.adopt(101, version=2)
        assert st.publish_rejects == 1
        assert st.version_of(101) == 1  # nothing adopted; v1 still serves
        assert st.bind(1, 101) > 0

    def test_torn_publication_invisible(self, tmp_path):
        crashed = {"arm": True}

        def hook(point, detail=None):
            if crashed["arm"] and point == "before_manifest" and detail == 2:
                raise RuntimeError("injected crash")

        st = small_store(tmp_path, test_hook=hook)
        st.publish(101, mk_layers(1, 2), alpha=4.0)
        with pytest.raises(RuntimeError, match="injected crash"):
            st.publish(101, mk_layers(2, 2), alpha=4.0)
        assert st.adopt(101) == 1  # the torn v2 is invisible to adopt()

    def test_hot_swap_in_place(self, tmp_path):
        st = small_store(tmp_path)
        st.publish(101, mk_layers(1, 2), alpha=4.0)
        st.adopt(101)
        slot = st.bind(1, 101)  # hot + leased (live traffic)
        new_layers = mk_layers(7, 2)
        st.publish(101, new_layers, alpha=4.0)
        st.adopt(101)  # in-place slab-row swap, no drain
        assert st.swaps == 1 and st.version_of(101) == 2
        assert st.slot_of(1) == slot  # lease intact
        a, _, _ = st.slabs()
        got = np.asarray(a["q_proj"][:, slot])[:, :, :2]
        assert np.array_equal(got, new_layers["q_proj"][0])


# ======================================================================
# real v2 engine: mixed-batch bit-identity and the kill switch
# ======================================================================
def make_engine(model, params, lora_on, hot_set=4, publish_root=None):
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=8,
        state_manager=DSStateManagerConfig(
            max_ragged_batch_size=64, max_ragged_sequence_count=4,
            max_tracked_sequences=4, max_context=64),
        lora=LoRAServingConfig(enabled=lora_on, hot_set=hot_set, max_rank=4,
                               prefetch=False,
                               publish_root=str(publish_root or "")))
    return InferenceEngineV2(model=model, config=cfg, params=params,
                             dtype=jnp.float32)


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def engine_adapter(store, seed, r):
    rs = np.random.RandomState(seed)
    return {site: (rs.randn(store.num_layers, din, r).astype(np.float32) * 0.05,
                   rs.randn(store.num_layers, r, dout).astype(np.float32) * 0.05)
            for site, (din, dout) in store.dims.items()}


def solo_stream(model, params, uid, adapter_id, prompt, k, adapters):
    eng = make_engine(model, params, True)
    for aid, (seed, r, alpha) in adapters.items():
        eng.register_adapter(aid, engine_adapter(eng.lora_store, seed, r),
                             alpha=alpha)
    if adapter_id:
        eng.bind_adapter(uid, adapter_id)
    logits = eng.put([uid], [prompt], sample=None)
    burst = eng.decode_burst([uid], [[int(np.argmax(logits[0]))]], k)
    eng.destroy()
    return np.asarray(logits[0]), np.asarray(burst[:, 0])


class TestEngineLoRA:
    ADAPTERS = {101: (1, 4, 8.0), 102: (2, 2, 4.0)}  # heterogeneous ranks

    def test_mixed_batch_bit_identical_to_solo(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params, True)
        st = eng.lora_store
        for aid, (seed, r, alpha) in self.ADAPTERS.items():
            eng.register_adapter(aid, engine_adapter(st, seed, r), alpha=alpha)
        eng.bind_adapter(11, 101)
        eng.bind_adapter(12, 102)
        p1 = (np.arange(10, dtype=np.int32) % 250) + 1
        p2 = ((np.arange(10) * 3) % 250 + 1).astype(np.int32)
        # uid 10 = base, 11 -> rank-4 adapter, 12 -> rank-2 adapter
        mixed = eng.put([10, 11, 12], [p1, p1, p2], sample=None)
        burst = eng.decode_burst(
            [10, 11, 12], [[int(np.argmax(mixed[i]))] for i in range(3)], 4)
        eng.destroy()
        for i, (uid, aid, prompt) in enumerate(
                [(10, 0, p1), (11, 101, p1), (12, 102, p2)]):
            logits, toks = solo_stream(model, params, uid, aid, prompt, 4,
                                       self.ADAPTERS)
            assert np.array_equal(np.asarray(mixed[i]), logits), \
                f"prefill logits differ for row {i} (adapter {aid})"
            assert np.array_equal(np.asarray(burst[:, i]), toks), \
                f"decode stream differs for row {i} (adapter {aid})"
        # and the adapters actually changed the output vs base
        assert not np.array_equal(np.asarray(mixed[0]), np.asarray(mixed[1]))

    def test_kill_switch_rebuilds_pre_lora_pipeline(self, model_and_params,
                                                    monkeypatch):
        model, params = model_and_params
        prompt = (np.arange(10, dtype=np.int32) % 250) + 1
        off = make_engine(model, params, False)
        logits_off = off.put([1], [prompt], sample=None)
        burst_off = off.decode_burst([1], [[7]], 4)
        keys_off = list(off._burst_fns.keys())
        off.destroy()
        # config says on; DS_LORA=0 wins in both directions
        monkeypatch.setenv("DS_LORA", "0")
        assert not lora_serving_enabled(LoRAServingConfig(enabled=True))
        killed = make_engine(model, params, True)
        assert killed.lora_store is None
        logits_k = killed.put([1], [prompt], sample=None)
        burst_k = killed.decode_burst([1], [[7]], 4)
        assert np.array_equal(np.asarray(logits_off), np.asarray(logits_k))
        assert np.array_equal(np.asarray(burst_off), np.asarray(burst_k))
        # program keys unchanged: the off state IS the pre-LoRA build
        assert list(killed._burst_fns.keys()) == keys_off
        killed.destroy()

    def test_hot_swap_mid_traffic_no_retrace(self, model_and_params,
                                             tmp_path):
        model, params = model_and_params
        eng = make_engine(model, params, True, publish_root=tmp_path)
        st = eng.lora_store
        eng.lora_store.publish(101, engine_adapter(st, 1, 2), alpha=4.0)
        eng.adopt_adapter(101)
        eng.bind_adapter(11, 101)
        prompt = (np.arange(10, dtype=np.int32) % 250) + 1
        logits = eng.put([11], [prompt], sample=None)
        eng.decode_burst([11], [[int(np.argmax(logits[0]))]], 4)
        n_programs = len(eng._burst_fns)
        # publish v2 and hot-swap while uid 11's lease is live
        eng.lora_store.publish(101, engine_adapter(st, 9, 2), alpha=4.0)
        assert eng.adopt_adapter(101) == 2
        assert st.swaps == 1 and st.version_of(101) == 2
        # traffic continues: same program (slabs are jit arguments)
        eng.decode_burst([11], [[3]], 4)
        assert len(eng._burst_fns) == n_programs
        # a fresh sequence on the swapped adapter serves v2 weights,
        # bit-identical to a cold engine that only ever saw v2
        eng.bind_adapter(12, 101)
        logits2 = eng.put([12], [prompt], sample=None)
        burst2 = eng.decode_burst([12], [[int(np.argmax(logits2[0]))]], 4)
        eng.destroy()
        ref = make_engine(model, params, True)
        ref.register_adapter(101, engine_adapter(st, 9, 2), alpha=4.0,
                             version=2)
        ref.bind_adapter(12, 101)
        logits_r = ref.put([12], [prompt], sample=None)
        burst_r = ref.decode_burst([12], [[int(np.argmax(logits_r[0]))]], 4)
        ref.destroy()
        assert np.array_equal(np.asarray(logits2), np.asarray(logits_r))
        assert np.array_equal(np.asarray(burst2), np.asarray(burst_r))


# ======================================================================
# gateway + fleet routing seams (FakeEngine — no device work)
# ======================================================================
class LoraFakeEngine(FakeEngine):
    """FakeEngine + the adapter surface the gateway/router probe."""

    def __init__(self, known=(), hot=(), bind_error=None, **kw):
        super().__init__(**kw)
        self.known_ids = set(known)
        self.hot_ids = set(hot)
        self.bind_error = bind_error
        self.bound = {}
        self.prefetch_kicks = []

    def knows_adapter(self, adapter_id):
        return int(adapter_id) in self.known_ids

    def has_adapter(self, adapter_id):
        return int(adapter_id) in self.hot_ids

    def prefetch_adapter(self, adapter_id):
        self.prefetch_kicks.append(int(adapter_id))

    def bind_adapter(self, uid, adapter_id):
        if self.bind_error is not None:
            raise self.bind_error
        self.bound[uid] = int(adapter_id)
        return 1


class TestGatewayAdapterRouting:

    def test_unknown_adapter_rejected_typed_at_submit(self):
        gw = make_gateway(LoraFakeEngine(known={7}))
        with pytest.raises(UnknownAdapterError) as ei:
            gw.submit([1, 2, 3], max_new_tokens=2, adapter_id=9)
        assert ei.value.details["adapter_id"] == 9
        assert not gw.engine.bound
        gw.shutdown()

    def test_known_adapter_binds_at_admission(self):
        eng = LoraFakeEngine(known={7})
        gw = make_gateway(eng)
        h = gw.submit([1, 2, 3], max_new_tokens=2, adapter_id=7)
        pump_until(gw, lambda: h.status == "completed")
        assert eng.bound == {h.uid: 7}
        gw.shutdown()

    def test_bind_failure_fails_handle_typed_and_releases_capacity(self):
        err = AdapterCapacityError("all slots leased", adapter_id=7,
                                   hot_slots=1, leased_slots=1)
        gw = make_gateway(LoraFakeEngine(known={7}, bind_error=err))
        h = gw.submit([1, 2, 3], max_new_tokens=2, adapter_id=7)
        pump_until(gw, lambda: h.status == "failed")
        assert h.error is err
        assert gw.gate.committed_blocks == 0  # capacity released
        # the gateway keeps serving base traffic afterwards
        h2 = gw.submit([1, 2, 3], max_new_tokens=2)
        pump_until(gw, lambda: h2.status == "completed")
        gw.shutdown()


def lora_replica(name, engine):
    return GatewayReplica(name, lambda: engine,
                          serving_config=ServingConfig(max_burst=1),
                          auto_start=True)


class TestFleetAdapterAffinity:

    def test_warm_replica_wins_placement(self):
        cold = LoraFakeEngine(known={7})
        warm = LoraFakeEngine(known={7}, hot={7})
        router = FleetRouter([lora_replica("r0", cold),
                              lora_replica("r1", warm)],
                             config=FleetConfig(retry_backoff_s=0.005),
                             auto_heartbeat=False)
        h = router.submit([1, 2, 3], max_new_tokens=2, adapter_id=7)
        h.result(timeout=10)
        assert h.replica_trail == ["r1"]
        assert router.snapshot()["counters"]["adapter_routed"] == 1
        router.shutdown()

    def test_miss_falls_back_least_loaded_with_prefetch_kick(self):
        engines = [LoraFakeEngine(known={7}), LoraFakeEngine(known={7})]
        router = FleetRouter([lora_replica("r0", engines[0]),
                              lora_replica("r1", engines[1])],
                             config=FleetConfig(retry_backoff_s=0.005),
                             auto_heartbeat=False)
        h = router.submit([1, 2, 3], max_new_tokens=2, adapter_id=7)
        h.result(timeout=10)
        assert router.snapshot()["counters"]["adapter_misses"] == 1
        kicked = [e for e in engines if 7 in e.prefetch_kicks]
        assert len(kicked) == 1  # exactly the chosen replica
        router.shutdown()
