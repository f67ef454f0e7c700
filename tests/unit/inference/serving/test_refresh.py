"""Live weight refresh: publications, staged no-drain swap, rollback.

Three layers, mirroring ``test_disagg.py``:

- **Publisher tests** on real files under ``tmp_path``: the atomic
  commit protocol, the chained content hash over the version lineage,
  and the trust boundary — torn, bit-flipped, forged, and
  wrong-lineage publications are all rejected typed with nothing
  adopted.
- **Logic tests** on a deterministic version-aware FakeEngine variant
  (token stream is a pure function of tokens ingested AND the adopted
  weights — the property real greedy decoding has): the gateway's
  staged-swap protocol (admission held, in-flight finishes on the old
  weights, zero requests shed), version-tagged handoff invalidation,
  and every controller path — canary gate, fleet-wide rollback,
  health demotion — driven through the scripted refresh fault modes.
- **Real-engine tests** over the v2 ragged engine: ``swap_params``
  produces streams bit-identical to a cold-started engine on the new
  weights, and version-tagged invalidation guarantees stale KV never
  serves them; plus the refresh-under-traffic chaos run with
  DS_SANITIZE=1 (zero lost requests, every stream single-version).
"""

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig,
                                        DynamicSplitFuseScheduler,
                                        InferenceEngineV2, KVTierConfig,
                                        PrefixCacheConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kv_tier import TierManager
from deepspeed_tpu.inference.v2.prefix_cache import PrefixCacheManager
from deepspeed_tpu.inference.v2.prefix_cache.radix_index import _chunk_key
from deepspeed_tpu.inference.v2.ragged import DSStateManager
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.serving import (CanaryDivergenceError, FaultyReplica,
                                   FleetConfig, FleetRefreshController,
                                   FleetRouter, GatewayClosedError,
                                   GatewayFailedError, GatewayReplica,
                                   ServingConfig, WeightPublisher,
                                   WeightRefreshError)
from deepspeed_tpu.serving.refresh.publisher import (LATEST, MANIFEST_NAME,
                                                     PAYLOAD_NAME)
from deepspeed_tpu.utils.sanitize import (KVTierCorruptionError,
                                          WeightPublicationError,
                                          check_handoff_record,
                                          reset_lock_graph)
from unit.inference.serving.test_admission import (FakeEngine, make_gateway,
                                                   pump_until)
from unit.inference.v2.test_kv_tier import fill_blocks, small_pool

BS = 8  # fake block size used by the fabricated handoff records
PROMPT = list(range(1, 13))  # 12 tokens


# ======================================================================
# harness
# ======================================================================
def params_for(v):
    """The param tree published as weight version ``v``."""
    return {"v": np.asarray(int(v))}


class VersionedEngine(FakeEngine):
    """FakeEngine whose token stream is a pure function of (tokens
    ingested, adopted weights) — the property real greedy decoding has,
    which is what makes the canary's bit-identical comparison against a
    cold start meaningful. Implements the ``swap_params`` surface with
    the real engine's quiet-engine precondition."""

    def __init__(self, params=None, **kw):
        super().__init__(**kw)
        self.params = params_for(0) if params is None else params
        self.weight_version = 0
        self.swaps = []  # every adopted version, in order

    def _v(self):
        return int(np.asarray(self.params["v"]))

    def put(self, uids, chunks, sample=None):
        out = []
        for uid, toks in zip(uids, chunks):
            self._seen[uid] = self._seen.get(uid, 0) + len(toks)
            out.append((self._seen[uid] + 31 * self._v()) % 97)
        return np.asarray(out, np.int32)

    @staticmethod
    def stream(prompt_len, n, v=0):
        return [(prompt_len + i + 31 * v) % 97 for i in range(n)]

    def swap_params(self, new_params, version):
        if self._seen or self._suspended:
            raise RuntimeError("swap_params with live sequences")
        self.params = new_params
        self.weight_version = int(version)
        self.swaps.append(int(version))
        return int(version)


def cold_reference(params, prompt, max_new):
    """The canary oracle: what a COLD-STARTED VersionedEngine on
    ``params`` greedy-decodes for ``prompt``."""
    return VersionedEngine.stream(len(prompt), max_new,
                                  v=int(np.asarray(params["v"])))


def record_for(prompt, root_key):
    """A handoff record exported under weight version ``root_key``
    (chained keys derive from the version-tagged root)."""
    toks = tuple(int(t) for t in prompt[:BS])
    return {"version": 1, "block_size": BS, "root_key": root_key,
            "quantized": False,
            "entries": [{"key": _chunk_key(root_key, toks),
                         "parent_key": root_key, "tokens": toks,
                         "handle": {"k": 1, "v": 1}, "nbytes": 64}]}


def refresh_engine(params=None):
    """VersionedEngine wearing the handoff surface, version-tagged: the
    export stamps the current weight version as the record's root key
    and the import validates against it — the engine-level contract the
    real tier machinery implements."""
    eng = VersionedEngine(params)
    eng.export_prefix = lambda prompt, max_blocks=None: record_for(
        prompt, eng.weight_version)

    def _imp(record):
        check_handoff_record(record, block_size=BS,
                             root_key=eng.weight_version)
        return len(record["entries"])
    eng.import_prefix = _imp
    return eng


def fleet(n=3, faulty=True, **cfg):
    """``n`` live-pump gateway replicas (wrapped in no-fault
    FaultyReplicas so tests can arm refresh faults later) behind a
    router. → (router, replicas, engines)."""
    reps, engines = [], []
    for i in range(n):
        eng = refresh_engine()
        engines.append(eng)
        rep = GatewayReplica(f"r{i}", (lambda e=eng: e),
                             serving_config=ServingConfig(max_burst=1),
                             auto_start=True)
        reps.append(FaultyReplica(rep) if faulty else rep)
    cfg.setdefault("retry_backoff_s", 0.005)
    router = FleetRouter(reps, config=FleetConfig(**cfg),
                         auto_heartbeat=False)
    return router, reps, engines


def controller(router, **kw):
    kw.setdefault("reference_fn", cold_reference)
    kw.setdefault("baseline_params", params_for(0))
    return FleetRefreshController(router, **kw)


@pytest.fixture
def shutdown():
    """Collect routers/gateways to tear down after the test body."""
    doomed = []
    yield doomed.append
    for obj in doomed:
        try:
            obj.shutdown()
        except Exception:
            pass


def tree_for(v):
    """A richer publication tree (nested dicts + a list) so the
    flatten/unflatten round trip is exercised, deterministic in ``v``."""
    rng = np.random.default_rng(1000 + v)
    return {"v": np.asarray(int(v)),
            "layers": [{"w": rng.standard_normal((3, 4)).astype(np.float32),
                        "b": np.arange(4, dtype=np.int32) + v}
                       for _ in range(2)],
            "head": {"scale": np.float32(0.5 + v)}}


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ======================================================================
# publisher: commit protocol + trust boundary
# ======================================================================
class TestWeightPublisher:

    def test_publish_load_roundtrip_and_chain(self, tmp_path):
        pub = WeightPublisher(tmp_path)
        m1 = pub.publish(tree_for(1))
        m2 = pub.publish(tree_for(2))
        assert pub.versions() == [1, 2]
        assert pub.latest_version() == 2
        # the chain links: v2's parent_chain IS v1's chain
        assert m1["parent_chain"] is None and m2["parent_chain"] == m1["chain"]
        assert m2["chain"] != m1["chain"]
        assert pub.verify_chain() == [1, 2]
        with open(os.path.join(pub.dir, LATEST)) as fd:
            assert fd.read().strip() == "v00000002"
        # load the latest, lineage pinned to the adopted chain
        tree, manifest = pub.load(expect_parent_chain=m1["chain"])
        assert manifest["weight_version"] == 2
        assert_trees_equal(tree, tree_for(2))
        # list positions survive the round trip as a real list, and
        # scalar (0-d) leaves keep their shape
        assert isinstance(tree["layers"], list) and len(tree["layers"]) == 2
        assert np.asarray(tree["v"]).shape == ()
        assert np.asarray(tree["head"]["scale"]).shape == ()
        assert pub.publishes == 2 and pub.rejects == 0

    def test_version_must_advance_lineage(self, tmp_path):
        pub = WeightPublisher(tmp_path)
        pub.publish(params_for(1), version=3)
        with pytest.raises(WeightPublicationError, match="advance"):
            pub.publish(params_for(2), version=3)
        with pytest.raises(WeightPublicationError, match="advance"):
            pub.publish(params_for(2), version=2)
        assert pub.versions() == [3]

    def test_torn_publication_invisible_and_rejected(self, tmp_path):
        """A crash before the manifest write leaves NOTHING adoptable:
        the version is invisible to the scan and a direct load is a
        typed reject, not a half-read tree."""
        armed = {"point": "before_manifest"}

        def hook(point, detail=None):
            if point == armed.get("point") and detail == 2:
                raise RuntimeError(f"injected crash at {point}")

        pub = WeightPublisher(tmp_path, test_hook=hook)
        pub.publish(params_for(1))
        with pytest.raises(RuntimeError, match="injected crash"):
            pub.publish(params_for(2))
        assert pub.versions() == [1] and pub.latest_version() == 1
        with pytest.raises(WeightPublicationError, match="nothing to adopt"):
            pub.load(2)
        assert pub.rejects == 1
        # the retry (same version, crash disarmed) commits cleanly
        armed["point"] = None
        m2 = pub.publish(params_for(2))
        assert pub.versions() == [1, 2] and m2["weight_version"] == 2
        pub.verify_chain()

    def test_crash_between_promote_and_latest_still_committed(self, tmp_path):
        """The manifest scan is authoritative: a publication promoted
        before the LATEST rotation crashed is still found and loads."""
        def hook(point, detail=None):
            if point == "before_latest":
                raise RuntimeError("injected crash before LATEST")

        pub = WeightPublisher(tmp_path, test_hook=hook)
        with pytest.raises(RuntimeError):
            pub.publish(params_for(1))
        assert not os.path.exists(os.path.join(pub.dir, LATEST))
        assert pub.latest_version() == 1
        tree, _ = pub.load()
        assert int(np.asarray(tree["v"])) == 1

    def test_payload_bitflip_rejected(self, tmp_path):
        """Same-size bit-level corruption slips past the size check but
        fails the per-file sha256 — typed reject, nothing adopted."""
        pub = WeightPublisher(tmp_path)
        pub.publish(tree_for(1))
        payload = os.path.join(pub.dir, "v00000001", PAYLOAD_NAME)
        size = os.path.getsize(payload)
        with open(payload, "r+b") as fd:
            fd.seek(size // 2)
            byte = fd.read(1)
            fd.seek(size // 2)
            fd.write(bytes([byte[0] ^ 0xFF]))
        assert os.path.getsize(payload) == size
        with pytest.raises(WeightPublicationError, match="corruption"):
            pub.load(1)
        assert pub.rejects == 1

    def test_forged_manifest_rejected(self, tmp_path):
        """Editing the manifest breaks the chained-hash re-derivation;
        grafting a publication onto a different lineage breaks the
        parent-chain pin."""
        import json
        pub = WeightPublisher(tmp_path)
        m1 = pub.publish(params_for(1))
        pub.publish(params_for(2))
        mpath = os.path.join(pub.dir, "v00000002", MANIFEST_NAME)
        with open(mpath) as fd:
            forged = json.load(fd)
        forged["files"][PAYLOAD_NAME]["bytes"] += 1
        with open(mpath, "w") as fd:
            json.dump(forged, fd)
        with pytest.raises(WeightPublicationError):
            pub.load(2)
        with pytest.raises(WeightPublicationError):
            pub.verify_chain()
        # wrong lineage: valid publication, wrong adopted chain
        with pytest.raises(WeightPublicationError, match="lineage"):
            pub.load(1, expect_parent_chain=m1["chain"])
        assert pub.rejects == 2  # the two load() calls; verify_chain is a walk

    def test_gc_keeps_rollback_target(self, tmp_path):
        pub = WeightPublisher(tmp_path, keep=2)
        for v in (1, 2, 3):
            pub.publish(params_for(v))
        assert pub.versions() == [2, 3]  # previous version always kept
        assert not os.path.isdir(os.path.join(pub.dir, "v00000001"))
        pub.load(2)  # the rollback target still validates + loads
        assert pub.verify_chain() == [2, 3]

    def test_keep_floor_is_two(self, tmp_path):
        assert WeightPublisher(tmp_path, keep=1).keep == 2


# ======================================================================
# gateway: staged no-drain swap (manual pump — deterministic interleave)
# ======================================================================
class TestGatewayRefresh:

    def test_staged_swap_drops_nothing_and_versions_streams(self):
        """In-flight streams finish on the OLD weights; a request queued
        behind the refresh waits it out (never shed) and streams
        entirely on the NEW weights."""
        eng = refresh_engine()
        gw = make_gateway(eng)
        h1 = gw.submit(PROMPT, max_new_tokens=4)
        pump_until(gw, lambda: gw.inflight()["active"] == 1)
        h2 = gw.submit(list(range(21, 27)), max_new_tokens=3)

        assert gw.refresh_weights(params_for(1), 1, timeout=5.0) == 1
        assert gw.weight_version == 1 and eng.swaps == [1]
        assert gw.metrics.snapshot()["counters"]["weight_refreshes"] == 1
        # h1 was in flight when the swap staged: old weights end to end
        assert list(h1.tokens(timeout=5.0)) == VersionedEngine.stream(12, 4, 0)
        # h2 was queued behind the held admission: new weights end to end
        pump_until(gw, lambda: h2.done)   # a step's tokens: behind the next dispatch, or a pass that has none
        assert list(h2.tokens(timeout=5.0)) == VersionedEngine.stream(6, 3, 1)
        assert gw.metrics.snapshot()["counters"].get("failed", 0) == 0
        gw.shutdown()

    def test_outbox_cleared_and_cross_version_import_rejected(self):
        """Handoff records exported under version N are purged at the
        swap, and a version-N record offered to the version-N+1 engine
        is rejected typed with nothing adopted."""
        eng = refresh_engine()
        gw = make_gateway(eng, role="prefill")
        h = gw.submit(PROMPT, max_new_tokens=2)
        pump_until(gw, lambda: h.done)
        list(h.tokens(timeout=5.0))
        assert len(gw._handoffs) == 1  # prefill finish exported a record
        stale = record_for(PROMPT, 0)
        assert gw.import_handoff(stale) == 1  # same-version import adopts

        gw.refresh_weights(params_for(1), 1, timeout=5.0)
        assert gw._handoffs == {}  # exported records predate the new weights
        with pytest.raises(KVTierCorruptionError, match="root_key"):
            gw.import_handoff(stale)
        # a record exported UNDER the new version round-trips
        assert gw.import_handoff(record_for(PROMPT, 1)) == 1
        gw.shutdown()

    def test_timeout_withdraws_staged_swap_nothing_adopted(self):
        class SlowEngine(VersionedEngine):
            def put(self, uids, chunks, sample=None):
                time.sleep(0.02)
                return super().put(uids, chunks, sample=sample)

        eng = SlowEngine()
        gw = make_gateway(eng)
        h = gw.submit(PROMPT, max_new_tokens=30)
        pump_until(gw, lambda: gw.inflight()["active"] == 1)
        with pytest.raises(TimeoutError, match="nothing adopted"):
            gw.refresh_weights(params_for(1), 1, timeout=0.05)
        assert gw.weight_version == 0 and eng.swaps == []
        assert gw._pending_refresh is None  # withdrawn; admission resumes
        # the in-flight stream was never disturbed: full length, old weights
        pump_until(gw, lambda: h.done, n=400)
        assert list(h.tokens(timeout=5.0)) == VersionedEngine.stream(12, 30, 0)
        # and a later unhurried refresh adopts cleanly
        assert gw.refresh_weights(params_for(1), 1, timeout=5.0) == 1
        gw.shutdown()

    def test_mid_swap_crash_fails_replica_typed(self):
        """A swap that dies half way must look like a replica crash —
        gateway failed, queued work failed TYPED (router replays it
        elsewhere), never a silently half-refreshed replica."""
        eng = refresh_engine()

        def boom(params, version):
            raise RuntimeError("donated buffer torn mid-swap")
        eng.swap_params = boom
        gw = make_gateway(eng)
        h = gw.submit(PROMPT, max_new_tokens=4)  # queued; engine is quiet
        with pytest.raises(RuntimeError, match="mid-swap"):
            gw.refresh_weights(params_for(1), 1, timeout=5.0)
        assert gw._state == "failed"
        with pytest.raises(GatewayFailedError):
            list(h.tokens(timeout=5.0))
        with pytest.raises(GatewayFailedError):
            gw.submit(PROMPT, max_new_tokens=1)

    def test_refresh_rejected_off_running(self):
        gw = make_gateway(refresh_engine())
        gw.drain()
        with pytest.raises(GatewayClosedError):
            gw.refresh_weights(params_for(1), 1, timeout=1.0)

    def test_double_refresh_rejected(self):
        """Two concurrent staged swaps cannot interleave."""
        eng = refresh_engine()
        gw = make_gateway(eng)
        h = gw.submit(PROMPT, max_new_tokens=50)
        pump_until(gw, lambda: gw.inflight()["active"] == 1)
        gw._pending_refresh = {"params": params_for(1), "version": 1,
                               "done": threading.Event(), "error": None}
        with pytest.raises(RuntimeError, match="already in progress"):
            gw.refresh_weights(params_for(2), 2, timeout=0.5)
        gw._pending_refresh = None
        h.cancel()
        gw.shutdown()


# ======================================================================
# controller: rollout, canary, rollback, demotion (live-pump fleet)
# ======================================================================
class TestFleetRollout:

    def test_rollout_happy_path(self, shutdown):
        router, reps, engines = fleet(3)
        shutdown(router)
        ctrl = controller(router)
        h0 = router.submit(PROMPT, max_new_tokens=3)
        assert list(h0.tokens(timeout=5.0)) == VersionedEngine.stream(12, 3, 0)

        report = ctrl.rollout(version=1, params=params_for(1))
        assert report["refreshed"] == ["r0", "r1", "r2"]
        assert report["canary"] == "passed"
        assert report["rolled_back"] is False and report["demoted"] == []
        assert ctrl.current_version == 1 and ctrl.rollouts == 1
        assert all(eng.swaps == [1] for eng in engines)
        assert all(rep.weight_version() == 1 for rep in reps)
        c = router.snapshot()["counters"]
        assert c["refreshes"] == 1 and c["refresh_rollbacks"] == 0

        h1 = router.submit(PROMPT, max_new_tokens=3)
        assert list(h1.tokens(timeout=5.0)) == VersionedEngine.stream(12, 3, 1)
        with pytest.raises(WeightRefreshError, match="already"):
            ctrl.rollout(version=1, params=params_for(1))

    def test_rollout_from_publisher_pins_lineage(self, tmp_path, shutdown):
        router, reps, engines = fleet(2)
        shutdown(router)
        pub = WeightPublisher(tmp_path, keep=4)
        ctrl = controller(router, publisher=pub)
        pub.publish(params_for(1))
        r1 = ctrl.rollout()  # resolves the latest publication
        assert r1["version"] == 1 and ctrl.current_chain == pub.manifest(1)["chain"]
        pub.publish(params_for(2))
        r2 = ctrl.rollout()
        assert r2["version"] == 2 and r2["canary"] == "passed"
        assert all(rep.weight_version() == 2 for rep in reps)

        # a torn later publication: typed reject, NOTHING adopted anywhere
        pub.publish(params_for(3))
        payload = os.path.join(pub.dir, "v00000003", PAYLOAD_NAME)
        with open(payload, "r+b") as fd:
            fd.write(b"\xff")
        with pytest.raises(WeightPublicationError):
            ctrl.rollout()
        assert ctrl.current_version == 2
        assert all(rep.weight_version() == 2 for rep in reps)
        assert all(eng.swaps == [1, 2] for eng in engines)

    def test_version_lie_trips_canary_and_rolls_back(self, shutdown):
        """A replica that reports the new version without adopting it is
        caught by the bit-identical canary gate before a second replica
        refreshes; the fleet rolls back with zero requests dropped."""
        router, reps, engines = fleet(3)
        shutdown(router)
        ctrl = controller(router)
        reps[0].lie_version = True

        report = ctrl.rollout(version=1, params=params_for(1))
        assert report["canary"] == "diverged"
        assert report["rolled_back"] is True
        assert "canary divergence on r0" in report["reason"]
        assert report["refreshed"] == []
        assert report["rolled_back_replicas"] == ["r0"]
        # no engine ever adopted v1; the fleet still serves v0
        assert all(eng.swaps == [] for eng in engines)
        assert ctrl.current_version == 0 and ctrl.rollouts == 0
        c = router.snapshot()["counters"]
        assert c["canary_divergences"] == 1 and c["refresh_rollbacks"] == 1
        assert c["refreshes"] == 0
        h = router.submit(PROMPT, max_new_tokens=3)
        assert list(h.tokens(timeout=5.0)) == VersionedEngine.stream(12, 3, 0)

    def test_crash_mid_swap_rolls_back_fleet(self, shutdown):
        """A replica dying mid-swap aborts the rollout: the already-
        refreshed replica returns to the previous version (no-drain),
        the dead one is DOWN, and traffic keeps flowing on v0."""
        router, reps, engines = fleet(3)
        shutdown(router)
        ctrl = controller(router)
        reps[1].crash_mid_swap = True

        report = ctrl.rollout(version=1, params=params_for(1))
        assert report["rolled_back"] is True
        assert "r1 crashed mid-swap" in report["reason"]
        assert report["rolled_back_replicas"] == ["r0"]
        assert engines[0].swaps == [1, 0]  # adopted, then rolled back
        assert engines[1].swaps == [] and engines[2].swaps == []
        assert router.health["r1"].snapshot()["state"] == "down"
        assert router.snapshot()["counters"]["refresh_rollbacks"] == 1
        h = router.submit(PROMPT, max_new_tokens=3)
        assert list(h.tokens(timeout=5.0)) == VersionedEngine.stream(12, 3, 0)

    def test_torn_publication_at_replica_rolls_back(self, shutdown):
        """A typed WeightPublicationError from a replica means the
        publication cannot be trusted: abort + roll back, don't demote
        the messenger and press on."""
        router, reps, engines = fleet(2)
        shutdown(router)
        ctrl = controller(router)
        reps[1].refresh_torn = True

        report = ctrl.rollout(version=1, params=params_for(1))
        assert report["rolled_back"] is True
        assert engines[0].swaps == [1, 0] and engines[1].swaps == []
        assert ctrl.current_version == 0

    def test_slow_adopter_demoted_rollout_continues(self, shutdown):
        """Convergence failures demote ONE replica through the health
        machine; the rollout completes on the rest (no rollback)."""
        router, reps, engines = fleet(3, refresh_canary=False,
                                      refresh_timeout_s=0.05,
                                      refresh_demote_after=2)
        shutdown(router)
        ctrl = controller(router, reference_fn=None)
        reps[1].slow_adopt_s = 5.0

        report = ctrl.rollout(version=1, params=params_for(1))
        assert report["refreshed"] == ["r0", "r2"]
        assert report["demoted"] == ["r1"]
        assert report["rolled_back"] is False and report["canary"] == "skipped"
        assert ctrl.current_version == 1
        assert engines[0].swaps == [1] and engines[2].swaps == [1]
        assert engines[1].swaps == []
        assert router.health["r1"].snapshot()["state"] == "down"
        assert router.snapshot()["counters"]["refresh_demotions"] == 1

    def test_no_replica_adopts_raises_typed(self, shutdown):
        router, reps, engines = fleet(2, refresh_canary=False,
                                      refresh_timeout_s=0.05,
                                      refresh_demote_after=1)
        shutdown(router)
        ctrl = controller(router, reference_fn=None)
        for rep in reps:
            rep.slow_adopt_s = 5.0
        with pytest.raises(WeightRefreshError, match="no replica adopted"):
            ctrl.rollout(version=1, params=params_for(1))
        assert ctrl.current_version == 0
        assert all(eng.swaps == [] for eng in engines)

    def test_canary_knobs(self, monkeypatch, shutdown):
        router, reps, engines = fleet(1)
        shutdown(router)
        # canary on (config default) without an oracle: typed refusal
        ctrl = FleetRefreshController(router, baseline_params=params_for(0))
        with pytest.raises(WeightRefreshError, match="reference_fn"):
            ctrl.rollout(version=1, params=params_for(1))
        assert engines[0].swaps == []  # refused BEFORE any replica swap
        # DS_REFRESH_CANARY=0 force-disables the gate
        monkeypatch.setenv("DS_REFRESH_CANARY", "0")
        report = ctrl.rollout(version=1, params=params_for(1))
        assert report["canary"] == "skipped" and engines[0].swaps == [1]
        monkeypatch.setenv("DS_REFRESH_TIMEOUT_S", "7")
        assert ctrl._timeout() == 7.0


# ======================================================================
# version-tagged KV invalidation: the real tier machinery
# ======================================================================
class TestVersionedKVInvalidation:

    def test_stale_tier2_chain_never_crosses_versions(self):
        """A chain exported (or merely demoted) under weight version N
        is unreachable after ``invalidate_for_version(N+1)``: the trie
        and host store are empty, the root is re-keyed, and importing
        the stale record is a typed reject that adopts nothing."""
        cache = small_pool(10)
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        pc = PrefixCacheManager(cache)
        mgr.attach_prefix_cache(pc)
        tier = TierManager(pc, 1 << 20, quantize=False, prefetch=False)
        pc.attach_tier(tier)

        # retire one sequence so its full blocks land in the trie...
        tokens = list(range(12))
        d = mgr.get_or_create_sequence(1)
        mgr.allocate_for(d, len(tokens))
        d.advance(len(tokens))
        d.tokens = tokens
        full = len(tokens) // cache.block_size
        fill_blocks(cache, [int(b) for b in d.blocks[:full]])
        mgr.flush_sequence(1)
        assert pc.cached_blocks == full

        record = tier.export_chain(tokens + [99])
        old_root = pc.index.root.key
        assert record is not None and record["root_key"] == old_root

        # ...then refresh the weights: everything version-N is gone
        pc.invalidate_for_version(7)
        assert pc.index.root.key == 7 and pc.index.root.key != old_root
        assert pc.cached_blocks == 0 and len(tier.store) == 0
        assert pc.match_len(tokens + [99]) == 0  # stale KV unreachable

        with pytest.raises(KVTierCorruptionError, match="root_key"):
            tier.import_chain(record)
        assert tier.import_rejects == 1
        assert len(tier.store) == 0  # typed reject adopted NOTHING

    def test_invalidate_refuses_outstanding_leases(self):
        cache = small_pool(10)
        mgr = DSStateManager(cache, max_tracked_sequences=4)
        pc = PrefixCacheManager(cache)
        mgr.attach_prefix_cache(pc)
        tokens = list(range(12))
        d = mgr.get_or_create_sequence(1)
        mgr.allocate_for(d, len(tokens))
        d.advance(len(tokens))
        d.tokens = tokens
        mgr.flush_sequence(1)
        pc.acquire(2, tokens + [99])  # an in-flight lease on the chain
        with pytest.raises(RuntimeError, match="lease"):
            pc.invalidate_for_version(1)
        pc.release_lease(2)
        pc.invalidate_for_version(1)  # quiesced: allowed
        assert pc.cached_blocks == 0


# ======================================================================
# real engine: swap_params is bit-identical to a cold start
# ======================================================================
EBS = 8  # real engine KV block size
REAL_PROMPT = [int(t) for t in (np.arange(1, 25) % 250)]  # 24 tok = 3 blocks


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_real_engine(model_and_params, params=None):
    model, base = model_and_params
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=EBS,
        prefix_cache=PrefixCacheConfig(enabled=True),
        kv_tier=KVTierConfig(enabled=True, host_bytes=1 << 20),
        state_manager=DSStateManagerConfig(max_ragged_batch_size=64,
                                           max_ragged_sequence_count=4,
                                           max_tracked_sequences=4,
                                           max_context=64))
    return InferenceEngineV2(model=model, config=cfg,
                             params=base if params is None else params,
                             dtype=jnp.float32)


def run_real(engine, uid, prompt, max_new=6):
    sched = DynamicSplitFuseScheduler(engine, token_budget=48, max_burst=1)
    sched.add_request(uid, prompt, max_new_tokens=max_new)
    return [int(t) for t in sched.run_to_completion()[uid]]


def perturbed(params, seed=3):
    """A genuinely different publication: every float leaf gets
    deterministic noise, on HOST numpy (the publish/load wire form)."""
    rng = np.random.default_rng(seed)

    def bump(x):
        a = np.asarray(x)
        if np.issubdtype(a.dtype, np.floating):
            return (a + rng.standard_normal(a.shape).astype(a.dtype)
                    * (0.1 * (np.abs(a).mean() + 1.0))).astype(a.dtype)
        return a
    return jax.tree.map(bump, params)


class TestRefreshRealEngine:

    def test_swap_bit_identical_to_cold_start(self, model_and_params):
        """The acceptance criterion, on the real v2 engine: after
        ``swap_params`` the greedy stream is bit-identical to a COLD-
        STARTED engine on the new weights; the prefix trie is re-keyed
        (no stale-KV reuse across versions) and a handoff record
        exported under the old version is a typed reject."""
        eng = make_real_engine(model_and_params)
        s0 = run_real(eng, 1, REAL_PROMPT)
        assert eng.prefix_match_len(REAL_PROMPT) > 0  # chain cached at v0
        stale = eng.export_prefix(REAL_PROMPT + [99])
        assert stale is not None and stale["root_key"] == 0

        new_params = perturbed(model_and_params[1])
        cold = make_real_engine(model_and_params, params=new_params)
        s_cold = run_real(cold, 1, REAL_PROMPT)
        cold.destroy()

        assert eng.swap_params(new_params, 1) == 1
        assert eng.weight_version == 1
        assert eng.prefix_match_len(REAL_PROMPT) == 0  # v0 KV unreachable
        with pytest.raises(KVTierCorruptionError, match="root_key"):
            eng.import_prefix(stale)  # v0 record at v1: typed reject

        s1 = run_real(eng, 2, REAL_PROMPT)
        assert s1 == s_cold  # refresh path == cold start, bit for bit
        assert s1 != s0     # and the weights actually changed

        # records exported AFTER the swap carry the new root key and
        # round-trip into a same-version peer
        rec1 = eng.export_prefix(REAL_PROMPT + [99])
        assert rec1 is not None and rec1["root_key"] == 1
        eng.destroy()

    def test_swap_refuses_live_sequences(self, model_and_params):
        eng = make_real_engine(model_and_params)
        sched = DynamicSplitFuseScheduler(eng, token_budget=48, max_burst=1)
        sched.add_request(1, REAL_PROMPT, max_new_tokens=4)
        sched.step()  # sequence now tracked: the engine is NOT quiesced
        with pytest.raises(RuntimeError, match="quiesce"):
            eng.swap_params(perturbed(model_and_params[1]), 1)
        sched.run_to_completion()
        eng.swap_params(perturbed(model_and_params[1]), 1)  # idle: allowed
        eng.destroy()


# ======================================================================
# chaos: refresh under traffic with the sanitizer armed
# ======================================================================
class TestRefreshChaos:

    def test_refresh_under_traffic_zero_lost_single_version(
            self, monkeypatch, shutdown):
        """Client threads hammer the fleet while a clean rollout to v1
        lands and a poisoned rollout to v2 (version-report liar) rolls
        back. DS_SANITIZE=1 arms the handoff validators and the runtime
        lock-order sanitizer for the whole run. Invariants: ZERO lost
        requests, and every stream is single-version — each equals a
        cold v0 or v1 stream bit-exactly (never v2, never a mid-stream
        weight change, never stale KV)."""
        monkeypatch.setenv("DS_SANITIZE", "1")
        reset_lock_graph()
        router, reps, engines = fleet(3)
        shutdown(router)
        ctrl = controller(router)

        results, failures = [], []
        res_lock = threading.Lock()
        stop = threading.Event()
        submitted = [0, 0, 0]

        def client(k):
            i = 0
            while i < 12 or not stop.is_set():
                plen = 3 + (5 * k + i) % 5
                prompt = list(range(1, plen + 1))
                submitted[k] += 1
                try:
                    h = router.submit(prompt, max_new_tokens=4)
                    toks = [int(t) for t in h.tokens(timeout=10.0)]
                    with res_lock:
                        results.append((plen, toks))
                except Exception as e:  # noqa: BLE001 — chaos audit
                    with res_lock:
                        failures.append((k, i, repr(e)))
                i += 1

        threads = [threading.Thread(target=client, args=(k,), daemon=True)
                   for k in range(3)]
        for t in threads:
            t.start()
        try:
            time.sleep(0.02)  # let traffic establish on v0
            r1 = ctrl.rollout(version=1, params=params_for(1))
            assert not r1["rolled_back"] and r1["canary"] == "passed"
            assert sorted(r1["refreshed"]) == ["r0", "r1", "r2"]

            reps[0].lie_version = True  # poison the next rollout
            r2 = ctrl.rollout(version=2, params=params_for(2))
            assert r2["rolled_back"] and r2["canary"] == "diverged"
            assert "canary divergence" in r2["reason"]
            reps[0].lie_version = False
        finally:
            stop.set()
        for t in threads:
            t.join(timeout=30.0)
        assert not any(t.is_alive() for t in threads)

        # zero lost requests: every submit either streamed or... no,
        # EVERY submit streamed — the rollout path never sheds
        assert failures == []
        assert len(results) == sum(submitted) and sum(submitted) >= 36

        # every stream is single-version: bit-equal to a cold v0 or v1
        # stream (v2 was rolled back before a second replica saw it)
        versions = set()
        for plen, toks in results:
            v = next((v for v in (0, 1)
                      if toks == VersionedEngine.stream(plen, 4, v)), None)
            assert v is not None, (plen, toks)
            versions.add(v)
        assert 1 in versions  # traffic kept flowing after the refresh

        # the fleet converged on v1 — including the (un-poisoned) liar
        for rep in reps:
            assert rep.weight_version() == 1
        for eng in engines:
            assert eng.swaps == [1]  # v2 adopted NOWHERE

        counters = router.snapshot()["counters"]
        assert counters["refreshes"] == 1
        assert counters["refresh_rollbacks"] == 1
        assert counters["canary_divergences"] == 1
        assert counters["refresh_demotions"] == 0
