"""Fleet router: health-checked routing, failover, rolling restart.

Two layers of coverage:

- **Logic tests** against the deterministic :class:`FakeEngine` from
  ``test_admission.py`` (no device work): the health state machine on a
  fake clock, retry/failover decisions, hang detection, reject bursts,
  replay-divergence refusal, the shared fault-injection harness, and the
  ``DS_FLEET_*`` kill switches.
- **Real-engine tests** over the v2 ragged engine (CPU mesh): the
  acceptance contract — a replica crash mid-decode ends with every
  affected request either completed on a surviving replica with greedy
  outputs BIT-IDENTICAL to a no-fault run or failed typed within its
  deadline; no hung handles, no duplicate streamed tokens; rolling
  restart of one replica loses zero requests while the peer serves.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.serving import GatewayClosedError, ServingConfig
from deepspeed_tpu.serving.fleet import (DEGRADED, DOWN, HEALTHY, RESTARTING,
                                         FaultyReplica, FleetConfig, FleetRouter,
                                         GatewayReplica, NoReplicaAvailableError,
                                         ReplayDivergenceError, ReplicaDiedError,
                                         ReplicaHealth, get_fleet_config)
from unit.common.fault_injection import FaultInjector
from unit.inference.serving.test_admission import FakeEngine


# ======================================================================
# logic tests (FakeEngine — no device work)
# ======================================================================
def fake_replica(name, auto_start=True, engine=None, **serving_cfg):
    serving_cfg.setdefault("max_burst", 1)
    return GatewayReplica(name, lambda: engine or FakeEngine(),
                          serving_config=ServingConfig(**serving_cfg),
                          auto_start=auto_start)


def make_router(replicas, auto_heartbeat=False, **cfg):
    cfg.setdefault("retry_backoff_s", 0.005)
    cfg.setdefault("heartbeat_interval_s", 0.05)
    return FleetRouter(replicas, config=FleetConfig(**cfg),
                       auto_heartbeat=auto_heartbeat)


class TestReplicaHealth:

    def test_threshold_state_machine(self):
        clock = [0.0]
        h = ReplicaHealth(FleetConfig(degraded_after=2, down_after=4),
                          now_fn=lambda: clock[0], name="r")
        assert h.state == HEALTHY and h.routable
        h.record_failure("f1")
        assert h.state == HEALTHY  # one failure is noise
        h.record_failure("f2")
        assert h.state == DEGRADED and h.routable  # fallback-only
        h.record_success()
        assert h.state == HEALTHY  # success resets the streak
        for i in range(4):
            h.record_failure(f"f{i}")
        assert h.state == DOWN and not h.routable

    def test_fatal_failure_short_circuits_to_down(self):
        h = ReplicaHealth(FleetConfig(), now_fn=lambda: 0.0)
        h.record_failure("pump died", fatal=True)
        assert h.state == DOWN
        assert [(a, b) for _, a, b, _ in h.transitions] == [(HEALTHY, DOWN)]

    def test_half_open_probing_with_backoff(self):
        clock = [0.0]
        h = ReplicaHealth(
            FleetConfig(probe_backoff_s=0.25, probe_backoff_mult=2.0,
                        probe_backoff_max_s=1.0, recovery_probes=2),
            now_fn=lambda: clock[0])
        h.record_failure("dead", fatal=True)
        assert not h.probe_due()  # backoff window not open yet
        clock[0] = 0.3
        assert h.probe_due()
        assert not h.record_probe(False)  # failed probe doubles backoff
        assert not h.probe_due()
        clock[0] = 0.3 + 0.4
        assert not h.probe_due()  # 0.5s backoff now
        clock[0] = 0.3 + 0.6
        assert h.probe_due()
        assert not h.record_probe(True)   # 1/2 confirmations
        assert h.probe_due()              # next confirmation immediate
        assert h.record_probe(True)       # 2/2 -> recovered
        assert h.state == HEALTHY and h.routable
        assert not h.probe_due()

    def test_restart_overlay_ignores_drain_noise(self):
        h = ReplicaHealth(FleetConfig(down_after=2), now_fn=lambda: 0.0)
        h.begin_restart()
        assert h.state == RESTARTING and not h.routable
        for _ in range(5):
            h.record_failure("drain noise", fatal=True)
        assert h.state == RESTARTING  # intentional restart, not a crash
        h.end_restart(ok=True)
        assert h.state == HEALTHY
        h.begin_restart()
        h.end_restart(ok=False)
        assert h.state == DOWN  # failed readiness probe -> half-open path

    def test_fleet_config_validates(self):
        with pytest.raises(ValueError, match="degraded_after"):
            FleetConfig(degraded_after=5, down_after=3)
        with pytest.raises(ValueError, match="probe_backoff"):
            FleetConfig(probe_backoff_s=60.0, probe_backoff_max_s=1.0)
        assert get_fleet_config({"fleet": {"max_attempts": 2}}).max_attempts == 2
        assert get_fleet_config({}).prefix_routing is True


class TestRouterLogic:

    def test_reject_burst_retries_elsewhere_without_health_penalty(self):
        r0 = FaultyReplica(fake_replica("r0"), reject_next=3)
        r1 = fake_replica("r1")
        router = make_router([r0, r1])
        h = router.submit([10, 11, 12], max_new_tokens=3)
        got = h.result(timeout=10)
        assert got == FakeEngine.expected_tokens(0, 3, 3)
        assert h.replica_trail[0] == "r0" and h.replica_trail[-1] == "r1"
        # a full queue is load, not sickness: no health transition
        assert router.health["r0"].state == HEALTHY
        assert router.snapshot()["counters"]["retries"] >= 1
        router.shutdown()

    def test_hang_detection_fails_over_without_duplicates(self):
        r0 = FaultyReplica(fake_replica("r0"), hang_at_token=1)
        r1 = fake_replica("r1")
        router = make_router([r0, r1], stream_token_timeout_s=0.15)
        h = router.submit([5, 6, 7, 8], max_new_tokens=4)
        got = h.result(timeout=30)
        # token 0 streamed from r0 before the hang; replay on r1 must
        # produce the rest with no duplicate and no gap
        assert got == FakeEngine.expected_tokens(0, 4, 4)
        assert h.replica_trail == ["r0", "r1"]
        snap = router.snapshot()["counters"]
        assert snap["failovers"] >= 1 and snap["completed"] == 1
        router.shutdown()

    def test_crash_with_no_survivor_fails_typed_within_deadline(self):
        r0 = FaultyReplica(fake_replica("r0"), crash_at_token=0)
        router = make_router([r0])
        t0 = time.monotonic()
        h = router.submit([1, 2, 3], max_new_tokens=4, deadline_ms=5000)
        with pytest.raises(NoReplicaAvailableError):
            h.result(timeout=10)
        assert time.monotonic() - t0 < 5.0  # well inside the deadline
        assert h.status == "failed" and h.error.reason == "no_replica"
        assert h._collected == []  # nothing was ever streamed
        assert router.health["r0"].state == DOWN
        router.shutdown()

    def test_replay_divergence_refuses_to_fork_the_stream(self):
        r0 = FaultyReplica(fake_replica("r0"), crash_at_token=2)
        r1 = fake_replica("r1")
        # burn r1's uid 0 so its stream for the fleet request differs
        # from r0's (FakeEngine tokens depend on uid) — a stand-in for
        # non-deterministic sampling, which failover must refuse to splice
        r1.gateway.submit([9, 9], max_new_tokens=1).result(timeout=10)
        router = make_router([r0, r1])
        h = router.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(ReplayDivergenceError):
            h.result(timeout=10)
        assert h.error.reason == "replay_divergence"
        # the client saw exactly r0's pre-crash prefix, nothing forked
        assert h._collected == FakeEngine.expected_tokens(0, 3, 2)
        router.shutdown()

    def test_failover_kill_switch(self, monkeypatch):
        monkeypatch.setenv("DS_FLEET_FAILOVER", "0")
        r0 = FaultyReplica(fake_replica("r0"), crash_at_token=0)
        r1 = fake_replica("r1")
        router = make_router([r0, r1])
        h = router.submit([1, 2, 3], max_new_tokens=2)
        with pytest.raises(ReplicaDiedError):
            h.result(timeout=10)
        assert h.attempts == 1 and h.replica_trail == ["r0"]
        router.shutdown()

    def test_shared_fault_injector_drives_replica_death(self):
        # satellite: the checkpoint FaultInjector harness, promoted to
        # tests/unit/common, scripts serving faults through hook=
        inj = FaultInjector(kill_at="token", kill_detail=1)
        r0 = FaultyReplica(fake_replica("r0"), hook=inj)
        r1 = fake_replica("r1")
        router = make_router([r0, r1])
        h = router.submit([4, 5, 6], max_new_tokens=3)
        assert h.result(timeout=10) == FakeEngine.expected_tokens(0, 3, 3)
        assert inj.killed and ("token", 0) in inj.trace
        assert ("submit", 1) in inj.trace
        assert router.health["r0"].state == DOWN
        assert h.replica_trail == ["r0", "r1"]
        router.shutdown()

    def test_heartbeat_marks_down_and_half_open_recovers(self):
        clock = [0.0]
        r0 = fake_replica("r0")
        r1 = fake_replica("r1")
        router = FleetRouter(
            [r0, r1],
            config=FleetConfig(probe_backoff_s=0.25, recovery_probes=2),
            now_fn=lambda: clock[0], auto_heartbeat=False)
        r0.kill()
        router.tick()
        assert router.health["r0"].state == DOWN
        assert router.health["r1"].state == HEALTHY
        # traffic keeps flowing around the corpse
        h = router.submit([7, 8], max_new_tokens=2)
        assert h.result(timeout=10) == FakeEngine.expected_tokens(0, 2, 2)
        assert h.replica_trail == ["r1"]
        # replica comes back (ops rebuilt it); half-open probes readmit
        r0.restart(timeout=5)
        router.tick()  # probe window still closed
        assert router.health["r0"].state == DOWN
        clock[0] = 0.3
        router.tick()  # probe 1/2
        assert router.health["r0"].state == DOWN
        router.tick()  # probe 2/2 -> HEALTHY
        assert router.health["r0"].state == HEALTHY
        assert router.snapshot()["counters"]["recoveries"] == 1
        router.shutdown()

    def test_prefix_aware_placement_prefers_longest_match(self, monkeypatch):
        warm = FakeEngine()
        warm.prefix_match_len = lambda toks: 8  # pretends to cache a block
        r0 = fake_replica("r0")
        r1 = fake_replica("r1", engine=warm)
        router = make_router([r0, r1])
        h = router.submit(list(range(12)), max_new_tokens=2)
        h.result(timeout=10)
        assert h.replica_trail == ["r1"]  # matched despite equal load
        assert router.snapshot()["counters"]["prefix_routed"] == 1
        router.shutdown()
        # kill switch: same fleet shape, least-loaded wins (tie -> r0)
        monkeypatch.setenv("DS_FLEET_PREFIX_ROUTING", "0")
        warm2 = FakeEngine()
        warm2.prefix_match_len = lambda toks: 8
        router = make_router([fake_replica("r0"),
                              fake_replica("r1", engine=warm2)])
        h = router.submit(list(range(12)), max_new_tokens=2)
        h.result(timeout=10)
        assert h.replica_trail == ["r0"]
        assert router.snapshot()["counters"]["prefix_routed"] == 0
        router.shutdown()

    def test_cancel_mid_stream_terminates_typed(self):
        r0 = FaultyReplica(fake_replica("r0"), slow_token_s=0.02)
        router = make_router([r0])
        h = router.submit([1, 2, 3], max_new_tokens=32)
        while not h._collected and not h.done:
            time.sleep(0.005)
        h.cancel()
        with pytest.raises(Exception) as ei:
            h.result(timeout=10)
        assert getattr(ei.value, "reason", "") == "cancelled"
        assert h.status == "cancelled"
        assert router.snapshot()["counters"]["cancelled"] == 1
        router.shutdown()

    def test_router_drain_closes_admission(self):
        router = make_router([fake_replica("r0")])
        h = router.submit([1, 2], max_new_tokens=2)
        h.result(timeout=10)
        router.drain(timeout=30)
        with pytest.raises(GatewayClosedError):
            router.submit([3, 4])

    def test_background_heartbeat_thread_detects_death(self):
        r0 = fake_replica("r0")
        r1 = fake_replica("r1")
        router = make_router([r0, r1], auto_heartbeat=True,
                             heartbeat_interval_s=0.02)
        r0.kill()
        deadline = time.monotonic() + 5
        while (router.health["r0"].state != DOWN
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert router.health["r0"].state == DOWN
        router.shutdown()


# ======================================================================
# real-engine acceptance tests (v2 ragged engine, CPU mesh)
# ======================================================================
@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine_factory(model_and_params, prefix_cache=False):
    model, params = model_and_params

    def factory():
        cfg = RaggedInferenceEngineConfig(
            kv_block_size=8,
            num_kv_blocks=0,
            prefix_cache=PrefixCacheConfig(enabled=prefix_cache),
            state_manager=DSStateManagerConfig(max_ragged_batch_size=96,
                                               max_ragged_sequence_count=16,
                                               max_tracked_sequences=16,
                                               max_context=32))
        return InferenceEngineV2(model=model, config=cfg, params=params,
                                 dtype=jnp.float32)

    return factory


@pytest.fixture(scope="module")
def reference(model_and_params):
    """Prompts + the no-fault greedy streams from a direct scheduler run
    — the bit-identical yardstick for every fleet scenario below."""
    rng = np.random.RandomState(0)
    n = 10
    prompts = [rng.randint(0, 250, size=5 + i % 6).astype(np.int32)
               for i in range(n)]
    max_new = [2 + i % 3 for i in range(n)]
    engine = make_engine_factory(model_and_params)()
    direct = DynamicSplitFuseScheduler(engine, token_budget=48, max_burst=4)
    for i in range(n):
        direct.add_request(i, prompts[i], max_new_tokens=max_new[i])
    want = direct.run_to_completion()
    engine.destroy()
    return prompts, max_new, {i: want[i] for i in range(n)}


def real_fleet(model_and_params, names=("r0", "r1"), **fleet_cfg):
    factory = make_engine_factory(model_and_params)
    scfg = ServingConfig(token_budget=48, max_burst=4)
    reps = [GatewayReplica(name, factory, serving_config=scfg)
            for name in names]
    fleet_cfg.setdefault("retry_backoff_s", 0.01)
    return reps, FleetRouter(reps, config=FleetConfig(**fleet_cfg),
                             auto_heartbeat=False)


def _consume_all(handles):
    """Stream every handle from its own client thread (the real usage
    shape); → {i: tokens}, asserting no client ever hangs."""
    streams, errors = {}, {}

    def client(i, h):
        try:
            streams[i] = list(h.tokens(timeout=120))
        except Exception as e:
            errors[i] = e

    threads = [threading.Thread(target=client, args=(i, h))
               for i, h in enumerate(handles)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not any(t.is_alive() for t in threads), "hung client stream"
    return streams, errors


def test_fleet_parity_with_direct_run(model_and_params, reference):
    """N=2 healthy fleet == direct scheduler run, bit for bit; and the
    single-replica (N=1) case survives the Replica extraction."""
    prompts, max_new, want = reference
    reps, router = real_fleet(model_and_params)
    handles = [router.submit(prompts[i], max_new_tokens=max_new[i])
               for i in range(len(prompts))]
    streams, errors = _consume_all(handles)
    assert not errors
    for i in range(len(prompts)):
        assert streams[i] == want[i], f"request {i} diverged"
    counters = router.snapshot()["counters"]
    assert counters["completed"] == len(prompts)
    assert counters["failed"] == 0 and counters["retries"] == 0
    router.drain(timeout=60)
    with pytest.raises(GatewayClosedError):
        router.submit(prompts[0])


def test_replica_crash_mid_decode_failover_bit_identical(model_and_params,
                                                         reference):
    """THE acceptance test: kill a replica after it has streamed k
    tokens; every affected request completes on the survivor with
    greedy outputs bit-identical to the no-fault run — no duplicates,
    no gaps, no hung handles — and the dead replica goes DOWN."""
    prompts, max_new, want = reference
    factory = make_engine_factory(model_and_params)
    scfg = ServingConfig(token_budget=48, max_burst=4)
    faulty = FaultyReplica(GatewayReplica("r0", factory, serving_config=scfg),
                           crash_at_token=1)
    peer = GatewayReplica("r1", factory, serving_config=scfg)
    router = FleetRouter([faulty, peer],
                         config=FleetConfig(retry_backoff_s=0.01),
                         auto_heartbeat=False)
    handles = [router.submit(prompts[i], max_new_tokens=max_new[i])
               for i in range(len(prompts))]
    streams, errors = _consume_all(handles)
    assert not errors, {i: str(e) for i, e in errors.items()}
    for i in range(len(prompts)):
        assert streams[i] == want[i], f"request {i} not bit-identical"
    assert router.health["r0"].state == DOWN
    counters = router.snapshot()["counters"]
    assert counters["completed"] == len(prompts)
    assert counters["failovers"] >= 1 and counters["failed"] == 0
    router.shutdown()


def test_rolling_restart_loses_zero_requests(model_and_params, reference):
    """Restart r0 while traffic flows: queued work is shed to the peer
    through the retry path, active streams drain, and every request
    still produces the reference stream."""
    prompts, max_new, want = reference
    reps, router = real_fleet(model_and_params,
                              restart_drain_timeout_s=60)
    handles = {}

    def traffic():
        for i in range(len(prompts)):
            handles[i] = router.submit(prompts[i], max_new_tokens=max_new[i])
            time.sleep(0.01)

    feeder = threading.Thread(target=traffic)
    feeder.start()
    time.sleep(0.03)  # a few requests in flight on both replicas
    assert router.restart_replica("r0", timeout=60)
    feeder.join(timeout=60)
    streams, errors = _consume_all([handles[i] for i in sorted(handles)])
    assert not errors, {i: str(e) for i, e in errors.items()}
    for i in range(len(prompts)):
        assert streams[i] == want[i], f"request {i} lost or diverged"
    assert router.health["r0"].state == HEALTHY  # back in rotation
    assert reps[0].restarts == 1
    counters = router.snapshot()["counters"]
    assert counters["completed"] == len(prompts)
    assert counters["restarts"] == 1 and counters["failed"] == 0
    router.drain(timeout=60)


def test_prefix_aware_placement_routes_to_warm_replica(model_and_params):
    """With prefix caching on, the router sends a prompt to the replica
    whose radix trie already holds its prefix."""
    factory = make_engine_factory(model_and_params, prefix_cache=True)
    scfg = ServingConfig(token_budget=48, max_burst=4)
    r0 = GatewayReplica("r0", factory, serving_config=scfg)
    r1 = GatewayReplica("r1", factory, serving_config=scfg)
    router = FleetRouter([r0, r1], config=FleetConfig(),
                         auto_heartbeat=False)
    prompt = np.arange(1, 18, dtype=np.int32)  # 17 tokens = 2 full blocks
    # warm r1 directly (bypassing the router, as a peer fleet would)
    r1.gateway.submit(prompt, max_new_tokens=2).result(timeout=60)
    assert r1.prefix_match_len(prompt) >= 8 > r0.prefix_match_len(prompt)
    h = router.submit(prompt, max_new_tokens=2)
    h.result(timeout=60)
    assert h.replica_trail == ["r1"]
    assert router.snapshot()["counters"]["prefix_routed"] == 1
    router.drain(timeout=60)


def test_sampled_stream_kill_midgeneration_replays_bit_identical(
        model_and_params, monkeypatch):
    """Chaos acceptance for structured generation: sampled and
    schema-constrained requests stream through a fleet whose first
    replica is killed after one token; every stream completes on the
    survivor BIT-IDENTICAL to the no-fault run. The router derives each
    request's sampling seed from the router uid, so the failover replay
    re-draws the identical counter-keyed stream — the replay verifier
    (which refuses to fork a client-visible stream) passes for sampled
    traffic exactly as it does for greedy.

    Runs under DS_SANITIZE=1: the relay threads, gateway pumps, schema
    compiler cache, and structured store locks are all order-tracked, so
    this doubles as a dynamic deadlock harness for the new subsystem."""
    import json

    from deepspeed_tpu.inference.structured.grammar import (byte_vocab,
                                                            detokenize)
    from deepspeed_tpu.inference.v2 import StructuredConfig
    from deepspeed_tpu.utils.sanitize import reset_lock_graph
    monkeypatch.setenv("DS_SANITIZE", "1")
    reset_lock_graph()
    model, params = model_and_params
    EOS = 2
    SCHEMA = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "mode": {"enum": ["fast", "safe"]}},
              "required": ["ok", "mode"]}

    def factory():
        cfg = RaggedInferenceEngineConfig(
            kv_block_size=8,
            num_kv_blocks=0,
            structured=StructuredConfig(enabled=True),
            state_manager=DSStateManagerConfig(max_ragged_batch_size=96,
                                               max_ragged_sequence_count=16,
                                               max_tracked_sequences=16,
                                               max_context=64))
        return InferenceEngineV2(model=model, config=cfg, params=params,
                                 dtype=jnp.float32)

    probe = factory()
    vocab = byte_vocab(probe.structured.vocab_size)
    probe.destroy()
    scfg = ServingConfig(token_budget=48, max_burst=4, eos_token_id=EOS,
                         token_strings=vocab)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(3, 250, size=5 + i % 4).astype(np.int32)
               for i in range(6)]

    def drive(router):
        handles = []
        for i, p in enumerate(prompts):
            kw = {"sample": {"temperature": 1.2, "top_k": 24}}
            if i % 3 == 2:
                kw["schema"] = SCHEMA
                kw["max_new_tokens"] = 48
            else:
                kw["max_new_tokens"] = 4 + i % 3
            handles.append(router.submit(p, **kw))
        return _consume_all(handles)

    # no-fault reference: a single-replica fleet (same router uid
    # sequence -> same derived seeds as the chaos run below)
    ref_router = FleetRouter(
        [GatewayReplica("ref", factory, serving_config=scfg)],
        config=FleetConfig(retry_backoff_s=0.01), auto_heartbeat=False)
    want, errors = drive(ref_router)
    assert not errors, {i: str(e) for i, e in errors.items()}
    ref_router.shutdown()

    # chaos run: r0 dies after streaming one token; r1 survives
    faulty = FaultyReplica(GatewayReplica("r0", factory, serving_config=scfg),
                           crash_at_token=1)
    peer = GatewayReplica("r1", factory, serving_config=scfg)
    router = FleetRouter([faulty, peer],
                         config=FleetConfig(retry_backoff_s=0.01,
                                            # r0 crashes, nothing hangs: room for the two cold
                                            # compiles (checkified under DS_SANITIZE) a token
                                            # now waits for - its own program's and the next
                                            # one's, whose dispatch hands it over
                                            stream_token_timeout_s=20.0),
                         auto_heartbeat=False)
    streams, errors = drive(router)
    assert not errors, {i: str(e) for i, e in errors.items()}
    for i in range(len(prompts)):
        assert streams[i] == want[i], f"request {i} not bit-identical"
    # the constrained lanes stayed 100% schema-valid through the kill
    for i in range(2, len(prompts), 3):
        toks = streams[i]
        assert toks[-1] == EOS
        doc = json.loads(detokenize(toks[:-1], vocab))
        assert isinstance(doc["ok"], bool) and doc["mode"] in ("fast", "safe")
    assert router.health["r0"].state == DOWN
    counters = router.snapshot()["counters"]
    assert counters["completed"] == len(prompts)
    assert counters["failovers"] >= 1 and counters["failed"] == 0
    router.shutdown()
