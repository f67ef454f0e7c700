"""Serving gateway end-to-end over the REAL v2 ragged engine (CPU mesh).

The acceptance contract: >=16 overlapping streaming requests with mixed
priorities submitted from concurrent client threads produce token
streams IDENTICAL to a direct ``DynamicSplitFuseScheduler``
``run_to_completion`` on the same engine (on-device greedy sampling is
deterministic and batch-composition independent), over-capacity
requests are rejected with typed errors, cancellation mid-decode and
priority preemption (KV suspend/resume) free what they should, and
``drain()`` leaves the engine destroyed with zero leaked KV blocks.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.serving import (GatewayClosedError, RequestCancelledError,
                                   RequestTooLargeError, ServingConfig, ServingGateway)


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(model_and_params, num_kv_blocks=0, max_context=32, n_seqs=16):
    model, params = model_and_params
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=8,
        num_kv_blocks=num_kv_blocks,
        state_manager=DSStateManagerConfig(max_ragged_batch_size=96,
                                           max_ragged_sequence_count=n_seqs,
                                           max_tracked_sequences=n_seqs,
                                           max_context=max_context))
    return InferenceEngineV2(model=model, config=cfg, params=params,
                             dtype=jnp.float32)


class _RecordingMonitor:
    """Anything with Monitor.write_events(event_list) works."""

    def __init__(self):
        self.events = []

    def write_events(self, event_list):
        self.events.extend(event_list)


def test_concurrent_streams_match_direct_run(model_and_params):
    engine = make_engine(model_and_params)
    rng = np.random.RandomState(0)
    n = 16
    prompts = [rng.randint(0, 250, size=5 + i % 6).astype(np.int32)
               for i in range(n)]
    max_new = [2 + i % 3 for i in range(n)]

    # reference: the plain scheduler driving the same engine to completion
    direct = DynamicSplitFuseScheduler(engine, token_budget=48, max_burst=4)
    for i in range(n):
        direct.add_request(1000 + i, prompts[i], max_new_tokens=max_new[i])
    want = direct.run_to_completion()
    free0 = int(engine.free_blocks)  # engine fully idle again

    monitor = _RecordingMonitor()
    gw = ServingGateway(engine, config=ServingConfig(
        token_budget=48, max_burst=4, metrics_interval_steps=1),
        monitor=monitor)
    streams = {}

    def client(i):
        handle = gw.submit(prompts[i], max_new_tokens=max_new[i],
                           priority=i % 3)
        streams[i] = list(handle.tokens(timeout=120))  # incremental stream

    threads = [threading.Thread(target=client, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)

    for i in range(n):
        assert streams[i] == want[1000 + i], f"request {i} diverged"
    assert int(engine.free_blocks) == free0  # zero leaked KV blocks

    snap = gw.snapshot()
    c = snap["counters"]
    assert c["submitted"] == c["admitted"] == c["completed"] == n
    assert c["tokens_generated"] == sum(max_new)
    assert c["engine_steps"] > 0 and c["failed"] == 0
    assert snap["ttft"]["count"] == n and snap["ttft"]["p50_ms"] > 0
    assert snap["token_latency"]["count"] > 0
    assert snap["token_latency"]["p50_ms"] > 0
    assert snap["queue_wait"]["count"] == n
    assert snap["gauges"]["queue_depth_peak"] >= 1

    # SLO metrics route through the monitor's write_events interface
    gw.metrics.write_events(monitor)
    tags = {t: v for t, v, _ in monitor.events}
    assert tags["serving/ttft/p50_ms"] > 0
    assert tags["serving/count/completed"] == n
    assert tags["serving/gauge/queue_depth_peak"] >= 1

    gw.drain(timeout=60)
    assert gw.state == "stopped" and engine.kv_cache is None  # destroyed
    with pytest.raises(GatewayClosedError):
        gw.submit(prompts[0])


def test_over_capacity_rejected_with_typed_error(model_and_params):
    engine = make_engine(model_and_params, num_kv_blocks=4, max_context=32)
    gw = ServingGateway(engine, config=ServingConfig(max_burst=1),
                        auto_start=False)
    # 3 usable blocks (null pinned): 32 tokens = 4 blocks can never fit
    with pytest.raises(RequestTooLargeError, match="KV blocks"):
        gw.submit(list(range(24)), max_new_tokens=8)
    with pytest.raises(RequestTooLargeError, match="context window"):
        gw.submit(list(range(30)), max_new_tokens=8)
    assert gw.snapshot()["counters"]["rejected_too_large"] == 2
    gw.drain(timeout=10)


def test_cancel_mid_decode_frees_blocks(model_and_params):
    engine = make_engine(model_and_params)
    free0 = int(engine.free_blocks)
    gw = ServingGateway(engine, config=ServingConfig(max_burst=1),
                        auto_start=False)
    h = gw.submit(np.arange(8, dtype=np.int32), max_new_tokens=16)
    for _ in range(4):
        gw._pump_once()
    assert 1 <= len(h._collected) < 16
    h.cancel()
    gw._pump_once()
    assert h.status == "cancelled"
    with pytest.raises(RequestCancelledError):
        h.result(timeout=5)
    assert int(engine.free_blocks) == free0  # cancelled KV released
    # nothing held once every request has ended: no place, no prompt awaited, no worst case
    assert gw.gate.active == 0 and gw.gate.committed_blocks == 0 == gw.gate.committed_worst
    # the gateway keeps serving after a cancellation
    h2 = gw.submit(np.arange(6, dtype=np.int32), max_new_tokens=2)
    for _ in range(8):
        if h2.done:
            break
        gw._pump_once()
    assert h2.result(timeout=5) is not None and h2.status == "completed"
    gw.drain(timeout=30)
    assert engine.kv_cache is None


def test_priority_preemption_suspends_then_resumes(model_and_params):
    # pool of 3 usable blocks: A (2 blocks) and B (2 blocks) cannot
    # coexist, so admitting high-priority B must suspend A's KV to host
    engine = make_engine(model_and_params, num_kv_blocks=4, max_context=16,
                         n_seqs=4)
    prompt_a = np.arange(8, dtype=np.int32)
    prompt_b = (np.arange(8, dtype=np.int32) + 40)

    # uninterrupted references on the same engine — one at a time (the
    # tiny pool is the point; together they would exhaust it, which is
    # exactly what the gateway's preemption prevents)
    want = {}
    for uid, prompt, mn in ((998, prompt_a, 8), (999, prompt_b, 4)):
        direct = DynamicSplitFuseScheduler(engine, max_burst=1)
        direct.add_request(uid, prompt, max_new_tokens=mn)
        want.update(direct.run_to_completion())

    gw = ServingGateway(engine, config=ServingConfig(max_burst=1),
                        auto_start=False)
    h_a = gw.submit(prompt_a, max_new_tokens=8, priority=0)
    gw._pump_once()  # admit + prefill A
    gw._pump_once()  # decode A
    assert len(h_a._collected) >= 1
    h_b = gw.submit(prompt_b, max_new_tokens=4, priority=5)
    gw._pump_once()  # B preempts A: A's KV suspends to host
    assert engine.is_suspended(h_a.uid)
    assert gw.snapshot()["counters"]["preemptions"] == 1
    a_tokens_at_preempt = len(h_a._collected)
    for _ in range(12):
        if h_b.done:
            break
        gw._pump_once()
    assert h_b.result(timeout=5) == want[999]
    assert len(h_a._collected) == a_tokens_at_preempt  # truly paused
    for _ in range(16):
        if h_a.done:
            break
        gw._pump_once()
    assert not engine.is_suspended(h_a.uid)
    assert h_a.result(timeout=5) == want[998]  # suspend/resume is exact
    snap = gw.snapshot()
    assert snap["counters"]["resumes"] == 1
    assert snap["counters"]["completed"] == 2
    gw.drain(timeout=30)


def test_drain_finishes_queued_and_inflight(model_and_params):
    engine = make_engine(model_and_params)
    free0 = int(engine.free_blocks)
    with ServingGateway(engine, config=ServingConfig(max_burst=1)) as gw:
        handles = [gw.submit(np.arange(4 + i, dtype=np.int32),
                             max_new_tokens=3) for i in range(6)]
    # context exit == drain(): everything accepted must have finished
    assert all(h.status == "completed" for h in handles)
    assert all(len(h.result(timeout=1)) == 3 for h in handles)
    assert gw.state == "stopped" and engine.kv_cache is None
    # nothing held once every request has ended: no place, no prompt awaited, no worst case
    assert gw.gate.active == 0 and gw.gate.committed_blocks == 0 == gw.gate.committed_worst
    snap = gw.snapshot()
    assert snap["counters"]["completed"] == 6
    assert snap["gauges"]["kv_free_blocks"] == free0  # last observed: idle
