"""The gate admits on the blocks a request holds, and the scheduler makes
that safe: a step is fitted to the blocks that are free, and when no row can
run one request is preempted by recompute.

``FakeEngine`` keeps its pool like the engine (a ``put`` that asks for more
blocks than are free raises the engine's ``KV pool exhausted``), so a pump
that lives to the end of a test never asked for a block that was not there.
The real engine (debug llama, ``kv``) runs the same two scenes; the other
state kinds run the dry pool as cases of ``v2/kind_conformance.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.serving import ServingConfig, ServingGateway
from unit.inference.serving.test_admission import FakeEngine, make_gateway, pump_until

BLOCK = 8


def holds_nothing(gw, engine, blocks):
    """After every request ended: no place, no block, no prompt awaited."""
    gate = gw.gate
    assert gate.active == 0 and gate.committed_blocks == 0 and gate.committed_worst == 0
    assert gate.committed_window_blocks == 0 and gw._active == {} and gw._paused == []
    assert len(gw.queue) == 0 and gw.scheduler.requests == {}
    assert engine.free_blocks == blocks and gate.headroom() == blocks


def counters(gw):
    snap = gw.snapshot()["counters"]
    return snap["rows_held_back"], snap["preempted_for_room"], snap["recomputed_tokens"]


# ------------------------------------------------------------------ the fake
def dry_pair(**cfg):
    """Two requests whose worst cases (4 + 4 blocks) pass the pool's 5, let
    in on their prompts' 1 + 1 beside a reserve of 2."""
    engine = FakeEngine(block_size=BLOCK, free_blocks=5, max_ctx_tokens=64)
    gw = make_gateway(engine, token_budget=8, **cfg)
    a = gw.submit(list(range(7)), max_new_tokens=20)
    b = gw.submit(list(range(5)), max_new_tokens=20)
    return engine, gw, a, b


def test_a_dry_pool_holds_rows_back_then_preempts_one_request_by_recompute():
    engine, gw, a, b = dry_pair()
    gw._pump_once()
    assert gw.gate.active == 2 and len(gw.queue) == 0       # both on their prompts' blocks
    assert gw.gate.refused_by["kv_blocks"] == 0
    pump_until(gw, lambda: counters(gw)[1] == 1)
    held_back, preempted, recomputed = counters(gw)
    # b, a step behind, met the full pool first and waited while a ran on to its own
    # block's end; then neither could run, and b - fewer tokens in the cache - went
    assert held_back >= 2 and preempted == 1
    assert b.status == "queued" and len(gw.queue) == 1 and not b.done
    assert recomputed == len(b.prompt) + len(b._collected) - 1 == 16
    assert engine.query(b.uid) is None and gw.gate.active == 1
    sent = list(b._collected)
    pump_until(gw, lambda: a.done and b.done)
    assert a.result(timeout=1) == FakeEngine.expected_tokens(a.uid, 7, 20)
    # its stream kept what it was sent and went on from there
    assert b.result(timeout=1) == FakeEngine.expected_tokens(b.uid, 5, 20)
    assert b.result(timeout=1)[:len(sent)] == sent and list(b.tokens(timeout=1)) == b.result()
    snap = gw.snapshot()["counters"]
    assert snap["completed"] == 2 and snap["failed"] == 0 and snap["admitted"] == 3
    assert snap["tokens_generated"] == 40                   # a recomputed token is not served
    assert counters(gw) == (held_back, 1, 16) and gw.state == "running"
    holds_nothing(gw, engine, 5)


def test_a_request_preempted_for_room_comes_back_before_the_queue():
    """Arrival order is kept at the gate - no skip-ahead - and the head of
    the line is the request that was let in before: it waits for room with a
    later, smaller request behind it."""
    engine, gw, a, b = dry_pair(max_queue_depth=8)
    gw._pump_once()
    c = gw.submit(list(range(20)), max_new_tokens=4)        # 3 blocks: no room beside a and b
    d = gw.submit([1], max_new_tokens=2)                    # 1 block: would fit, and waits
    pump_until(gw, lambda: counters(gw)[1] == 1)
    assert [h.uid for h in gw.queue.candidates()] == [b.uid, c.uid, d.uid]
    assert d.admitted_ns is None and c.admitted_ns is None
    pump_until(gw, lambda: all(h.done for h in (a, b, c, d)))
    assert a.admitted_ns < b.admitted_ns < c.admitted_ns < d.admitted_ns    # b's second time
    for h, (plen, new) in zip((a, b, c, d), ((7, 20), (5, 20), (20, 4), (1, 2))):
        assert h.result(timeout=1) == FakeEngine.expected_tokens(h.uid, plen, new)
    holds_nothing(gw, engine, 5)


def test_the_victim_is_never_of_a_higher_priority_than_the_rest():
    engine, gw, a, b = dry_pair()
    b.priority = 3                                          # b has fewer tokens, a the lower priority
    pump_until(gw, lambda: counters(gw)[1] == 1)
    assert a.status == "queued" and b.status == "running"
    pump_until(gw, lambda: a.done and b.done)
    assert a.result(timeout=1) == FakeEngine.expected_tokens(a.uid, 7, 20)
    assert b.result(timeout=1) == FakeEngine.expected_tokens(b.uid, 5, 20)
    holds_nothing(gw, engine, 5)


def test_a_prompt_chunk_is_cut_to_the_blocks_there_are():
    """A prompt let in beside a request that goes on growing (the reserve
    taken away, so that it must): its chunks take the blocks that are free,
    none in a step that has none, the rest when the other has ended."""
    engine = FakeEngine(block_size=BLOCK, free_blocks=6, max_ctx_tokens=64)
    gw = make_gateway(engine, token_budget=8)
    gw.gate.reserve = lambda live: 0
    a = gw.submit(list(range(7)), max_new_tokens=14)        # 2 blocks now, 3 at its end
    pump_until(gw, lambda: engine.query(a.uid) == (15, 1))
    b = gw.submit(list(range(30)), max_new_tokens=2)        # 4 blocks: all there are
    steps = []
    put = engine.put
    engine.put = lambda uids, chunks, sample=None: (
        steps.append({u: len(c) for u, c in zip(uids, chunks)}), put(uids, chunks, sample))[1]
    pump_until(gw, lambda: a.done and b.done)
    # a's row took the block b's last chunk wanted: three blocks' worth, a step with no
    # row of b at all, and the rest once a had ended; then b's one decode row
    assert [s.get(b.uid, 0) for s in steps] == [7, 7, 7, 3, 0, 6, 1]
    assert a.result(timeout=1) == FakeEngine.expected_tokens(a.uid, 7, 14)
    assert b.result(timeout=1) == FakeEngine.expected_tokens(b.uid, 30, 2)
    assert counters(gw) == (0, 0, 0)
    holds_nothing(gw, engine, 6)


def test_one_request_alone_is_never_given_up():
    """Nobody to preempt: a stall is an error, as it was."""
    engine = FakeEngine(block_size=BLOCK, free_blocks=4, max_ctx_tokens=64)
    gw = make_gateway(engine, token_budget=8)
    a = gw.submit(list(range(7)), max_new_tokens=20)
    pump_until(gw, lambda: len(a._collected) == 3)
    engine.total_blocks = 2                                 # the pool shrinks to what it holds
    with pytest.raises(RuntimeError, match="scheduler stalled with 1 active"):
        for _ in range(20):
            gw._pump_once()
    assert counters(gw)[1] == 0


# ------------------------------------------------------------ the real engine
EOS = 152


@pytest.fixture(scope="module")
def llama():
    model = build_llama("debug")
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(llama, blocks=0, sequences=8):
    model, params = llama
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=BLOCK, num_kv_blocks=blocks,
        state_manager=DSStateManagerConfig(max_ragged_batch_size=32,
                                           max_ragged_sequence_count=sequences,
                                           max_tracked_sequences=sequences, max_context=64))
    return InferenceEngineV2(model=model, config=cfg, params=params, dtype=jnp.float32)


def alone(engine, prompts, new, eos=None):
    """Each prompt's greedy stream with the engine to itself."""
    streams = []
    for i, prompt in enumerate(prompts):
        direct = DynamicSplitFuseScheduler(engine, max_burst=1, eos_token_id=eos)
        direct.add_request(900 + i, prompt, max_new_tokens=new)
        streams.append(direct.run_to_completion()[900 + i])
    return streams


def test_worst_cases_of_one_and_a_half_pools_run_at_once_where_their_real_lengths_fit(llama):
    """Eight requests that ask for 40 tokens and end at an EOS long before:
    the gate lets all of them in at once, nobody waits, no row is held back,
    and every stream is the one the request gives alone."""
    new, roomy = 40, make_engine(llama)
    rng = np.random.default_rng(0)
    candidates = [rng.integers(0, 250, 6 + i % 8) for i in range(24)]
    streams = alone(roomy, candidates, new, eos=EOS)
    short = sorted(range(24), key=lambda i: len(streams[i]))[:8]
    prompts, want = [candidates[i] for i in short], [streams[i] for i in short]
    assert all(s[-1] == EOS for s in want)
    worst = sum(-(-(len(p) + new) // BLOCK) for p in prompts)
    real = sum(-(-(len(p) + len(s)) // BLOCK) for p, s in zip(prompts, want))
    blocks = -(-worst * 2 // 3)                             # the worst cases are 1.5 pools
    engine = make_engine(llama, blocks=blocks + 1)          # + the null block
    gw = ServingGateway(engine, auto_start=False, config=ServingConfig(
        max_burst=1, token_budget=32, eos_token_id=EOS))
    assert gw.gate.usable_blocks == blocks and real + gw.gate.reserve(8) <= blocks < worst
    handles = [gw.submit(p, max_new_tokens=new) for p in prompts]
    gw._pump_once()
    assert gw.gate.active == 8 and len(gw.queue) == 0 and gw.gate.refused_by["kv_blocks"] == 0
    pump_until(gw, lambda: all(h.done for h in handles), n=400)
    assert [h.result(timeout=1) for h in handles] == want
    assert counters(gw) == (0, 0, 0) and gw.snapshot()["counters"]["admitted"] == 8
    holds_nothing(gw, engine, blocks)
    gw.drain(timeout=30)


@pytest.mark.parametrize("burst", [1, 4])
def test_the_real_engine_run_dry_gives_the_streams_of_an_uncontended_run(llama, burst):
    """Three requests let in on their prompts' blocks, each a token short of
    a block's end, into a pool with nothing to spare (the gate's reserve
    taken away: the scheduler's half must hold alone). Rows wait, then one
    request is made again from its tokens; a sampled one keeps its seed."""
    new = 12
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 250, n) for n in (31, 7, 15)]
    sample = {"temperature": 0.9, "top_k": 20, "seed": 11}
    roomy = ServingGateway(make_engine(llama), auto_start=False,
                           config=ServingConfig(max_burst=burst, token_budget=32))
    want = []
    for i, p in enumerate(prompts):
        h = roomy.submit(p, max_new_tokens=new, sample=sample if i == 1 else None)
        pump_until(roomy, lambda: h.done, n=400)
        want.append(h.result(timeout=1))
    assert counters(roomy) == (0, 0, 0)
    roomy.drain(timeout=30)

    blocks = 4 + 1 + 2
    engine = make_engine(llama, blocks=blocks + 1)
    gw = ServingGateway(engine, auto_start=False,
                        config=ServingConfig(max_burst=burst, token_budget=32))
    gw.gate.reserve = lambda live: 0
    seen_at = {}
    preempt = gw.scheduler.preempt_for_room

    def watched():
        live = {uid: engine.query(uid) for uid in gw._active}
        request = preempt()
        seen_at[request.uid] = live[request.uid][0]
        assert request.recomputed == seen_at[request.uid]
        return request
    gw.scheduler.preempt_for_room = watched
    handles = [gw.submit(p, max_new_tokens=new, sample=sample if i == 1 else None)
               for i, p in enumerate(prompts)]
    gw._pump_once()
    assert gw.gate.active == 3 and gw.gate.headroom() == 0
    pump_until(gw, lambda: all(h.done for h in handles), n=600)
    assert [h.result(timeout=1) for h in handles] == want
    held_back, preempted, recomputed = counters(gw)
    assert held_back >= 2 and preempted == len(seen_at) >= 1
    assert recomputed == sum(seen_at.values()) and min(seen_at.values()) >= BLOCK
    assert gw.state == "running" and gw.snapshot()["counters"]["failed"] == 0
    holds_nothing(gw, engine, blocks)
    gw.drain(timeout=30)
