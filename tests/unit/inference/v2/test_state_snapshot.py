"""The prefix cache for a model kind with recurrent state: a **snapshot** of
a sequence's slot at a block boundary (``prefix_cache/manager.py``,
``InferenceEngineV2._copy_slots``), on the Granite 4.0-H debug preset
(float32 on the CPU, blocks of 16).

A sequence that starts behind a snapshot must be the sequence that ran from
token 0: the same bits where the chunks are cut alike, the reference's logits
wherever they are cut.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, PrefixCacheConfig
from deepspeed_tpu.models import build_model
from deepspeed_tpu.models.granite_hybrid import reference_logits

from unit.inference.v2.kinds import Case, engine_config, rel_err

CASE = Case(preset="granite-hybrid-debug", reference=None, refused=(), prefill=(), plans={},
            burst=None, records=None, step_counts=(), scopes=(), sequences=4, context=256)
BS = CASE.block
TOKENS = np.random.default_rng(7).integers(0, 256, (4, 200), dtype=np.int32)


def make_engine(model=None, params=None, cfg=None, **over):
    cache = PrefixCacheConfig(enabled=True, snapshot_slots=over.pop("snapshot_slots", 0))
    config = engine_config(CASE, prefix_cache=cache, **over)
    if params is not None:
        return InferenceEngineV2(params=params, model_config=cfg, config=config, dtype=jnp.float32)
    return InferenceEngineV2(model=model, config=config, dtype=jnp.float32,
                             rng=jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def engine():
    return make_engine(build_model(CASE.preset))


@pytest.fixture(scope="module")
def reference(engine):
    program = jax.jit(lambda params, ids: reference_logits(params, ids, engine.model_config))

    def logits(seq):
        padded = np.zeros((1, 200), np.int32)
        padded[0, :len(seq)] = seq
        return np.asarray(program(engine.params, jnp.asarray(padded))[0, :len(seq)])
    return logits


def run(engine, uid, seq, cuts, breakpoints=()):
    """``seq`` as sequence ``uid`` in chunks ending at ``cuts`` (positions
    before the cached prefix are skipped) → (cached tokens, {chunk end: logits})."""
    cached = engine.prefix_match(uid, seq, breakpoints=breakpoints)
    rows, at = {}, cached
    for end in cuts:
        if end <= at:
            continue
        rows[end] = engine.put([uid], [seq[at:end]])[0]
        at = end
    return cached, rows


def idle(engine):
    """Nothing tracked: every slot is free or a snapshot's."""
    pool, cache = engine.slot_pool, engine.prefix_cache
    assert engine.state_manager.n_tracked_sequences == 0
    assert pool.free_slots + len(pool.cached) == pool.slots
    assert cache.stats()["snapshots_cached"] == len(pool.cached)


def test_the_pool_holds_the_caches_slots_beyond_the_sequences(engine):
    assert engine.slot_pool.slots == 2 * CASE.sequences           # a trailing snapshot each
    assert engine.state_extra["ssm"].shape[1] == 2 * CASE.sequences + 1
    assert make_engine(params=engine.params, cfg=engine.model_config,
                       snapshot_slots=3).slot_pool.slots == CASE.sequences + 3


def test_a_resumed_turn_is_the_turn_run_from_zero_bit_for_bit(engine, reference):
    """A conversation's first turn (a prompt of 50 in chunks of 32 + 18, then
    20 decode rows) retires; its next turn's prompt is those 70 tokens and 30
    more. It starts at 64 - the last block boundary the turn crossed - and its
    logits are, bit for bit, those of one sequence run from 0 at the same cuts;
    at other cuts they are the reference's within the tolerance."""
    seq = TOKENS[0][:100]
    cuts = [32, 50] + list(range(51, 71)) + [80, 100]
    _, first = run(engine, 1, seq[:70], cuts[:-2])
    assert engine.last_step.counts["n_snapshots_taken"] == 0       # 70 is no boundary
    engine.flush(1)
    stats = engine.prefix_cache.stats()
    assert stats["snapshots_cached"] == 1 and stats["snapshots_taken"] == 2    # at 32 and at 64
    cached, resumed = run(engine, 2, seq, cuts)
    assert cached == 64 and engine.state_manager.query(2).cached_tokens == 64
    assert engine.last_step.counts["n_snapshots_restored"] == 0     # told in its first step's
    engine.flush(2)
    whole = make_engine(params=engine.params, cfg=engine.model_config)
    _, plain = run(whole, 3, seq, cuts)
    want = reference(seq)
    for end in (80, 100):
        assert np.array_equal(resumed[end], plain[end]), end
        assert rel_err(resumed[end], want[end - 1]) < CASE.tol
    for end, row in first.items():
        assert np.array_equal(row, plain[end]), end
    # at other cuts: a third turn of the same history, resumed at 80 (the second turn's last
    # landing: its chunk from 80 to 100 passed 96) and cut at 99
    cached, other = run(engine, 4, TOKENS[0][:120], [99, 120])
    assert cached == 80
    want = reference(TOKENS[0][:120])
    assert rel_err(other[99], want[98]) < CASE.tol and rel_err(other[120], want[119]) < CASE.tol
    engine.flush(4)
    idle(engine)


def test_the_first_step_of_a_resumed_turn_counts_the_restore(engine):
    seq = TOKENS[0][:130]
    cached, _ = run(engine, 5, seq, [100])
    counts = engine.last_step.counts
    assert cached >= 80 and counts["n_snapshots_restored"] == 1
    assert counts["n_snapshots_taken"] == 0 and counts["n_state_slots"] > 0
    engine.flush(5)


def test_two_sessions_share_a_system_prompts_snapshot(engine, reference):
    """A request names where its system prompt ends (40 tokens: the boundary at
    32): its prefill is cut there and the state kept. Two later requests with
    the same system prompt and other messages both start at 32."""
    system = TOKENS[1][:40]
    a = np.concatenate([system, TOKENS[2][:30]])
    assert engine.prefix_match(11, a, breakpoints=(40,)) == 0
    assert engine.chunk_cut(11, 0, 64) == 32 and engine.chunk_cut(11, 32, 64) == 64
    engine.put([11], [a[:32]])
    assert engine.last_step.counts["n_snapshots_taken"] == 1
    engine.put([11], [a[32:60]])
    engine.put([11], [a[60:]])
    engine.flush(11)
    before = engine.prefix_cache.stats()
    rows = {}
    for uid, message in ((12, TOKENS[3][:25]), (13, TOKENS[3][50:90])):
        seq = np.concatenate([system, message])
        cached, got = run(engine, uid, seq, [60, len(seq)], breakpoints=(40,))
        assert cached == 32
        rows[uid] = (got[len(seq)], reference(seq)[-1])
    for got, want in rows.values():
        assert rel_err(got, want) < CASE.tol
    after = engine.prefix_cache.stats()
    assert after["tokens_saved_by_kind"]["breakpoint"] \
        - before["tokens_saved_by_kind"]["breakpoint"] == 64
    assert after["snapshots_restored"] - before["snapshots_restored"] == 2
    assert engine.prefix_match_len(np.concatenate([system, TOKENS[0][:9]])) == 32
    engine.flush(12)
    engine.flush(13)
    idle(engine)


def test_blocks_past_every_snapshot_are_not_matched_and_not_kept(engine):
    """Keys and values alone cannot start a sequence of this kind: a match ends
    at the deepest snapshot, and a retiring sequence inserts no block past its own."""
    seq = np.random.default_rng(3).integers(0, 256, 62, dtype=np.int32)
    run(engine, 21, seq, [31, 62])              # no boundary landed on
    nodes = engine.prefix_cache.cached_blocks
    engine.flush(21)
    assert engine.prefix_cache.cached_blocks == nodes       # three whole blocks, none kept
    assert engine.prefix_match_len(seq) == 0
    cached, _ = run(engine, 22, seq, [20, 48, 62])          # lands on 48
    assert cached == 0
    engine.flush(22)
    assert engine.prefix_cache.cached_blocks == nodes + 3 and engine.prefix_match_len(seq) == 48
    idle(engine)


def test_eviction_under_slot_pressure_never_touches_a_live_slot(engine, reference):
    """Four tracked sequences and one slot for the cache. Snapshots give way,
    least recently used first, to sequences and to newer snapshots; a live
    sequence's own slot is never among them, and its logits stay the reference's."""
    small = make_engine(params=engine.params, cfg=engine.model_config, snapshot_slots=1)
    pool, cache = small.slot_pool, small.prefix_cache
    assert pool.slots == 5
    seqs = [np.random.default_rng(40 + i).integers(0, 256, 40, dtype=np.int32) for i in range(6)]
    run(small, 30, seqs[0], [32, 40])
    small.flush(30)
    assert len(pool.cached) == 1 and cache.stats()["snapshots_cached"] == 1
    live = {}
    for i in range(1, 5):                   # four live sequences, each landing on 32
        run(small, 30 + i, seqs[i], [32])
        live[30 + i] = small.state_manager.query(30 + i).state_row[0]
    assert len(set(live.values())) == 4 and pool.free_slots == 0
    assert not set(live.values()) & pool.cached and len(pool.cached) == 1
    assert cache.stats()["snapshot_evictions"] >= 3         # one slot, four takers after the first
    held = {uid: np.asarray(small.state_extra["ssm"][:, slot]) for uid, slot in live.items()}
    for uid, slot in live.items():          # the copies moved nothing of the live slots
        assert np.array_equal(np.asarray(small.state_extra["ssm"][:, slot]), held[uid])
    for i in range(1, 5):
        got = small.put([30 + i], [seqs[i][32:]])[0]
        assert rel_err(got, reference(seqs[i])[-1]) < CASE.tol
        small.flush(30 + i)
    # the gate counts the cache's slots as a sequence's to take
    from deepspeed_tpu.serving.admission import CapacityGate
    gate = CapacityGate(small, token_budget=32)
    assert pool.reclaimable_slots == pool.slots and gate.try_commit(99, 10, 4)
    assert gate.refused_by["slots"] == 0


def test_a_new_weight_version_drops_the_snapshots_with_the_trie(engine):
    run(engine, 41, TOKENS[2][:40], [32, 40])
    engine.flush(41)
    cache, pool = engine.prefix_cache, engine.slot_pool
    assert cache.stats()["snapshots_cached"] >= 1 and len(pool.cached) >= 1
    cache.invalidate_for_version(7)
    assert cache.stats()["snapshots_cached"] == 0 and not pool.cached
    assert pool.free_slots == pool.slots and cache.cached_blocks == 0
    assert engine.prefix_match_len(TOKENS[2][:40]) == 0


def test_a_burst_may_end_on_a_boundary_and_may_not_pass_one(engine):
    seq = TOKENS[3][:61]
    run(engine, 51, seq, [30, 61])
    assert engine.can_burst([51], 2) and not engine.can_burst([51], 4)    # 61 + 3 = 64 it may reach
    toks = engine.decode_burst([51], [int(seq[-1])], 2)
    assert toks.shape == (2, 1) and engine.state_manager.query(51).seen_tokens == 63
    assert engine.can_burst([51], 1) and not engine.can_burst([51], 2)
    engine.flush(51)


@pytest.mark.parametrize("preset", ["nemotron-h-debug", "lfm2-debug", "jamba-debug",
                                    "solar-open2-debug"])
def test_the_other_kinds_of_keys_and_values_beside_slots_refuse_the_cache_by_name(preset):
    """The mechanism reads only ``kind.slot_state``; until a cell runs it on
    them, the four other kinds say ``snapshots`` False and are refused."""
    model = build_model(preset)
    with pytest.raises(NotImplementedError, match="prefix cache") as e:
        InferenceEngineV2(model=model, dtype=jnp.float32, config=engine_config(
            CASE, prefix_cache=PrefixCacheConfig(enabled=True)))
    from deepspeed_tpu.inference.v2 import model_runner
    kind = model_runner.kind_of(model.config)
    assert not kind.snapshots and repr(kind.name) in str(e.value) and "'kv+slots'" in str(e.value)


def test_sessions_through_the_gateway_resume_and_the_record_says_so(engine, reference):
    """Two turns of a conversation behind ``ServingGateway``: the second's
    prompt is the first's prompt, its answer and a new message; it is served
    from the snapshot, its tokens are the reference's argmax, and its request
    record keeps ``prefix_cached_tokens``."""
    from deepspeed_tpu.serving import ServingConfig, ServingGateway
    served = make_engine(params=engine.params, cfg=engine.model_config)
    gateway = ServingGateway(served, config=ServingConfig(default_max_new_tokens=8))
    try:
        system = TOKENS[1][100:140]
        first = np.concatenate([system, TOKENS[2][100:130]])
        answer = gateway.submit(first, max_new_tokens=30, cache_breakpoints=(40,)).result(timeout=300)
        second = np.concatenate([first, np.asarray(answer, np.int32), TOKENS[2][140:160]])
        handle = gateway.submit(second, max_new_tokens=6, cache_breakpoints=(40,))
        again = handle.result(timeout=300)
        stats = served.prefix_cache.stats()
        from deepspeed_tpu.utils import tracing
        records = [r for r in tracing.snapshot()["requests"]
                   if r["uid"] == handle.uid and r["engine"] == served.trace_id]
    finally:
        gateway.shutdown()
    assert stats["tokens_saved_by_kind"]["trailing"] == 96 and stats["snapshots_restored"] == 1
    assert records and records[0]["prefix_cached_tokens"] == 96
    whole = np.concatenate([second, np.asarray(again, np.int32)])
    want = reference(whole)
    margins = np.sort(want[len(second) - 1:len(whole) - 1], axis=-1)
    clear = margins[:, -1] - margins[:, -2] > 1e-4
    assert clear.sum() >= 4
    assert (np.argmax(want[len(second) - 1:len(whole) - 1], -1)[clear]
            == np.asarray(again)[clear]).all()
