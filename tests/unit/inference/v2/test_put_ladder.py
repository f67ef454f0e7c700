"""The row counts a ``put`` may run at (``engine_v2.put_ladder``): the rule,
the program a step of a given size takes, the same rows through two sizes in
every state kind, what a scheduler has the engine build before its first
prompt step (and what that leaves in the pools: nothing), and the two counters
a gateway keeps of it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        RaggedInferenceEngineConfig, StructuredConfig)
from deepspeed_tpu.inference.v2.engine_v2 import put_ladder
from deepspeed_tpu.inference.v2.ragged.kv_cache import NULL_BLOCK
from deepspeed_tpu.inference.v2.scheduler import DynamicSplitFuseScheduler
from deepspeed_tpu.models import build_model
from deepspeed_tpu.utils import tracing

from unit.inference.v2.kinds import rel_err

TOL = 2e-5      # the kinds' own tests' (``kinds.Case.tol``)


def make_engine(preset="debug", block=8, seqs=4, tokens=64, context=128, config=None, **model):
    return InferenceEngineV2(
        model=build_model(preset, **model), dtype=jnp.float32, rng=jax.random.PRNGKey(3),
        config=RaggedInferenceEngineConfig(
            kv_block_size=block, num_kv_blocks=96,
            state_manager=DSStateManagerConfig(max_ragged_batch_size=tokens,
                                               max_ragged_sequence_count=seqs,
                                               max_tracked_sequences=seqs, max_context=context),
            **(config or {})))


# ------------------------------------------------------------------ the rule
# the benchmark's eleven engines (max_ragged_sequence_count, token budget), then the edges
LADDERS = {
    "ouro-2.6b": (16, 512, (16, 256, 512)),
    "minicpm-sala-16l": (24, 512, (24, 256, 512)),
    "laguna-xs2-ep8-20l": (48, 512, (48, 512)),
    "mixtral-8x7b": (64, 512, (64, 512)),
    "lfm2-24b-a2b-10l": (64, 512, (64, 512)),
    "mistral-7b": (128, 512, (128, 512)),
    "moonlight-16b-a3b": (128, 512, (128, 512)),
    "nemotron3-super-ep4-11l": (128, 512, (128, 512)),
    "jamba2-3b": (256, 512, (256, 512)),
    "longcat-flash-omni-ep32": (256, 512, (256, 512)),
    "solar-open2-ep8-4l": (192, 512, (192, 512)),
    "an-eighth-of-the-rung-is-the-decode-program": (4, 64, (4, 32, 64)),
    "one-sequence-more": (5, 64, (5, 64)),
    "one-token-less": (4, 63, (4, 63)),
    "a-quarter-of-the-rung-is-the-decode-program": (8, 64, (8, 64)),
    "an-odd-budget": (2, 33, (2, 16, 33)),
    "the-config's-defaults": (512, 768, (512, 768)),
    "as-many-sequences-as-tokens": (32, 32, (32,)),
    "more-sequences-than-tokens": (48, 32, (48,)),
}


@pytest.mark.parametrize("name", LADDERS)
def test_the_ladder_is_the_decode_size_one_rung_where_it_is_worth_one_and_the_budget(name):
    max_seqs, max_tokens, want = LADDERS[name]
    ladder = put_ladder(max_seqs, max_tokens)
    assert ladder == want and list(ladder) == sorted(set(ladder))
    assert ladder[0] == max_seqs and ladder[-1] == max(max_seqs, max_tokens)
    for rung in ladder[1:-1]:
        assert rung == max_tokens // 2 and rung >= 8 * max_seqs


@pytest.fixture(scope="module")
def engine():
    engine = make_engine()
    yield engine
    engine.destroy()


def fresh(engine, *uids):
    for uid in uids:
        if engine.state_manager.query(uid) is not None:
            engine.flush(uid)


@pytest.mark.parametrize("total,rows", [(4, 4), (5, 32), (32, 32), (33, 64), (64, 64)])
def test_put_takes_the_smallest_program_that_holds_the_step(engine, total, rows):
    assert engine.put_buckets == (4, 32, 64)
    fresh(engine, 1, 2)
    chunks = [np.arange(1, total - 1), np.arange(5, 7)]     # two sequences, `total` tokens
    logits = engine.put([1, 2], chunks)
    rec = engine.last_step
    assert (rec.kind, rec.program, rec.n_rows, rec.n_tokens, rec.n_seqs) == \
        ("put", str(rows), rows, total, 2)
    assert logits.shape[0] == 2 and ("logits", rows) in engine._put_built


def test_a_step_over_the_budget_is_refused_as_ever(engine):
    fresh(engine, 1)
    with pytest.raises(ValueError, match="65 tokens > max_ragged_batch_size=64"):
        engine.put([1], [np.arange(65)])


# ------------------------------------------------ every state kind: built, then both sizes
# preset, block size, what build_model takes besides: one case a state kind that has a rung, of
# the kinds whose state is keys and values the looped one too (four passes a step), and two
# expert kinds - a latent's, and one whose steps leave padding's picks outside every group
# (a kind whose experts run behind a share keeps two programs, the window ring's with it: below)
KINDS = {
    "kv": ("debug", 8, {}),
    "kv-looped": ("ouro-debug", 8, {}),
    "latent-experts": ("moonlight-debug", 16, {}),
    "sparse_kv+slots": ("minicpm-sala-debug", None, {}),
    "kv+slots-experts": ("lfm2-debug", 16, {}),
}


@pytest.fixture(scope="module", params=list(KINDS))
def kind_engine(request):
    preset, block, over = KINDS[request.param]
    if block is None:
        block = build_model(preset).config.sparse_block_size
    engine = make_engine(preset, block, **over)
    assert engine.put_buckets == (4, 32, 64)
    yield engine
    engine.destroy()


def live_state(engine):
    """The pools and the kind's further state outside the null block and the
    null slot (block 0 of the pools, of a window pool's arrays and of a
    selection's pooled keys; slot 0 of a slot's state)."""
    assert NULL_BLOCK == 0
    pools = {"k": engine.kv_cache.k, "v": engine.kv_cache.v, **(engine.state_extra or {})}
    return {name: np.asarray(x)[:, 1:].copy() for name, x in pools.items()}


def test_a_schedulers_first_prompt_step_has_the_rung_built_and_the_pools_left_alone(kind_engine):
    """A scheduler's first step over ``max_seqs`` tokens (a budget-sized
    chunk) builds its own program as ever, and then has the engine build its
    mode's other programs over ``max_seqs`` rows on batches of the null sequence alone:
    outside the null block and the null slot the pools hold after the build
    what that step alone left. A later rung-sized step compiles nothing. (The
    mode is ``logits``, whose programs the next test runs.)"""
    engine = kind_engine
    build, states = engine.build_put_programs, []

    def watched(budget):
        if engine.last_step.n_tokens == 64:         # (called after every prompt step)
            states.append(live_state(engine))
        built = build(budget)
        if built:
            states.append(live_state(engine))
        return built

    engine.build_put_programs = watched
    try:
        prompt = np.random.default_rng(5).integers(1, engine.model_config.vocab_size, 80).tolist()
        scheduler = DynamicSplitFuseScheduler(engine, sample_fn=lambda row: int(row.argmax()))
        scheduler.add_request(1, prompt, max_new_tokens=1)
        before = len(tracing.RECORDER.steps)
        assert scheduler.step() == [1]
        records = [(r.kind, r.program, r.n_tokens) for r in list(tracing.RECORDER.steps)[before:]
                   if r.engine == engine.trace_id]
        assert records == [("put", "64", 64), ("build", "32", 0)]
        assert engine.last_step.kind == "put"       # a build is no step of the engine's
        assert engine._put_built == {("logits", 32), ("logits", 64)}
        was, now = states
        assert set(was) == set(now) and len(was) >= 2 and any(x.any() for x in was.values())
        for name in was:
            np.testing.assert_array_equal(was[name], now[name], err_msg=name)
        # the rest of the prompt, 16 rows: the rung, which is built
        compiles = tracing.process_counters()[3]
        assert scheduler.step() == [1] and len(states) == 2
        rec = engine.last_step
        assert (rec.program, rec.n_tokens, rec.build) == ("32", 16, None)
        assert tracing.process_counters()[3] == compiles
        assert len(scheduler.requests[1].generated) == 1
    finally:
        engine.build_put_programs = build


def served(engine, steps):
    """``steps``: ``[(uid, tokens), ...]`` a step → the last step's logits and
    its record; every sequence is told whole first, as a scheduler does."""
    told = {}
    for step in steps:
        for uid, tokens in step:
            told.setdefault(uid, []).extend(tokens)
    for uid, tokens in told.items():
        engine.prefix_match(uid, tokens)
    for step in steps:
        logits = engine.put([u for u, _ in step], [t for _, t in step])
    rec = engine.last_step
    for uid in told:
        engine.flush(uid)
    return np.asarray(logits), rec


def test_a_step_gives_the_same_logits_through_the_rung_as_through_the_budgets_program(kind_engine):
    """A decode row and an 11-token prompt: 12 rows, the 32-row program.
    With a throw-away third sequence of 25 the step has 37 and runs the
    64-row one. The two kept sequences' logits and greedy tokens agree."""
    engine = kind_engine
    rng = np.random.default_rng(7)
    first, second, third = (rng.integers(1, engine.model_config.vocab_size, n).tolist()
                            for n in (4, 11, 25))
    steps = [[(11, first[:3])], [(11, first[3:]), (12, second)]]
    rung, rec = served(engine, steps)
    assert (rec.program, rec.n_rows, rec.n_tokens) == ("32", 32, 12)
    steps[1] = steps[1] + [(13, third)]
    budget, rec = served(engine, steps)
    assert (rec.program, rec.n_rows, rec.n_tokens) == ("64", 64, 37)
    assert rel_err(rung, budget[:2]) < TOL
    assert (rung.argmax(-1) == budget[:2].argmax(-1)).all()


SHARES = {
    "kv+window": ("laguna-debug", 4, {}),
    "latent": ("longcat-flash-debug", 16, {"experts_held": 4, "first_expert_held": 2}),
    "kv+slots": ("nemotron-h-debug", 16, {}),
    "kv+slots-kda": ("solar-open2-debug", 16, {}),
}


@pytest.mark.parametrize("name", SHARES)
def test_a_kind_whose_experts_run_behind_a_share_keeps_the_two_programs_it_had(name):
    """The one such engine with a rung (``laguna-xs2-ep8-20l``, 256 rows)
    never came back from its first 256-row step on the chip, and did with
    ``ragged_dot`` in the share's place (PERF.md, PR 55): a kind that counts
    ``n_share_passes`` has the two ends alone, a 10-token step takes the
    budget's program and a scheduler builds nothing."""
    preset, block, over = SHARES[name]
    engine = make_engine(preset, block, **over)
    try:
        assert "n_share_passes" in engine.kind.step_counts and engine.put_buckets == (4, 64)
        assert put_ladder(4, 64) == (4, 32, 64) and put_ladder(4, 64, rungs=False) == (4, 64)
        if name != "kv+window":
            return      # one of them served: its records
        assert engine.window_pool is not None
        scheduler = DynamicSplitFuseScheduler(engine, max_burst=1)
        scheduler.add_request(1, list(range(1, 11)), max_new_tokens=2)
        before = len(tracing.RECORDER.steps)
        scheduler.run_to_completion()
        records = [(r.kind, r.program) for r in list(tracing.RECORDER.steps)[before:]
                   if r.engine == engine.trace_id]
        assert records == [("put", "64"), ("put", "4")]
    finally:
        engine.destroy()


def test_a_scheduler_that_plans_no_prompt_step_builds_nothing(engine):
    fresh(engine, 1, 2)
    built = set(engine._put_built)
    scheduler = DynamicSplitFuseScheduler(engine, max_burst=1)
    scheduler.add_request(1, [5, 6, 7], max_new_tokens=3)      # 3 tokens: the decode program
    before = len(tracing.RECORDER.steps)
    assert len(scheduler.run_to_completion()[1]) == 3
    records = [r for r in list(tracing.RECORDER.steps)[before:] if r.engine == engine.trace_id]
    assert {(r.kind, r.program) for r in records} == {("put", "4")}
    assert engine._put_built - built == {("greedy", 4)}


def test_each_mode_is_built_once_before_its_first_prompt_step():
    """Greedy traffic builds the greedy programs; the first prompt step that
    carries a sampling spec builds the packed ones, both sizes at once; a
    host ``sample_fn`` the logits ones. Nothing is built twice."""
    engine = make_engine(config={"structured": StructuredConfig(enabled=False)})
    try:
        scheduler = DynamicSplitFuseScheduler(engine, max_burst=1)
        prompt = list(range(1, 11))
        scheduler.add_request(1, prompt, max_new_tokens=2)
        scheduler.step()                                    # 10 tokens: the rung, then the rest
        assert engine._put_built == {("greedy", 32), ("greedy", 64)}
        scheduler.add_request(2, prompt, max_new_tokens=2,
                              sample={"temperature": 0.8, "top_k": 5, "seed": 11})
        scheduler.step()
        assert engine._put_built == {("greedy", 32), ("greedy", 64),
                                     ("packed", 32), ("packed", 64)}
        compiles = tracing.process_counters()[3]
        scheduler.add_request(3, prompt * 4, max_new_tokens=2, sample={"temperature": 0.5, "seed": 1})
        scheduler.step()                                    # 2 decode rows + 40: the 64-row one
        assert engine.last_step.program == "64" and tracing.process_counters()[3] == compiles
        assert engine._put_mode == "packed" and engine.build_put_programs() == []
        host = DynamicSplitFuseScheduler(engine, max_burst=1, sample_fn=lambda row: int(row.argmax()))
        host.add_request(9, prompt, max_new_tokens=1)
        host.step()
        assert {size for mode, size in engine._put_built if mode == "logits"} == {32, 64}
    finally:
        engine.destroy()


# --------------------------------------------------------------------- the gateway's counters
def test_a_gateway_counts_its_prompt_steps_and_those_on_a_rung():
    from deepspeed_tpu.serving import ServingConfig, ServingGateway
    engine = make_engine()
    gateway = ServingGateway(engine, config=ServingConfig(default_max_new_tokens=4))
    try:
        # 80 tokens: a budget-sized step (64), then 16 on the rung; 10 tokens: the rung;
        # 3 tokens: the decode-sized program, no prompt step by the engine's count
        for prompt in (np.arange(1, 81), np.arange(1, 11), np.arange(1, 4)):
            assert len(gateway.submit(prompt, max_new_tokens=4).result(timeout=120)) == 4
        counters = gateway.snapshot()["counters"]
        records = [r for r in tracing.RECORDER.steps
                   if r.engine == engine.trace_id and r.kind == "put" and r.n_tokens > 4]
        assert [r.program for r in records] == ["64", "32", "32"]
        assert (counters["prompt_steps"], counters["prompt_steps_on_rung"]) == (3, 2)
        assert counters["engine_steps"] > 3
    finally:
        gateway.shutdown()
        engine.destroy()
