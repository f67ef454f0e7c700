"""Nemotron-H through the v2 ragged engine at the debug preset: the served
logits against the plain float32 reference, and the pieces alone.

The served path keeps the attention layers' keys and values in paged
pools and every Mamba-2 layer's state and convolution tail in a slot a
sequence, takes a step's rows through a packed recurrence (a decay mask
for a chunk's own rows, the carried state for all sequences' first rows
at once - one visit of each slot, ``ops/pallas/ssm_state``: the tests of
the mixer run it both ways, ``xla`` and the kernel interpreted - and for
further rows a few sequences a round) and the held picks
of the expert layer through a grouped matmul in the latent; the reference
(``models/nemotron_h.reference_logits``) runs whole sequences, the
recurrence a token at a time, every held expert on every token. They
share no line.

Tolerances as ``test_minicpm_sala.py``: float32 engines on the CPU, so the
two differ by the order of float32 additions (relative L2 errors of
3-7e-7 were read when this was written); ``TOL`` = 2e-5.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        KVTierConfig, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig, SpecDecodeConfig)
from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.inference.v2.config_v2 import LoRAServingConfig, QuantizationConfig
from deepspeed_tpu.models import NEMOTRON_H_CONFIGS, build_model
from deepspeed_tpu.models.nemotron_h import (PUBLISHED_PATTERN, NemotronHConfig, layer_params,
                                             param_shapes, reference_experts, reference_logits,
                                             reference_mamba)
from deepspeed_tpu.utils import tracing

TOL = 2e-5
DEBUG = NEMOTRON_H_CONFIGS["nemotron-h-debug"]
BLOCK = 16
KIND = model_runner.NemotronHKind


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def engine_config(**over):
    return RaggedInferenceEngineConfig(
        kv_block_size=BLOCK, num_kv_blocks=96,
        state_manager=DSStateManagerConfig(max_ragged_batch_size=32,
                                           max_ragged_sequence_count=4,
                                           max_tracked_sequences=4, max_context=192), **over)


@pytest.fixture(scope="module")
def model():
    return build_model("nemotron-h-debug")


@pytest.fixture(scope="module")
def engine(model):
    return InferenceEngineV2(model=model, config=engine_config(), dtype=jnp.float32,
                             rng=jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def kernel_engine(model):
    """An engine whose programs are first run under ``DS_PALLAS=1``
    (``state_step``'s second case), so that they hold the state step's
    kernel, interpreted."""
    return InferenceEngineV2(model=model, config=engine_config(), dtype=jnp.float32,
                             rng=jax.random.PRNGKey(5))


@pytest.fixture(params=["xla", "pallas_ssm_state"])
def state_step(request, monkeypatch):
    """What serves the Mamba-2 state step in the test: the reference, or
    the kernel (``DS_PALLAS=1`` forces the kernel paths, interpreted off
    the chip)."""
    if request.param != "xla":
        monkeypatch.setenv("DS_PALLAS", "1")
    return request.param


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(3).integers(0, 256, (4, 192), dtype=np.int32)


_REFERENCE = {}        # a config → its jitted reference, one program for every length


def reference(engine, seq):
    """The reference's logits [len(seq), V]: the sequence padded to the rows'
    192 tokens, which a causal model's rows before the padding cannot see, so
    that one compiled program serves every length a test asks for."""
    cfg = engine.model_config
    if cfg not in _REFERENCE:
        _REFERENCE[cfg] = jax.jit(lambda params, ids: reference_logits(params, ids, cfg))
    padded = np.zeros((1, 192), np.int32)
    padded[0, :len(seq)] = seq
    return np.asarray(_REFERENCE[cfg](engine.params, jnp.asarray(padded))[0, :len(seq)])


def serve(engine, plan):
    """``plan``: steps of ``[(uid, tokens)]`` → {uid: [the logits row of
    each of its steps]}; a uid's first appearance tells the engine its
    prompt, as the scheduler does."""
    rows = {}
    for step in plan:
        for u, t in step:
            if engine.state_manager.query(u) is None:
                engine.prefix_match(u, t)
        out = engine.put([u for u, _ in step], [t for _, t in step])
        for (u, _), row in zip(step, out):
            rows.setdefault(u, []).append(row)
    return rows


# ------------------------------------------------------------- the model file
def test_the_presets_are_the_published_pattern_and_its_cut():
    whole, cut = NemotronHConfig(), NEMOTRON_H_CONFIGS["nemotron3-super-ep4-11l"]
    assert len(PUBLISHED_PATTERN) == 88
    assert [PUBLISHED_PATTERN.count(t) for t in "ME*"] == [40, 40, 8]
    assert cut.hybrid_override_pattern == PUBLISHED_PATTERN[26:37] == "EMEMEMEMEM*"
    assert cut.segments == (("EM", 5), ("*", 1))              # one scan over a period, one layer
    assert DEBUG.segments == (("EM", 2), ("*", 1), ("M", 1), ("E", 1), ("M", 1))
    assert "".join(u * r for u, r in whole.segments) == PUBLISHED_PATTERN
    assert (cut.hidden_size, cut.mamba_num_heads, cut.mamba_head_dim, cut.n_groups,
            cut.ssm_state_size, cut.moe_latent_size, cut.moe_intermediate_size,
            cut.num_experts_per_tok, cut.n_routed_experts, cut.held, cut.vocab_size) == (
                4096, 128, 64, 8, 128, 1024, 2688, 22, 512, 128, 32768)
    assert cut.conv_dim == 10240 and cut.mamba_inner == 8192
    assert model_runner.kind_of(DEBUG) is KIND

    def count(cfg):
        return sum(int(np.prod(s)) for s in jax.tree.leaves(
            param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))

    assert 120.6e9 < count(whole) < 120.8e9                   # the published "120B": 120.67 B
    assert count(cut) == 4648163712                           # ISSUE 38's count: 4.648 B


@pytest.mark.parametrize("field,value", [
    ("hybrid_override_pattern", "EM-M"), ("n_group", 2), ("topk_group", 2),
    ("norm_topk_prob", False), ("attention_bias", True), ("mamba_proj_bias", True),
    ("mlp_bias", True), ("use_conv_bias", False), ("tie_word_embeddings", True),
    ("mamba_hidden_act", "gelu"), ("mlp_hidden_act", "silu"), ("expand", 4),
    ("n_shared_experts", 2)])
def test_what_is_not_implemented_is_refused_by_name(field, value):
    over = {field: value}
    if field == "hybrid_override_pattern":
        over["num_hidden_layers"] = len(value)
    with pytest.raises(ValueError, match=field):
        dataclasses.replace(DEBUG, **over)


def test_a_share_outside_the_routers_columns_is_refused():
    with pytest.raises(ValueError, match="not among"):
        dataclasses.replace(DEBUG, experts_held=6, first_expert_held=4)


# ---------------------------------------------- the engine against the forward
@pytest.mark.parametrize("prompt,steps,chunks", [
    (20, 6, [20]), (75, 5, [32, 32, 11]), (100, 4, [7, 32, 32, 29]), (3, 8, [2, 1])])
def test_prefill_in_chunks_then_decode_through_the_pools_and_the_slots(engine, tokens, prompt,
                                                                       steps, chunks):
    seq = tokens[0][:prompt + steps]
    plan, at = [], 0
    for n in chunks:
        plan.append([(7, seq[at:at + n])])
        at += n
    plan += [[(7, seq[prompt + j:prompt + j + 1])] for j in range(steps)]
    engine.prefix_match(7, seq[:prompt])
    rows = serve(engine, plan)[7]
    engine.flush(7)
    want = reference(engine, seq)
    compared = [sum(chunks[:i + 1]) - 1 for i in range(len(chunks))] \
        + [prompt + j for j in range(steps)]
    assert max(rel_err(r, want[p]) for r, p in zip(rows, compared)) < TOL


def test_two_prompts_in_one_chunk_beside_decoding_sequences(engine, tokens):
    """One step holds a decode row, the end of one prompt and the start of
    another: the sequences with further rows go through the rounds, the
    others through the all-at-once path, in one program."""
    a, b, c = tokens[0][:60], tokens[1][:41], tokens[2][:30]
    for uid, seq in ((1, a[:50]), (2, b[:40]), (3, c[:29])):
        engine.prefix_match(uid, seq)
    rows = serve(engine, [[(1, a[:32])], [(3, c[:29])],
                          [(3, c[29:30]), (1, a[32:50]), (2, b[:13])],
                          [(1, a[50:51]), (2, b[13:40])],
                          [(1, a[51:52]), (2, b[40:41])]])
    for uid in (1, 2, 3):
        engine.flush(uid)
    wa, wb, wc = reference(engine, a), reference(engine, b), reference(engine, c)
    got = [(rows[1][1], wa[49]), (rows[1][2], wa[50]), (rows[1][3], wa[51]),
           (rows[2][1], wb[39]), (rows[2][2], wb[40]), (rows[3][0], wc[28]), (rows[3][1], wc[29])]
    assert max(rel_err(g, w) for g, w in got) < TOL


def test_decode_bursts_carry_every_state(engine, tokens):
    seq = tokens[1][:80]
    engine.prefix_match(50, seq)
    for at in (0, 32, 64):
        out = engine.put([50], [seq[at:at + 32][:80 - at]])
    first = int(np.argmax(out[0]))
    burst = [first] + [int(t) for t in engine.decode_burst([50], [first], 8)[:, 0]]
    burst += [int(t) for t in engine.decode_burst([50], burst[-1:], 8)[:, 0]]
    counts = engine.last_step.counts
    assert counts["n_ssm_rows"] == counts["n_state_slots"] == 8 * DEBUG.count("M")
    engine.flush(50)
    full = np.concatenate([seq, np.asarray(burst[:-1], np.int32)])
    greedy = [int(t) for t in np.argmax(reference(engine, full)[79:], axis=-1)]
    assert burst == greedy


def test_a_slot_is_reused_with_its_stale_state_and_the_next_owner_starts_from_zero(
        request, state_step, tokens):
    engine = request.getfixturevalue("engine" if state_step == "xla" else "kernel_engine")
    assert engine.slot_pool.free_slots == engine.slot_pool.slots == 4
    serve(engine, [[(11, tokens[2][:30])]])
    slot = engine.state_manager.query(11).state_row[0]
    engine.flush(11)
    assert np.abs(np.asarray(engine.state_extra["ssm"][:, slot])).max() > 1e-3
    assert np.abs(np.asarray(engine.state_extra["conv"][:, slot])).max() > 1e-3
    seq = tokens[3][:32]
    rows = serve(engine, [[(12, seq[:2])], [(12, seq[2:31])], [(12, seq[31:32])]])[12]
    assert engine.state_manager.query(12).state_row[0] == slot           # the same slot
    assert engine.last_step.state_step == state_step
    assert set(engine.state_step_impls.values()) == {state_step}
    engine.flush(12)
    want = reference(engine, seq)
    assert max(rel_err(r, want[p]) for r, p in zip(rows, (1, 30, 31))) < TOL
    # a slot's bytes are both entries': what the gate and the start-up line count
    per = sum(int(np.prod(engine.state_extra[k].shape[2:])) * engine.state_extra[k].dtype.itemsize
              for k in KIND.slot_state)
    assert engine.slot_pool.bytes_per_slot == DEBUG.count("M") * per


# --------------------------------------------------------- the pieces alone
def _batch(rows, n_rows, slots):
    """``rows``: [(sequence row, first position, length)] in batch order."""
    seq = np.concatenate([np.full(n, s, np.int32) for s, _, n in rows])
    pos = np.concatenate([np.arange(f, f + n, dtype=np.int32) for _, f, n in rows])
    state = np.zeros((n_rows, 1), np.int32)
    state[:len(slots), 0] = slots
    return {"token_seq": jnp.asarray(seq), "token_pos": jnp.asarray(pos),
            "block_tables": jnp.zeros((n_rows, 1), jnp.int32), "seq_state": jnp.asarray(state)}


def _pools(cfg, slots, fill):
    Lm = cfg.count("M")
    return (jnp.full((Lm, slots + 1, cfg.mamba_num_heads, cfg.mamba_head_dim,
                      cfg.ssm_state_size), fill, jnp.float32),
            jnp.full((Lm, slots + 1, cfg.conv_kernel - 1, cfg.conv_dim), fill, jnp.float32))


@pytest.mark.parametrize("chunk", [7, 64, 150])
def test_a_prompt_in_chunks_leaves_the_state_and_the_tail_of_the_recurrence(engine, chunk,
                                                                            state_step):
    """A prompt of 150 rows through ``M`` layer 1 in chunks of 7, of 64 and
    whole, in a slot that held ones: the same output rows, state and tail
    as the reference's token-by-token recurrence from zero."""
    cfg, layer, S = engine.model_config, 1, 150
    x = jax.random.normal(jax.random.PRNGKey(2), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["mamba_layers"])
    with jax.default_matmul_precision("highest"):
        want, state, tail = reference_mamba(lp, x[None], cfg)
    ssm, conv = _pools(cfg, 2, 1.0)
    got = []
    for at in range(0, S, chunk):
        n = min(chunk, S - at)
        y, ssm, conv = KIND.mamba_layer(engine.params, cfg, layer, x[at:at + n], ssm, conv,
                                        _batch([(0, at, n)], 2, [2]))
        got.append(y)
    assert rel_err(jnp.concatenate(got), want[0]) < TOL
    assert rel_err(ssm[layer, 2], state[0]) < TOL and rel_err(conv[layer, 2], tail[0]) < TOL
    assert np.asarray(ssm[layer, 1] == 1.0).all()                     # no other slot is touched


def test_decode_rows_beside_chunks_in_one_step_each_from_its_own_state(engine, state_step):
    """Five sequences' rows in one step - two decode rows, a prompt's
    first chunk, a later chunk of two rows and one of nine (three
    sequences with further rows: two rounds at MAMBA_ROUND 2) - each
    against the token-by-token recurrence continued from the state and
    tail its sequence carried."""
    cfg, layer = engine.model_config, 0
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["mamba_layers"])
    key = jax.random.PRNGKey(4)
    before = [30, 12, 0, 9, 5]                   # rows each sequence has behind it
    now = [1, 1, 6, 2, 9]
    xs = [jax.random.normal(jax.random.fold_in(key, i), (b + n, cfg.hidden_size))
          for i, (b, n) in enumerate(zip(before, now))]
    ssm, conv = _pools(cfg, 6, 0.5)
    slots = [3, 1, 6, 2, 5]
    want = []
    with jax.default_matmul_precision("highest"):
        for i, (b, n) in enumerate(zip(before, now)):
            state = tail = None
            if b:
                _, state, tail = reference_mamba(lp, xs[i][None, :b], cfg)
                ssm = ssm.at[layer, slots[i]].set(state[0])
                conv = conv.at[layer, slots[i]].set(tail[0])
            want.append(reference_mamba(lp, xs[i][None, b:], cfg, state, tail))
    batch = _batch([(i, b, n) for i, (b, n) in enumerate(zip(before, now))], 7, slots)
    x = jnp.concatenate([xs[i][b:] for i, b in enumerate(before)])
    old = model_runner.MAMBA_ROUND
    model_runner.MAMBA_ROUND = 2
    try:
        y, ssm, conv = KIND.mamba_layer(engine.params, cfg, layer, x, ssm, conv, batch)
    finally:
        model_runner.MAMBA_ROUND = old
    at = 0
    for i, n in enumerate(now):
        out, state, tail = want[i]
        assert rel_err(y[at:at + n], out[0]) < TOL, i
        assert rel_err(ssm[layer, slots[i]], state[0]) < TOL, i
        assert rel_err(conv[layer, slots[i]], tail[0]) < TOL, i
        at += n
    assert np.asarray(ssm[layer, 4] == 0.5).all()                     # a slot no row names


def test_the_order_around_the_in_place_write_in_a_mixed_step(engine, state_step):
    """One step of decode rows, later chunks of several rows, prompts that
    start with several rows, sequence rows with no token and padding: the
    further rows of a chunk read the state its sequence carried - the
    *prior* one, though the step writes the new one where it lay - and add
    to the one the step leaves; a prompt that starts here reads nothing of
    what its slot held. Six sequences with further rows, so two rounds at
    ``MAMBA_ROUND`` 4. State and ``y`` against the token-by-token
    reference continued from what each sequence carried."""
    cfg, layer = engine.model_config, 1
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["mamba_layers"])
    key = jax.random.PRNGKey(11)
    #          decode  chunk  fresh  decode  chunk  fresh  chunk  chunk  fresh (one row)
    before = [17,     8,     0,     3,      21,    0,     5,     2,     0]
    now = [1,         5,     4,     1,      3,     7,     2,     6,     1]
    rows_of_batch = [0, 1, 2, 4, 5, 6, 7, 9, 10]          # sequence rows 3 and 8 hold nothing
    slots = [7, 2, 9, 4, 11, 1, 6, 3, 10]
    xs = [jax.random.normal(jax.random.fold_in(key, i), (b + n, cfg.hidden_size))
          for i, (b, n) in enumerate(zip(before, now))]
    ssm, conv = _pools(cfg, 12, 0.25)
    want = []
    with jax.default_matmul_precision("highest"):
        for i, (b, n) in enumerate(zip(before, now)):
            state = tail = None
            if b:
                _, state, tail = reference_mamba(lp, xs[i][None, :b], cfg)
                ssm = ssm.at[layer, slots[i]].set(state[0])
                conv = conv.at[layer, slots[i]].set(tail[0])
            want.append(reference_mamba(lp, xs[i][None, b:], cfg, state, tail))
    n_rows, pad = 12, 3
    batch = _batch([(r, b, n) for r, b, n in zip(rows_of_batch, before, now)]
                   + [(n_rows - 1, 0, 1)] * pad, n_rows, [])
    state_rows = np.zeros((n_rows, 1), np.int32)
    state_rows[rows_of_batch, 0] = slots
    batch["seq_state"] = jnp.asarray(state_rows)
    x = jnp.concatenate([xs[i][b:] for i, b in enumerate(before)]
                        + [jnp.ones((pad, cfg.hidden_size))])
    held = np.asarray(ssm)
    y, ssm, conv = jax.jit(lambda x, ssm, conv: KIND.mamba_layer(
        engine.params, cfg, layer, x, ssm, conv, batch), donate_argnums=(1, 2))(x, ssm, conv)
    at = 0
    for i, n in enumerate(now):
        out, state, tail = want[i]
        assert rel_err(y[at:at + n], out[0]) < TOL, i
        assert rel_err(ssm[layer, slots[i]], state[0]) < TOL, i
        assert rel_err(conv[layer, slots[i]], tail[0]) < TOL, i
        at += n
    # nothing else of the pool moved: the slots no sequence of the step owns - padding's
    # slot 0, which the rows without a sequence name, among them - and the other layers
    for slot in (0, 5, 8, 12):
        assert np.array_equal(np.asarray(ssm[layer, slot]), held[layer, slot]), slot
    assert np.array_equal(np.asarray(ssm[0]), held[0])


def test_the_packed_rows_bookkeeping_sums_each_sequences_log_decays():
    seq = jnp.asarray([0, 0, 0, 2, 1, 1, 3, 3], jnp.int32)           # 3: padding's row
    pos = jnp.asarray([4, 5, 6, 0, 9, 10, 0, 0], jnp.int32)
    log_decay = -jnp.arange(1.0, 9.0)[:, None] * jnp.asarray([[1.0, 0.5]])
    rows = model_runner._packed_rows(seq, pos, 4, log_decay)
    first, length = model_runner._row_spans(seq, pos, 4)
    assert [list(np.asarray(v)) for v in (first, length)] == [[4, 9, 0, 0], [3, 2, 1, 1]]
    np.testing.assert_allclose(np.asarray(rows.since)[:6, 0], [-1, -3, -6, -4, -5, -11])
    np.testing.assert_allclose(np.asarray(rows.until)[:6, 0], [-5, -3, 0, 0, -6, 0])
    np.testing.assert_allclose(np.asarray(rows.whole)[:3, 1], [-3, -5.5, -2])
    w = np.asarray(rows.weights)[0]
    assert w[2, 0] == pytest.approx(np.exp(-5.0)) and w[0, 2] == 0 and w[4, 3] == 0
    assert w[5, 4] == pytest.approx(np.exp(-6.0)) and w[1, 1] == 1.0


def test_the_served_expert_layer_is_the_references_on_a_share(engine):
    cfg = dataclasses.replace(engine.model_config, experts_held=4, first_expert_held=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.hidden_size))
    moe = dict(engine.params["model"]["moe_layers"])
    moe["experts"] = jax.tree.map(lambda w: w[:, 2:6], moe["experts"])
    params = {"model": {"moe_layers": moe}}
    with jax.default_matmul_precision("highest"):
        want = reference_experts(jax.tree.map(lambda w: w[1], moe), x, cfg)
    assert rel_err(KIND.expert_layer(params, cfg, 1, x), want) < TOL


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(engine):
    """Every share routes over all 8 columns and computes its own 2
    experts' part; the shared expert is what every rank computes alike,
    counted once: the parts add up to the reference with all 8 held."""
    cfg, layer = engine.model_config, 2
    x = jax.random.normal(jax.random.PRNGKey(9), (40, cfg.hidden_size))
    moe = engine.params["model"]["moe_layers"]
    whole = jax.tree.map(lambda w: w[layer], moe)
    with jax.default_matmul_precision("highest"):
        want = reference_experts(whole, x, cfg)
        shared = want - reference_experts(whole, x, cfg, shared=False)
    total = shared
    for rank in range(4):
        part = dataclasses.replace(cfg, experts_held=2, first_expert_held=2 * rank)
        held = dict(moe, experts=jax.tree.map(lambda w: w[:, 2 * rank:2 * rank + 2],
                                              moe["experts"]))
        served = KIND.expert_layer({"model": {"moe_layers": held}}, part, layer, x)
        total = total + served - shared                      # the share's routed part alone
    assert rel_err(total, want) < TOL
    # and a share's routed part alone is not the layer's: the absent picks are left out
    assert rel_err(served - shared, want - shared) > 0.3


# ----------------------------------------------------------- what is refused
@pytest.mark.parametrize("name,over", [
    ("prefix cache", {"prefix_cache": PrefixCacheConfig(enabled=True)}),
    ("KV tier", {"kv_tier": KVTierConfig(enabled=True)}),
    ("speculative decoding", {"spec_decode": SpecDecodeConfig(enabled=True)}),
    ("LoRA serving", {"lora": LoRAServingConfig(enabled=True)}),
    ("weight-only quantization", {"quantization": QuantizationConfig(quantization_mode="wf6af16")}),
    ("tensor/expert-parallel sharding", {"expert_parallel_degree": 2}),
])
def test_each_subsystem_that_assumes_two_kv_pools_refuses_the_model_by_name(model, name, over):
    with pytest.raises(NotImplementedError, match=name) as e:
        InferenceEngineV2(model=model, config=engine_config(**over), dtype=jnp.float32)
    assert "'kv+slots'" in str(e.value) and "nemotron_h" in str(e.value)


def test_suspend_and_an_unannounced_prompt_are_refused_by_name(engine, tokens):
    with pytest.raises(ValueError, match="needs the whole prompt.*prefix_match"):
        engine.put([70], [tokens[0][:5]])
    serve(engine, [[(70, tokens[0][:5])]])
    with pytest.raises(NotImplementedError, match="suspend/resume.*kv\\+slots"):
        engine.suspend(70)
    engine.flush(70)
    assert engine.slot_pool.free_slots == engine.slot_pool.slots


# ------------------------------------------------------------------- tracing
def test_step_records_carry_the_counts_and_the_scopes_are_in_the_program(engine, tokens):
    from deepspeed_tpu.models.nemotron_h import reference_router
    cfg = engine.model_config
    a, b = tokens[2][:40], tokens[3][:9]
    engine.prefix_match(60, a)
    engine.prefix_match(61, b)
    syncs = engine.host_syncs
    engine.put([60, 61], [a[:20], b])
    assert engine.host_syncs - syncs == 2            # as for any model kind: pack + fetch
    counts = engine.last_step.counts
    assert set(counts) == set(KIND.step_counts)
    assert counts["n_ssm_rows"] == 29 * cfg.count("M")
    assert counts["n_state_slots"] == 2 * cfg.count("M") and counts["n_picks_zero"] == 0
    assert 0 < counts["n_picks_held"] <= 29 * cfg.num_experts_per_tok * cfg.count("E")
    assert 0 < counts["n_groups_live"] <= cfg.held * cfg.count("E")
    assert counts["n_share_passes"] == cfg.count("E")   # every expert held: a pass a layer
    assert tracing.snapshot()["steps"][-1]["counts"] == counts
    engine.flush(60)
    engine.flush(61)
    # the first expert layer reads the embedding alone: its picks are the reference's
    x = engine.params["model"]["embed_tokens"][jnp.concatenate([a[:20], b])]
    first = layer_params(engine.params, cfg, 0)
    from deepspeed_tpu.models.moonlight import _rms_norm
    weights, _ = reference_router(first, _rms_norm(x, first["norm"]["scale"],
                                                   cfg.layer_norm_epsilon), cfg)
    assert int((np.asarray(weights) > 0).sum()) == 29 * cfg.num_experts_per_tok
    lowered = engine._step.lower(engine.params, engine.kv_cache.k, engine.kv_cache.v,
                                 engine.state_extra, engine._batch.finalize_packed()).as_text(
                                     debug_info=True)
    for scope in ("ds.nemotron.mamba", "ds.nemotron.attn", "ds.nemotron.latent_moe",
                  "ds.moe_routed", "ds.moe_shared"):
        assert scope in lowered, scope


# ------------------------------------------------------------------- gateway
def test_the_gateway_serves_it_through_the_same_scheduler(model, engine, tokens):
    from deepspeed_tpu.serving import ServingConfig, ServingGateway
    prompts = [tokens[0][:75], tokens[1][:9], tokens[2][:40]]
    served = InferenceEngineV2(params=engine.params, model_config=model.config,
                               config=engine_config(), dtype=jnp.float32)
    pool = served.slot_pool
    gateway = ServingGateway(served, config=ServingConfig(default_max_new_tokens=12))
    try:
        handles = [gateway.submit(p, max_new_tokens=12) for p in prompts]
        streams = [[int(t) for t in h.result(timeout=300)] for h in handles]
    finally:
        gateway.shutdown()
    for prompt, stream in zip(prompts, streams):
        full = np.concatenate([prompt, np.asarray(stream[:-1], np.int32)])
        ref = reference(engine, full)
        assert stream == [int(t) for t in np.argmax(ref[len(prompt) - 1:], axis=-1)]
    records = [r for r in tracing.snapshot()["steps"] if r["engine"] == served.trace_id]
    assert {"burst", "put"} <= {r["kind"] for r in records}
    assert all(r["counts"] is not None for r in records if r["kind"] in ("burst", "put"))
    assert pool.free_slots == pool.slots                   # every slot came back
