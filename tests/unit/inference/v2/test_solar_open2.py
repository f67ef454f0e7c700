"""Solar Open 2 through the v2 ragged engine at the debug preset: the served
logits against the plain float32 reference, and the pieces alone.

The served path keeps the attention layers' keys and values in paged pools
and every KDA layer's state - a ``d x d`` matrix a head, float32 - and
convolution tails in a slot a sequence; a step's rows go through
``ops/pallas/kda`` (the kernel, interpreted, under ``DS_PALLAS=1``; its XLA
fallback otherwise), each sequence's run from its own slot; the routed
experts are one share behind the whole router. The reference
(``models/solar_open2.reference_logits``) runs whole sequences, the
convolutions as shifted products and the delta rule a token at a time from a
zero start, every held expert on every token. They share no line.

Tolerances: float32 engines on the CPU differ from the reference by the
order of float32 additions (relative L2 errors of 2-4e-6 were read when this
was written); ``TOL`` = 2e-5. The bfloat16 engine's is written where it is
used.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, model_runner
from deepspeed_tpu.models import SOLAR_OPEN2_CONFIGS, build_model
from deepspeed_tpu.models.solar_open2 import (SolarOpen2Config, layer_params, param_shapes,
                                              reference_attention, reference_kda,
                                              reference_logits, reference_moe)

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Refused, count, engine_config, rel_err, serve,
                                     slot_batch, two_prompts, two_sequences)

DEBUG = SOLAR_OPEN2_CONFIGS["solar-open2-debug"]
KIND = model_runner.SolarOpen2Kind
LK = DEBUG.count("k")
# around the convolutions' four taps and the kernel's blocks of 8
CUTS = (1, 2, 3, 4, 5, 8, 9, 16, 17)

CASE = Case(
    preset="solar-open2-debug",
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    refused=tuple(Refused(*row) for row in (
        ("kda_use_full_proj", True), ("use_rope", True), ("use_gqa_gate", False),
        ("kda_allow_neg_eigval", False), ("first_k_dense_replace", 1), ("n_shared_experts", 2),
        ("norm_topk_prob", False), ("tie_word_embeddings", True), ("gqa_layers", (0, 9)),
        ("num_key_value_heads", 3),
        ("linear_attn_config", {"head_dim": 16, "num_heads": 4, "num_kv_heads": 2,
                                "short_conv_kernel_size": 4}, "num_kv_heads"))),
    # whole and in chunks (of 1 and 2 rows among them: shorter than the convolutions' tail),
    # then decode rows - 64 of them in the last case, so that an error of the state would compound
    prefill=((20, 6, [20]), (75, 12, [32, 32, 11]), (100, 4, [7, 32, 32, 29]), (3, 8, [2, 1]),
             (40, 3, [1, 1, 1, 31, 6]), (64, 64, [32, 32])),
    cuts=tuple((19, 1, [cut, 19 - cut]) for cut in CUTS),
    # three runs of the delta rule in the third step, each from its own slot; a batch of 32 rows
    # is no whole block; 32 tokens x 4 picks x 8 layers, all 16 experts held: every pick a row
    # of some group
    plans={"two_prompts_in_one_chunk": two_prompts({
        2: {"n_kda_rows": 32 * LK, "n_state_slots": 3 * LK, "n_scan_runs": 2 * LK,
            "n_kda_chunk_rows": 0, "n_picks_held": 32 * 4 * 8, "n_picks_zero": 0},
        4: {"n_scan_runs": 0, "n_state_slots": 2 * LK}})},
    burst=Burst(1, 0, 80, (8, 8),
                {"n_kda_rows": 8 * LK, "n_state_slots": 8 * LK, "n_scan_runs": 0}),
    # every expert held: each layer one pass
    records=two_sequences({"n_kda_rows": 29 * LK, "n_state_slots": 2 * LK, "n_scan_runs": 2 * LK,
                           "n_kda_chunk_rows": 0, "n_picks_held": 29 * 4 * 8,
                           "n_groups_live": (1, 16 * 8), "n_share_passes": 8}),
    step_counts=("n_picks_held", "n_picks_zero", "n_groups_live", "n_share_passes", "n_kda_rows",
                 "n_state_slots", "n_scan_runs", "n_kda_chunk_rows"),
    scopes=("ds.solar.kda", "ds.solar.kda_state", "ds.solar.attn", "ds.moe_routed",
            "ds.moe_shared"),
    # a slot: a layer's state a head, float32, and three tails of K - 1 rows side by side
    state_extra=("kda", "conv"), state_step="pallas_kda",
    slot_bytes=LK * 4 * (DEBUG.kda_heads * DEBUG.kda_head_dim ** 2
                         + (DEBUG.kda_conv - 1) * 3 * DEBUG.kda_inner),
    kernel_tests=("test_a_chunk_cut_at_every_offset", "test_sequences_side_by_side_in_a_step",
                  "test_a_slot_is_reused_with_its_stale_state_and_the_next_owner_starts_from_zero"))
TOL = CASE.tol


# ------------------------------------------------------------- the model file
def test_the_presets_are_the_published_stack_its_share_and_a_small_one_of_its_pattern():
    whole = SolarOpen2Config()
    assert build_model("solar-open2-250b").config == whole
    assert whole.letters == "gkkk" * 12 and whole.segments == (("gkkk", 12),)
    assert [i for i, t in enumerate(whole.letters) if t == "g"] == list(range(0, 48, 4))
    assert (whole.hidden_size, whole.head_dim, whole.num_attention_heads,
            whole.num_key_value_heads, whole.kda_heads, whole.kda_head_dim, whole.kda_conv,
            whole.n_routed_experts, whole.num_experts_per_tok, whole.moe_intermediate_size,
            whole.vocab_size, whole.max_position_embeddings, whole.rms_norm_eps) == (
                4096, 128, 64, 8, 64, 128, 4, 320, 8, 1280, 196608, 1048576, 1e-5)
    # ISSUE 48's count: 36 x 154.7 M + 12 x 126.1 M + 48 x 5.033 B + 2 x 196,608 x 4096
    assert count(param_shapes(whole)) == 250288105216
    # of which a token reads 8 of 320 experts a layer: the "250B-A15B" of the model's name
    assert round((count(param_shapes(whole)) - 48 * 312 * 3 * 4096 * 1280) / 1e9, 1) == 14.7
    share = SOLAR_OPEN2_CONFIGS["solar-open2-ep8-4l"]
    assert share.letters == "gkkk" and share.segments == (("g", 1), ("k", 3))
    assert (share.held, share.first_expert_held, share.n_routed_experts) == (40, 0, 320)
    assert round(count(param_shapes(share)) / 1e9, 2) == 3.31   # 6.62 GB in bfloat16
    assert DEBUG.letters == "gkkkgkkk" and DEBUG.segments == (("gkkk", 2),)   # two whole periods
    assert DEBUG.num_attention_heads // DEBUG.num_key_value_heads == 2
    assert DEBUG.num_key_value_heads > 1 and DEBUG.kda_conv == 4
    assert (DEBUG.n_routed_experts, DEBUG.num_experts_per_tok, DEBUG.n_shared_experts) == (16, 4, 1)
    assert model_runner.kind_of(DEBUG) is KIND


def test_the_shapes_are_the_catalog_rows():
    every = param_shapes(SolarOpen2Config())
    shapes = every["model"]
    assert shapes["embed_tokens"] == (196608, 4096) and every["lm_head"]["kernel"] == (4096, 196608)
    kda, gqa, moe = shapes["kda_layers"], shapes["gqa_layers"], shapes["moe"]
    assert kda["qkv_proj"]["kernel"] == (36, 4096, 3 * 8192)
    assert kda["conv_kernel"] == (36, 4, 3 * 8192)                     # three convolutions of 4
    assert kda["f_a_proj"]["kernel"] == (36, 4096, 128)                # the two-factor form
    assert kda["f_b_proj"]["kernel"] == (36, 128, 8192)
    assert kda["g_a_proj"]["kernel"] == (36, 4096, 128)
    assert kda["g_b_proj"] == {"kernel": (36, 128, 8192), "bias": (36, 8192)}
    assert kda["b_proj"]["kernel"] == (36, 4096, 64) and kda["A_log"] == (36, 64)
    assert kda["dt_bias"] == (36, 8192) and kda["o_norm"]["scale"] == (36, 128)
    assert gqa["q_proj"]["kernel"] == gqa["gate_proj"]["kernel"] == (12, 4096, 8192)
    assert gqa["k_proj"]["kernel"] == (12, 4096, 1024)
    assert moe["gate"]["weight"] == (48, 4096, 320)
    assert moe["experts"]["gate_proj"] == (48, 320, 4096, 1280)
    assert moe["shared_experts"]["down_proj"]["kernel"] == (48, 1280, 4096)
    held = param_shapes(SOLAR_OPEN2_CONFIGS["solar-open2-ep8-4l"])["model"]["moe"]
    assert held["experts"]["up_proj"] == (4, 40, 4096, 1280)           # the share's 40
    assert held["gate"]["weight"] == (4, 4096, 320)                    # behind the whole router


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="not among the 16 routed"):
        dataclasses.replace(DEBUG, experts_held=8, first_expert_held=12)
    with pytest.raises(ValueError, match="exceeds the router"):
        dataclasses.replace(DEBUG, num_experts_per_tok=17)


def test_the_flax_module_is_the_reference_and_the_seeded_recurrence_lives(model):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 24), dtype=np.int32))
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    got = model.apply({"params": params}, ids)
    assert got.shape == (2, 24, 256) and got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(reference_logits(params, ids, DEBUG)))
    # causal: a sequence's later tokens do not reach its earlier logits
    again = model.apply({"params": params}, ids.at[:, 20:].set(0))
    assert rel_err(again[:, :20], got[:, :20]) < 1e-6
    kda = params["model"]["kda_layers"]
    # A_log = log U(1, 16) a head; the step log-uniform in [1e-3, 1e-1] a channel
    rate = np.exp(np.asarray(kda["A_log"]))
    assert 1.0 <= rate.min() < 3.0 and 12.0 < rate.max() <= 16.0
    step = np.asarray(jax.nn.softplus(kda["dt_bias"]))
    assert 9e-4 < step.min() < 2e-3 and 0.05 < step.max() < 0.11
    assert not np.asarray(params["model"]["moe"]["gate"]["e_score_correction_bias"]).any()
    # neither dead nor exploding: a state after 150 tokens is of its increments' order
    lp = jax.tree.map(lambda w: w[0], kda)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 150, DEBUG.hidden_size))
    _, state, _ = reference_kda(lp, x, DEBUG)
    assert 1e-3 < float(jnp.abs(state).max()) < 1e2 and bool(jnp.isfinite(state).all())


# ------------------------------------------------- the delta rule's block form
@pytest.fixture(scope="module")
def block_engine(model):
    """A batch of one whole block of the delta rule's block form (64 rows),
    first run under ``DS_PALLAS=1``: a run of ``kda.MIN_CHUNK_RUN`` rows or
    more goes through the chunked form, interpreted."""
    return InferenceEngineV2(model=model, config=engine_config(CASE, rows=64), dtype=jnp.float32,
                             rng=jax.random.PRNGKey(CASE.rng))


def test_a_prompt_chunk_goes_through_the_block_form_beside_decode_rows(
        block_engine, reference, tokens, monkeypatch):
    """A step with a prompt chunk and decode rows counts the chunk's rows
    through the block form, a decode-only step none; a prompt cut into two
    chunks (both block-form runs, the second from the carried state) serves
    the logits of the uncut run."""
    from deepspeed_tpu.ops.pallas import kda
    monkeypatch.setenv("DS_PALLAS", "1")
    engine = block_engine
    a, b, n = tokens[0][:90], tokens[1][:12], kda.MIN_CHUNK_RUN
    assert kda.CHUNK == 64 and n + 6 <= 40
    engine.prefix_match(1, a[:89])
    engine.prefix_match(2, b[:11])
    rows = serve(engine, [[(2, b[:10]), (1, a[:40])]])
    counts = engine.last_step.counts
    assert engine.last_step.state_step == "pallas_kda" and tuple(counts) == CASE.step_counts
    assert (counts["n_kda_rows"], counts["n_scan_runs"], counts["n_kda_chunk_rows"]) == (
        50 * LK, 2 * LK, 40 * LK)
    more = serve(engine, [[(2, b[10:11]), (1, a[40:89])], [(2, b[11:12]), (1, a[89:90])]])
    counts = engine.last_step.counts                # two decode rows
    assert (counts["n_kda_rows"], counts["n_state_slots"], counts["n_kda_chunk_rows"]) == (
        2 * LK, 2 * LK, 0)
    engine.flush(1)
    engine.flush(2)
    wa, wb = reference(a), reference(b)
    got = [(rows[1][0], wa[39]), (more[1][0], wa[88]), (more[1][1], wa[89]),
           (rows[2][0], wb[9]), (more[2][0], wb[10]), (more[2][1], wb[11])]
    assert max(rel_err(g, w) for g, w in got) < TOL
    # the same prompt cut elsewhere: a whole block's 64 rows, then 25 from the carried state
    engine.prefix_match(3, a[:89])
    whole = serve(engine, [[(3, a[:64])], [(3, a[64:89])], [(3, a[89:90])]])[3]
    assert engine.last_step.counts["n_kda_chunk_rows"] == 0
    engine.flush(3)
    assert rel_err(whole[1], more[1][0]) < TOL and rel_err(whole[2], more[1][1]) < TOL


def test_a_bursts_step_says_that_it_holds_one_row_a_sequence():
    """``decode_burst``'s steps name ``query_tiles: None`` (one row a sequence
    by construction), which the delta rule's call reads as it does the paged
    kernel's: such a program holds no block form."""
    batch = {"token_seq": jnp.zeros((4,), jnp.int32), "token_pos": jnp.zeros((4,), jnp.int32),
             "block_tables": jnp.zeros((3, 2), jnp.int32),
             "seq_state": jnp.zeros((3, 1), jnp.int32)}
    assert not model_runner._SlotStep(DEBUG, batch, 4).one_row_runs
    assert not model_runner._SlotStep(DEBUG, dict(batch, query_tiles=()), 4).one_row_runs
    assert model_runner._SlotStep(DEBUG, dict(batch, query_tiles=None), 4).one_row_runs


def test_a_bfloat16_engine_keeps_its_state_in_float32_and_reads_close(model, engine, tokens):
    """The served types: bfloat16 weights, stream, keys, values and tails, a
    float32 state. Against the float32 reference on the same (bfloat16)
    weights the logits differ by bfloat16's rounding of the stream, 2**-8 a
    value, gathered over eight layers of two sublayers at a hidden size of
    64, and - where a router's margin is under that rounding - by a pick
    (all 16 experts are held here and 4 are picked, so a flipped pick is a
    quarter of a layer's routed part): median 0.033, largest 0.091 was read
    over the 66 positions. The limits leave twice that and say what a
    fault of the mechanism's size reads, not more: the pieces are held to
    ``TOL`` alone, above and below, and their controls with them."""
    served = InferenceEngineV2(model=model, config=engine_config(CASE), dtype=jnp.bfloat16,
                               rng=jax.random.PRNGKey(CASE.rng))
    assert engine.state_extra["kda"].dtype == served.state_extra["kda"].dtype == jnp.float32
    assert engine.state_extra["kda"].shape == (LK, 5, 4, 16, 16)
    assert engine.state_extra["conv"].shape == (LK, 5, 3, 3 * 64)       # three tails side by side
    assert engine.kv_cache.k.shape[0] == DEBUG.count("g")
    assert served.state_extra["conv"].dtype == jnp.bfloat16
    seq = tokens[2][:124]
    rows = serve(served, [[(5, seq[:32])], [(5, seq[32:60])]]
                 + [[(5, seq[60 + j:61 + j])] for j in range(64)])[5]
    served.flush(5)
    want = np.asarray(reference_logits(served.params, jnp.asarray(seq)[None], DEBUG))[0]
    errs = [rel_err(r, want[p]) for r, p in zip(rows, [31, 59] + list(range(60, 124)))]
    assert np.median(errs) < 0.07 and max(errs) < 0.2, (np.median(errs), max(errs))


# --------------------------------------------------------- the pieces alone
_MIXERS = {}


def kda_layer(state_step, params, layer, x, kda, conv, batch):
    """``SolarOpen2Kind.kda_layer`` at the debug preset, jitted - a program a
    shape for each of ``state_step``'s cases, which is read when a program is
    traced."""
    if state_step not in _MIXERS:
        _MIXERS[state_step] = jax.jit(
            lambda params, layer, x, kda, conv, batch: KIND.kda_layer(
                params, DEBUG, layer, x, kda, conv, batch))
    return _MIXERS[state_step](params, jnp.int32(layer), x, kda, conv, batch)


def _pools(cfg, slots, fill):
    H, d = cfg.kda_heads, cfg.kda_head_dim
    return (jnp.full((LK, slots + 1, H, d, d), fill, jnp.float32),
            jnp.full((LK, slots + 1, cfg.kda_conv - 1, 3 * cfg.kda_inner), fill, jnp.float32))


@pytest.mark.parametrize("chunk", [1, 7, 64, 100])
def test_a_prompt_in_chunks_leaves_the_state_and_the_tail_of_the_recurrence(engine, chunk,
                                                                            state_step):
    """A prompt of 100 rows through KDA layer 3 one row a call, in chunks of
    7 and 64 rows and whole, in a slot that held ones: the reference's output
    rows, state and tails."""
    cfg, layer, S = engine.model_config, 3, 100
    x = jax.random.normal(jax.random.PRNGKey(2), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["kda_layers"])
    with jax.default_matmul_precision("highest"):
        want, state, tail = reference_kda(lp, x[None], cfg)
    kda, conv = _pools(cfg, 2, 1.0)
    got = []
    for at in range(0, S, chunk):
        n = min(chunk, S - at)
        y, kda, conv = kda_layer(state_step, engine.params, layer, x[at:at + n], kda, conv,
                                 slot_batch([(0, at, n)], 2, [2]))
        got.append(y)
    assert rel_err(jnp.concatenate(got), want[0]) < TOL
    assert rel_err(kda[layer, 2], state[0]) < TOL and rel_err(conv[layer, 2], tail[0]) < TOL
    assert np.asarray(kda[layer, 1] == 1.0).all() and np.asarray(kda[0] == 1.0).all()


def test_several_runs_in_one_step_each_from_its_own_slot(engine, state_step):
    """Decode rows, runs that go on from a carried slot and runs that start,
    side by side in one call, then padding's rows: every run the
    reference's continuation of its own sequence, the slot of a sequence
    that starts ignored (it held 0.5), a slot no row names untouched."""
    cfg, layer = engine.model_config, 4
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["kda_layers"])
    key = jax.random.PRNGKey(4)
    before = [30, 12, 0, 9, 0, 1]                # rows each sequence has behind it
    now = [1, 1, 6, 2, 9, 1]
    slots = [3, 1, 6, 2, 5, 4]
    xs = [jax.random.normal(jax.random.fold_in(key, i), (b + n, cfg.hidden_size))
          for i, (b, n) in enumerate(zip(before, now))]
    kda, conv = _pools(cfg, 7, 0.5)
    want = []
    with jax.default_matmul_precision("highest"):
        for i, (b, n) in enumerate(zip(before, now)):
            state = tail = None
            if b:
                _, state, tail = reference_kda(lp, xs[i][None, :b], cfg)
                kda = kda.at[layer, slots[i]].set(state[0])
                conv = conv.at[layer, slots[i]].set(tail[0])
            want.append(reference_kda(lp, xs[i][None, b:], cfg, state, tail))
    batch = slot_batch([(i, b, n) for i, (b, n) in enumerate(zip(before, now))] + [(7, 0, 1)] * 4,
                   8, slots)
    x = jnp.concatenate([xs[i][b:] for i, b in enumerate(before)]
                        + [jnp.ones((4, cfg.hidden_size))])
    held = np.asarray(kda)
    y, kda, conv = kda_layer(state_step, engine.params, layer, x, kda, conv, batch)
    at = 0
    for i, n in enumerate(now):
        out, state, tail = want[i]
        assert rel_err(y[at:at + n], out[0]) < TOL, i
        assert rel_err(kda[layer, slots[i]], state[0]) < TOL, i
        assert rel_err(conv[layer, slots[i]], tail[0]) < TOL, i
        at += n
    assert np.array_equal(np.asarray(kda[layer, 7]), held[layer, 7])      # a slot no row names
    assert np.array_equal(np.asarray(kda[layer, 0]), held[layer, 0])      # padding's
    assert np.array_equal(np.asarray(kda[0]), held[0])


def test_a_state_carried_in_bfloat16_and_a_clipped_beta_are_seen(engine):
    """The controls of the checks above: the state rounded to bfloat16 after
    every decode row drifts from the float32 recurrence by far more than
    ``TOL``; and a ``beta`` held to (0, 1) - what forgetting
    ``kda_allow_neg_eigval`` does - moves the rows themselves."""
    cfg, layer, S = engine.model_config, 1, 80
    x = jax.random.normal(jax.random.PRNGKey(7), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["kda_layers"])
    with jax.default_matmul_precision("highest"):
        want, state, _ = reference_kda(lp, x[None], cfg)
    kda, conv = _pools(cfg, 2, 0.0)
    for at in range(S):
        _, kda, conv = kda_layer("xla", engine.params, layer, x[at:at + 1], kda, conv,
                                 slot_batch([(0, at, 1)], 2, [2]))
        kda = kda.astype(jnp.bfloat16).astype(jnp.float32)
    assert rel_err(kda[layer, 2], state[0]) > 50 * TOL
    # beta = 2 sigmoid(.): halving b_proj's output range is sigmoid alone
    from deepspeed_tpu.models import solar_open2

    original = solar_open2.delta_rule
    solar_open2.delta_rule = lambda q, k, v, g, beta, s: original(q, k, v, g, beta / 2.0, s)
    try:
        with jax.default_matmul_precision("highest"):
            wrong = reference_kda(lp, x[None], cfg)[0]
    finally:
        solar_open2.delta_rule = original
    assert rel_err(wrong, want) > 0.05


def test_the_served_attention_layer_is_the_references_and_its_gate_is_seen(engine):
    """Two key-value heads under two query heads each, no positional term,
    the sigmoid gate on the output, over a chunk cut; the reference without
    the gate - the control - reads far off."""
    cfg, layer, S = engine.model_config, 1, 40
    x = jax.random.normal(jax.random.PRNGKey(6), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["gqa_layers"])
    with jax.default_matmul_precision("highest"):
        want = reference_attention(lp, x[None], cfg)[0]
        gateless = reference_attention(lp, x[None], cfg, gated=False)[0]
    shape = (cfg.count("g"), 8, CASE.block, cfg.num_key_value_heads * cfg.head_dim)
    kc, vc = jnp.zeros(shape), jnp.zeros(shape)
    got = []
    for at, n in ((0, 25), (25, 15)):
        batch = slot_batch([(0, at, n)], 2, [1])
        batch["block_tables"] = jnp.asarray([[1, 2, 3], [0, 0, 0]], jnp.int32)
        y, kc, vc = KIND.attention_layer(engine.params, cfg, layer, x[at:at + n], kc, vc, batch)
        got.append(y)
    assert rel_err(jnp.concatenate(got), want) < TOL
    assert rel_err(gateless, want) > 0.3


def test_the_served_expert_layer_is_the_references(engine):
    cfg, layer = engine.model_config, 5
    x = jax.random.normal(jax.random.PRNGKey(8), (24, cfg.hidden_size))
    fp = jax.tree.map(lambda w: w[layer], engine.params["model"]["moe"])
    with jax.default_matmul_precision("highest"):
        want = reference_moe(fp, x, cfg)
    got = KIND.expert_layer(engine.params, cfg, jnp.int32(layer), x)
    assert rel_err(got, want) < TOL


def test_the_eight_shares_add_up_to_the_uncut_layer(engine):
    """The guide's section 4: the routed parts that the 8 shares give
    (experts 0-1, 2-3, ... of 16, each behind the whole router), with the
    shared expert counted once, sum to the uncut reference's layer - by the
    reference's shares and by the served layer's."""
    cfg, layer = engine.model_config, 2
    x = jax.random.normal(jax.random.PRNGKey(9), (20, cfg.hidden_size))
    fp = jax.tree.map(lambda w: w[layer], engine.params["model"]["moe"])
    with jax.default_matmul_precision("highest"):
        whole = reference_moe(fp, x, cfg)
        shared = reference_moe(fp, x, cfg, share=(0, 0))
    summed, served = shared, shared
    for first in range(0, 16, 2):
        part = dataclasses.replace(cfg, experts_held=2, first_expert_held=first)
        held = {**fp, "experts": jax.tree.map(lambda w: w[first:first + 2], fp["experts"])}
        with jax.default_matmul_precision("highest"):
            summed = summed + reference_moe(held, x, part, shared=False)
        params = {"model": {"moe": jax.tree.map(
            lambda w: w[None], {**held, "experts": held["experts"]})}}
        served = served + KIND.expert_layer(params, part, jnp.int32(0), x) - shared
    assert rel_err(summed, whole) < TOL
    assert rel_err(served, whole) < TOL


def test_layer_params_cuts_each_layers_mixer_and_feed_forward(engine):
    cfg, params = engine.model_config, engine.params
    mixer, moe = layer_params(params, cfg, 1)
    assert "A_log" in mixer and "gate" in moe
    mixer, moe = layer_params(params, cfg, 4)                # letters gkkkgkkk: the second 'g'
    assert np.array_equal(np.asarray(mixer["gate_proj"]["kernel"]),
                          np.asarray(params["model"]["gqa_layers"]["gate_proj"]["kernel"][1]))
    assert np.array_equal(np.asarray(moe["gate"]["weight"]),
                          np.asarray(params["model"]["moe"]["gate"]["weight"][4]))
    mixer, _ = layer_params(params, cfg, 6)                  # the fifth KDA layer
    assert np.array_equal(np.asarray(mixer["dt_bias"]),
                          np.asarray(params["model"]["kda_layers"]["dt_bias"][4]))


class TestServing(conformance.ChunkCuts, conformance.Slots, conformance.NotKV):
    pass
