"""LFM2-MoE through the v2 ragged engine at the debug preset: the served
logits against the plain float32 reference, and the pieces alone.

The served path keeps the attention operators' keys and values in paged
pools and every ``conv`` operator's tail - the last two rows of the gated
stream - in a slot a sequence, reads a chunk's first rows out of the
carried tail (``model_runner._conv_with_tail``, the carry Nemotron-H's
Mamba mixers use) and runs the picks of an expert layer through a grouped
matmul over the table of every layer's experts; the reference
(``models/lfm2.reference_logits``) runs whole sequences, the convolution
as three shifted products from a zero start, every expert on every token.
They share no line.

Tolerances as ``test_nemotron_h.py``: float32 engines on the CPU, so the
two differ by the order of float32 additions (relative L2 errors of
2-6e-7 were read when this was written); ``TOL`` = 2e-5.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.models import LFM2_CONFIGS, build_model
from deepspeed_tpu.models.lfm2 import (ATTENTION, CONV, PUBLISHED_LAYER_TYPES, Lfm2MoeConfig,
                                       layer_params, param_shapes, reference_attention,
                                       reference_conv, reference_experts, reference_logits,
                                       reference_router)

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Refused, count, rel_err, serve, slot_batch,
                                     two_prompts, two_sequences)

DEBUG = LFM2_CONFIGS["lfm2-debug"]
KIND = model_runner.Lfm2Kind
LC = DEBUG.count(CONV)
PICKS = DEBUG.num_experts_per_tok * DEBUG.num_moe_layers

CASE = Case(
    preset="lfm2-debug",
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    refused=tuple(Refused(*row) for row in (
        ("layer_types", ("conv", "sliding_attention"), None, {"num_hidden_layers": 2}),
        ("conv_bias", True), ("conv_L_cache", 1), ("norm_topk_prob", False),
        ("use_expert_bias", False), ("tie_word_embeddings", False), ("num_attention_heads", 3),
        ("num_key_value_heads", 3))),
    # chunk boundaries at every offset mod 3 of the convolution (chunks of 1 and 2 rows among
    # them: shorter than the tail), then decode rows
    prefill=((20, 6, [20]), (75, 5, [32, 32, 11]), (100, 4, [7, 32, 32, 29]), (3, 8, [2, 1]),
             (40, 3, [1, 1, 1, 31, 6]), (62, 3, [30, 31, 1]), (64, 3, [32, 32])),
    cuts=tuple((19, 1, [cut, 19 - cut]) for cut in range(1, 8)),
    # the last step: two decode rows; a context counts once a sequence, at its length
    plans={"two_prompts_in_one_chunk": two_prompts({
        4: {"n_conv_rows": 2 * LC, "n_tail_slots": 2 * LC, "n_ctx_seq_tokens": 52 + 41,
            "n_picks_held": 2 * PICKS, "n_picks_zero": 0}})},
    burst=Burst(1, 0, 80, (8, 8), {"n_conv_rows": 8 * LC, "n_tail_slots": 8 * LC,
                                   "n_ctx_seq_tokens": sum(range(89, 97))}),
    records=two_sequences({"n_conv_rows": 29 * LC, "n_tail_slots": 2 * LC, "n_ctx_seq_tokens": 29,
                           "n_picks_held": 29 * PICKS, "n_picks_zero": 0}),
    step_counts=("n_picks_held", "n_picks_zero", "n_groups_live", "n_conv_rows", "n_tail_slots",
                 "n_ctx_seq_tokens"),
    scopes=("ds.lfm2.conv", "ds.lfm2.attn", "ds.dense_ffn", "ds.moe_routed"),
    # a slot is K - 1 rows of the hidden width a conv layer, in float32: what the gate counts
    state_extra=("conv",), slot_bytes=LC * 2 * DEBUG.hidden_size * 4)
TOL = CASE.tol


# ------------------------------------------------------------- the model file
def test_the_presets_are_the_published_stack_and_its_cut():
    whole, cut = Lfm2MoeConfig(), LFM2_CONFIGS["lfm2-24b-a2b-10l"]
    assert build_model("lfm2-24b-a2b").config == whole
    assert len(PUBLISHED_LAYER_TYPES) == 40
    assert [PUBLISHED_LAYER_TYPES.count(t) for t in (CONV, ATTENTION)] == [30, 10]
    assert [i for i, t in enumerate(PUBLISHED_LAYER_TYPES) if t == ATTENTION] == list(
        range(2, 40, 4))
    assert whole.letters == "CC" + "accc" * 9 + "ac"
    assert whole.segments == (("C", 1), ("C", 1), ("accc", 9), ("a", 1), ("c", 1))
    assert cut.letters == "CCacccaccc"
    assert cut.segments == (("C", 1), ("C", 1), ("accc", 2))        # two leading layers, one scan
    assert DEBUG.segments == (("C", 1), ("A", 1), ("acc", 2), ("c", 1))
    assert (whole.hidden_size, whole.head_dim, whole.num_attention_heads,
            whole.num_key_value_heads, whole.intermediate_size, whole.moe_intermediate_size,
            whole.num_experts, whole.num_experts_per_tok, whole.conv_L_cache, whole.vocab_size,
            whole.num_dense_layers, whole.max_position_embeddings) == (
                2048, 64, 32, 8, 11776, 1536, 64, 4, 3, 65536, 2, 128000)
    assert dataclasses.replace(cut, num_hidden_layers=40,
                               layer_types=PUBLISHED_LAYER_TYPES) == whole   # nothing else is cut
    assert model_runner.kind_of(DEBUG) is KIND
    assert count(param_shapes(whole)) == 23843661440   # the published "24B": 23.84 B, tied head
    assert count(param_shapes(cut)) == 5267090176   # benchmark/configs/lfm2-24b-a2b-10l.json


def test_the_shapes_are_the_catalog_rows():
    shapes = param_shapes(Lfm2MoeConfig())["model"]
    assert shapes["embed_tokens"] == (65536, 2048) and "lm_head" not in param_shapes(
        Lfm2MoeConfig())
    assert shapes["conv_layers"]["in_proj"]["kernel"] == (30, 2048, 6144)
    assert shapes["conv_layers"]["conv_kernel"] == (30, 3, 2048)
    assert shapes["attn_layers"]["q_proj"]["kernel"] == (10, 2048, 2048)
    assert shapes["attn_layers"]["k_proj"]["kernel"] == (10, 2048, 512)
    assert shapes["attn_layers"]["q_layernorm"]["scale"] == (10, 64)
    assert shapes["dense_ffn"]["gate_proj"]["kernel"] == (2, 2048, 11776)
    assert shapes["moe_ffn"]["experts"]["down_proj"] == (38, 64, 1536, 2048)
    assert shapes["moe_ffn"]["gate"]["expert_bias"] == (38, 64)


def test_the_flax_module_is_the_reference(model):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 24), dtype=np.int32))
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    got = model.apply({"params": params}, ids)
    assert got.shape == (2, 24, 256) and got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(reference_logits(params, ids, DEBUG)))
    # causal: a sequence's later tokens do not reach its earlier logits
    again = model.apply({"params": params}, ids.at[:, 20:].set(0))
    assert rel_err(again[:, :20], got[:, :20]) < 1e-6
    bias = params["model"]["moe_ffn"]["gate"]["expert_bias"]
    assert float(jnp.std(bias)) > 0.05                  # seeded, not zero


# ------------------------------------------------------------ the counts
def test_a_chunk_counts_its_context_once_a_sequence(engine, tokens):
    seq = tokens[2][:50]
    engine.prefix_match(21, seq)
    serve(engine, [[(21, seq[:30])], [(21, seq[30:50])]])
    counts = engine.last_step.counts
    engine.flush(21)
    assert counts["n_ctx_seq_tokens"] == 50               # not 20 rows x their contexts
    assert counts["n_conv_rows"] == 20 * DEBUG.count(CONV)
    assert counts["n_tail_slots"] == DEBUG.count(CONV)


# --------------------------------------------------------- the pieces alone
def _pool(cfg, slots, fill):
    return jnp.full((cfg.count(CONV), slots + 1, cfg.conv_L_cache - 1, cfg.hidden_size), fill,
                    jnp.float32)


@pytest.mark.parametrize("chunk", [1, 2, 3, 7, 64, 150])
def test_a_prompt_in_chunks_leaves_the_references_last_two_gated_rows(engine, chunk):
    """A prompt of 150 rows through ``conv`` layer 1 in chunks of 1, 2, 3, 7,
    64 rows and whole, in a slot that held ones: the same output rows and
    the same tail - the last two rows of ``B * x`` - as the reference's
    shifted products from zero."""
    cfg, layer, S = engine.model_config, 1, 150
    x = jax.random.normal(jax.random.PRNGKey(2), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["conv_layers"])
    with jax.default_matmul_precision("highest"):
        want, tail = reference_conv(lp, x[None], cfg)
        bcx = x @ lp["in_proj"]["kernel"]
    D = cfg.hidden_size
    assert rel_err(tail[0], (bcx[:, :D] * bcx[:, 2 * D:])[-2:]) < 1e-6
    conv = _pool(cfg, 2, 1.0)
    got = []
    for at in range(0, S, chunk):
        n = min(chunk, S - at)
        y, conv = KIND.conv_layer(engine.params, cfg, layer, x[at:at + n], conv,
                                  slot_batch([(0, at, n)], 2, [2]))
        got.append(y)
    assert rel_err(jnp.concatenate(got), want[0]) < TOL
    assert rel_err(conv[layer, 2], tail[0]) < TOL
    assert np.asarray(conv[layer, 1] == 1.0).all() and np.asarray(conv[0] == 1.0).all()


def test_a_dropped_tail_is_seen(engine):
    """The control of the check above: the tail zeroed at a chunk boundary
    moves the chunk's first rows."""
    cfg, layer = engine.model_config, 0
    x = jax.random.normal(jax.random.PRNGKey(3), (12, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["conv_layers"])
    with jax.default_matmul_precision("highest"):
        want, _ = reference_conv(lp, x[None], cfg)
    conv = _pool(cfg, 2, 0.0)
    _, conv = KIND.conv_layer(engine.params, cfg, layer, x[:6], conv,
                              slot_batch([(0, 0, 6)], 2, [2]))
    y, _ = KIND.conv_layer(engine.params, cfg, layer, x[6:], jnp.zeros_like(conv),
                           slot_batch([(0, 6, 6)], 2, [2]))
    assert rel_err(y[:2], want[0, 6:8]) > 0.05 and rel_err(y[2:], want[0, 8:]) < TOL


def test_decode_rows_beside_chunks_in_one_step_each_from_its_own_tail(engine):
    cfg, layer = engine.model_config, 2
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["conv_layers"])
    key = jax.random.PRNGKey(4)
    before = [30, 12, 0, 9, 5, 1]                # rows each sequence has behind it
    now = [1, 1, 6, 2, 9, 1]
    slots = [3, 1, 6, 2, 5, 4]
    xs = [jax.random.normal(jax.random.fold_in(key, i), (b + n, cfg.hidden_size))
          for i, (b, n) in enumerate(zip(before, now))]
    conv = _pool(cfg, 7, 0.5)
    want = []
    with jax.default_matmul_precision("highest"):
        for i, (b, n) in enumerate(zip(before, now)):
            tail = None
            if b:
                _, tail = reference_conv(lp, xs[i][None, :b], cfg)
                conv = conv.at[layer, slots[i]].set(tail[0])
            want.append(reference_conv(lp, xs[i][None, b:], cfg, tail))
    batch = slot_batch([(i, b, n) for i, (b, n) in enumerate(zip(before, now))] + [(7, 0, 1)] * 3,
                   8, slots)
    x = jnp.concatenate([xs[i][b:] for i, b in enumerate(before)]
                        + [jnp.ones((3, cfg.hidden_size))])
    held = np.asarray(conv)
    y, conv = KIND.conv_layer(engine.params, cfg, layer, x, conv, batch)
    at = 0
    for i, n in enumerate(now):
        out, tail = want[i]
        assert rel_err(y[at:at + n], out[0]) < TOL, i
        assert rel_err(conv[layer, slots[i]], tail[0]) < TOL, i
        at += n
    assert np.array_equal(np.asarray(conv[layer, 7]), held[layer, 7])     # a slot no row names
    assert np.array_equal(np.asarray(conv[0]), held[0])


def test_the_served_attention_operator_is_the_references(engine):
    cfg, layer, S = engine.model_config, 1, 40
    x = jax.random.normal(jax.random.PRNGKey(6), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["attn_layers"])
    with jax.default_matmul_precision("highest"):
        want = reference_attention(lp, x[None], cfg)[0]
    assert engine.kv_cache.k.shape[0] == cfg.count(ATTENTION)       # no pool layer for a conv one
    shape = (cfg.count(ATTENTION), 8, CASE.block, cfg.num_key_value_heads * cfg.head_dim)
    kc, vc = jnp.zeros(shape), jnp.zeros(shape)
    got = []
    for at, n in ((0, 25), (25, 15)):
        batch = slot_batch([(0, at, n)], 2, [1])
        batch["block_tables"] = jnp.asarray([[1, 2, 3], [0, 0, 0]], jnp.int32)
        y, kc, vc = KIND.attention_layer(engine.params, cfg, layer, x[at:at + n], kc, vc, batch)
        got.append(y)
    assert rel_err(jnp.concatenate(got), want) < TOL


def test_the_served_expert_layer_is_the_references(engine):
    cfg, layer = engine.model_config, 3
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = reference_experts(
            jax.tree.map(lambda w: w[layer], engine.params["model"]["moe_ffn"]), x, cfg)
    assert rel_err(KIND.expert_layer(engine.params, cfg, layer, x), want) < TOL


def test_the_bias_moves_the_choice_and_not_the_weights(engine):
    """Chosen by ``s + expert_bias``, weighted by ``s``: a bias that lifts
    three columns over every other makes them every token's picks, with the
    weights their unbiased scores over their sum; and the seeded bias
    already changes picks that the scores alone would make."""
    cfg, layer = engine.model_config, 0
    x = jax.random.normal(jax.random.PRNGKey(8), (64, cfg.hidden_size))
    p = jax.tree.map(lambda w: w[layer], engine.params["model"]["moe_ffn"])
    with jax.default_matmul_precision("highest"):
        weights, margin = reference_router(p, x, cfg)
        s = jax.nn.sigmoid(x @ p["gate"]["weight"])
        unbiased = jax.lax.top_k(s, cfg.num_experts_per_tok)[1]
        lifted = {"gate": {"weight": p["gate"]["weight"],
                           "expert_bias": jnp.zeros(8).at[jnp.asarray([1, 4, 6])].set(10.0)}}
        forced, _ = reference_router(lifted, x, cfg)
    picks = np.asarray(weights > 0)
    assert (picks.sum(-1) == cfg.num_experts_per_tok).all() and float(margin.min()) > 0
    by_score = np.zeros_like(picks)
    np.put_along_axis(by_score, np.asarray(unbiased), True, axis=-1)
    assert (picks != by_score).any()                      # the seeded bias is not vacuous
    assert np.array_equal(np.asarray(forced > 0), np.tile(np.isin(np.arange(8), [1, 4, 6]),
                                                          (64, 1)))
    want = np.asarray(s)[:, [1, 4, 6]]
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(forced)[:, [1, 4, 6]], want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-4)
    # and the served layer makes the same choice: the forced bias through the engine's function
    moe = dict(engine.params["model"]["moe_ffn"])
    moe["gate"] = {"weight": moe["gate"]["weight"],
                   "expert_bias": jnp.tile(lifted["gate"]["expert_bias"], (cfg.num_moe_layers, 1))}
    with jax.default_matmul_precision("highest"):
        want = reference_experts(jax.tree.map(lambda w: w[layer], moe), x, cfg)
    assert rel_err(KIND.expert_layer({"model": {"moe_ffn": moe}}, cfg, layer, x), want) < TOL


def test_layer_params_cuts_each_layers_operator_and_feed_forward(engine):
    cfg, params = engine.model_config, engine.params
    op, ffn = layer_params(params, cfg, 0)
    assert "conv_kernel" in op and "gate_proj" in ffn
    op, ffn = layer_params(params, cfg, 1)
    assert "q_layernorm" in op and "gate_proj" in ffn
    op, ffn = layer_params(params, cfg, 5)                # letters CAaccaccc: the second 'a'
    assert "q_layernorm" in op and "gate" in ffn
    assert np.array_equal(np.asarray(op["q_proj"]["kernel"]),
                          np.asarray(params["model"]["attn_layers"]["q_proj"]["kernel"][2]))
    assert np.array_equal(np.asarray(ffn["gate"]["expert_bias"]),
                          np.asarray(params["model"]["moe_ffn"]["gate"]["expert_bias"][3]))


class TestServing(conformance.ChunkCuts, conformance.Slots, conformance.NotKV):
    pass
