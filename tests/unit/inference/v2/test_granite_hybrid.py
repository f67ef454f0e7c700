"""Granite 4.0-H through the v2 ragged engine at the debug preset: the served
logits against the plain float32 reference, and the pieces alone.

The served path keeps the attention layers' keys and values in paged pools
and every mamba layer's state and convolution tail in a slot a sequence,
takes a step's rows through ``model_runner._mamba_mixer`` (Nemotron-H's, here
in **one group**: every head reads the same ``B`` and ``C`` row; its tests of
the packed recurrence are ``test_nemotron_h.py``'s), scales the attention
scores by ``attention_multiplier`` and takes the held picks of a softmax over
the picks through the grouped matmul; the reference
(``models/granite_hybrid.reference_logits``) runs whole sequences, the
recurrence a token at a time, every held expert on every token.

Tolerances as ``test_nemotron_h.py``: float32 engines on the CPU; ``TOL`` 2e-5.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.models import GRANITE_HYBRID_CONFIGS, build_model
from deepspeed_tpu.models.granite_hybrid import (PUBLISHED_LAYER_TYPES, GraniteHybridConfig,
                                                 layer_params, param_shapes,
                                                 reference_attention, reference_experts,
                                                 reference_logits, reference_mamba,
                                                 reference_router)
from deepspeed_tpu.models.moonlight import _rms_norm

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Refused, count, rel_err, second_engine, serve,
                                     slot_batch, two_pool_subsystems, two_prompts, two_sequences)

DEBUG = GRANITE_HYBRID_CONFIGS["granite-hybrid-debug"]
KIND = model_runner.GraniteHybridKind
LM, L = DEBUG.count("mamba"), DEBUG.num_hidden_layers

CASE = Case(
    preset="granite-hybrid-debug",
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    refused=tuple(Refused(*row) for row in (
        ("layer_types", ("mamba", "dense"), None, {"num_hidden_layers": 2}),
        ("num_hidden_layers", 7), ("attention_bias", True), ("mamba_proj_bias", True),
        ("mamba_conv_bias", False), ("tie_word_embeddings", False),
        ("position_embedding_type", "rope"), ("hidden_act", "gelu"),
        ("normalization_function", "layernorm"), ("mamba_expand", 4), ("mamba_n_groups", 3))),
    prefill=((20, 6, [20]), (75, 5, [32, 32, 11]), (100, 4, [7, 32, 32, 29]), (3, 8, [2, 1])),
    plans={"two_prompts_in_one_chunk": two_prompts()},
    burst=Burst(1, 0, 80, (8, 8), {"n_ssm_rows": 8 * LM, "n_state_slots": 8 * LM,
                                   "n_fresh_slots": 0}),
    # every expert held: a pass a layer
    records=two_sequences({"n_ssm_rows": 29 * LM, "n_state_slots": 2 * LM,
                           "n_fresh_slots": 2 * LM, "n_picks_zero": 0,
                           "n_picks_held": 29 * DEBUG.num_experts_per_tok * L,
                           "n_groups_live": (1, DEBUG.held * L), "n_share_passes": L}),
    step_counts=("n_picks_held", "n_picks_zero", "n_groups_live", "n_share_passes", "n_ssm_rows",
                 "n_state_slots", "n_fresh_slots"),
    scopes=("ds.granite.mamba", "ds.granite.attn", "ds.granite.moe", "ds.moe_routed",
            "ds.moe_shared"),
    # the prefix cache serves this kind (test_state_snapshot.py): every other subsystem refuses
    subsystems=tuple(row for row in two_pool_subsystems("expert_parallel_degree")
                     if row[0] != "prefix cache"),
    state_extra=("ssm", "conv"), state_step="pallas_ssm_state",
    slot_bytes=LM * 4 * (DEBUG.mamba_n_heads * DEBUG.mamba_d_head * DEBUG.mamba_d_state
                         + (DEBUG.mamba_d_conv - 1) * DEBUG.conv_dim),
    kernel_tests=(
        "test_a_slot_is_reused_with_its_stale_state_and_the_next_owner_starts_from_zero",))
TOL = CASE.tol


# ------------------------------------------------------------- the model file
def test_the_presets_are_the_published_layers_and_their_cut():
    whole, cut = GraniteHybridConfig(), GRANITE_HYBRID_CONFIGS["granite4-h-small-ep4-10l"]
    assert len(PUBLISHED_LAYER_TYPES) == 40
    assert [i for i, t in enumerate(PUBLISHED_LAYER_TYPES) if t == "attention"] == [5, 15, 25, 35]
    assert cut.layer_types == PUBLISHED_LAYER_TYPES[10:20] and cut.letters == "mmmmmammmm"
    assert cut.segments == (("m", 5), ("a", 1), ("m", 4))
    assert whole.segments == (("mmmmmammmm", 4),)        # the period as one scan
    assert DEBUG.segments == (("ma", 2), ("m", 2))
    assert (cut.hidden_size, cut.mamba_n_heads, cut.mamba_d_head, cut.mamba_n_groups,
            cut.mamba_d_state, cut.intermediate_size, cut.shared_intermediate_size,
            cut.num_experts_per_tok, cut.num_local_experts, cut.held, cut.vocab_size) == (
                4096, 128, 64, 1, 128, 768, 1536, 10, 72, 18, 25088)
    assert cut.conv_dim == 8448 and cut.mamba_inner == 8192 and cut.head_dim == 128
    assert cut.attention_multiplier == 1 / 128 and cut.logits_scaling == 16
    assert model_runner.kind_of(DEBUG) is KIND
    assert 32.1e9 < count(param_shapes(whole)) < 32.3e9     # the published "32B": 32.21 B
    assert count(param_shapes(cut)) == 2955758208           # ISSUE 61's count: 2.956 B
    assert "lm_head" not in param_shapes(cut)               # tied


def test_a_share_outside_the_routers_columns_is_refused():
    with pytest.raises(ValueError, match="not among"):
        dataclasses.replace(DEBUG, experts_held=6, first_expert_held=4)


def test_the_registry_builds_it_by_name_and_ds_serve_lists_it():
    import subprocess
    import sys
    assert build_model("granite4-h-small-ep4-10l").config.experts_held == 18
    out = subprocess.run([sys.executable, "bin/ds_serve", "--help"], capture_output=True, text=True)
    listed = "".join(out.stdout.split())        # argparse wraps a name at its hyphens
    assert out.returncode == 0 and "granite4-h-small-ep4-10l" in listed \
        and "granite-hybrid-debug" in listed


# --------------------------------------------------------- the pieces alone
def test_the_mamba_layer_in_one_group_is_the_recurrence(engine, state_step):
    """A prompt in chunks, then decode rows, through mamba layer 1 in a slot
    that held ones: rows, state and tail as the token-by-token recurrence."""
    cfg, layer, S = engine.model_config, 1, 70
    x = jax.random.normal(jax.random.PRNGKey(2), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["mamba_layers"])
    with jax.default_matmul_precision("highest"):
        want, state, tail = reference_mamba(lp, x[None], cfg)
    ssm = jnp.ones((LM, 3, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state))
    conv = jnp.ones((LM, 3, cfg.mamba_d_conv - 1, cfg.conv_dim))
    got, at = [], 0
    for n in (32, 30, 1, 1, 1, 1, 4):
        y, ssm, conv = KIND.mamba_layer(engine.params, cfg, layer, x[at:at + n], ssm, conv,
                                        slot_batch([(0, at, n)], 2, [2]))
        got.append(y)
        at += n
    assert rel_err(jnp.concatenate(got), want[0]) < TOL
    assert rel_err(ssm[layer, 2], state[0]) < TOL and rel_err(conv[layer, 2], tail[0]) < TOL
    assert np.asarray(ssm[layer, 1] == 1.0).all()


def test_the_attention_scores_are_scaled_by_the_multiplier_not_the_root(engine):
    """The served attention mixer alone against the reference's at
    ``attention_multiplier``; at ``1 / sqrt(d)`` the reference reads elsewhere."""
    cfg, S = engine.model_config, 24
    x = jax.random.normal(jax.random.PRNGKey(6), (S, cfg.hidden_size)) * 3
    lp = jax.tree.map(lambda w: w[1], engine.params["model"]["attn_layers"])
    kv = cfg.num_key_value_heads * cfg.head_dim
    kc = vc = jnp.zeros((2, 4, 16, kv))
    batch = {"token_seq": jnp.zeros(S, jnp.int32), "token_pos": jnp.arange(S, dtype=jnp.int32),
             "block_tables": jnp.asarray([[1, 2], [0, 0]], jnp.int32)}
    batch["live_rows"] = jnp.int32(S)
    y, _, _ = KIND.attention_layer(engine.params, cfg, 1, x, kc, vc, batch)
    with jax.default_matmul_precision("highest"):
        want = reference_attention(lp, x[None], cfg)[0]
        other = reference_attention(lp, x[None], cfg, scale=cfg.head_dim ** -0.5)[0]
    assert rel_err(y, want) < TOL and rel_err(other, want) > 1e-2


def test_the_router_is_the_softmax_over_the_picks(engine):
    cfg = engine.model_config
    x = jax.random.normal(jax.random.PRNGKey(8), (16, cfg.hidden_size))
    fp = model_runner._layer_of(engine.params["model"]["moe_layers"], 0)
    with jax.default_matmul_precision("highest"):
        picks, weights = model_runner._route(x, KIND.router(cfg, fp))
        want, margin = reference_router(fp, x, cfg)
        logits = np.asarray(x @ fp["router"]["weight"])
    picks, weights, want = np.asarray(picks), np.asarray(weights), np.asarray(want)
    for t in range(16):
        top = np.sort(np.argsort(logits[t])[-cfg.num_experts_per_tok:])
        assert sorted(picks[t]) == list(top)
        e = np.exp(logits[t, picks[t]] - logits[t, picks[t]].max())
        np.testing.assert_allclose(weights[t], e / e.sum(), rtol=1e-5)
        np.testing.assert_allclose(want[t, picks[t]], weights[t], rtol=1e-5)
    assert np.allclose(weights.sum(-1), 1.0, atol=1e-6) and float(np.min(margin)) > 0


def test_the_served_expert_layer_is_the_references_on_a_share(engine):
    cfg = dataclasses.replace(engine.model_config, experts_held=4, first_expert_held=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.hidden_size))
    moe = dict(engine.params["model"]["moe_layers"])
    moe["experts"] = jax.tree.map(lambda w: w[:, 2:6], moe["experts"])
    with jax.default_matmul_precision("highest"):
        want = reference_experts(jax.tree.map(lambda w: w[1], moe), x, cfg)
    assert rel_err(KIND.expert_layer({"model": {"moe_layers": moe}}, cfg, 1, x), want) < TOL


def test_the_four_ranks_shares_the_shared_expert_and_the_residual_add_up_to_the_uncut_layer(
        engine):
    """Every rank routes over all 8 columns and computes its own 2 experts'
    part; the shared expert is what every rank computes alike and the
    residual what every rank carries, each counted once: the stream after the
    layer's feed-forward, ``h + r * (sum of the ranks' routed parts + shared)``,
    is the reference's with all 8 held."""
    cfg, layer = engine.model_config, 2
    h = jax.random.normal(jax.random.PRNGKey(9), (40, cfg.hidden_size))
    moe = engine.params["model"]["moe_layers"]
    whole = jax.tree.map(lambda w: w[layer], moe)
    u = _rms_norm(h, whole["norm"]["scale"], cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want = h + cfg.residual_multiplier * reference_experts(whole, u, cfg)
        shared = reference_experts(whole, u, cfg) - reference_experts(whole, u, cfg, shared=False)
    routed = 0
    for rank in range(4):
        part = dataclasses.replace(cfg, experts_held=2, first_expert_held=2 * rank)
        held = dict(moe, experts=jax.tree.map(lambda w: w[:, 2 * rank:2 * rank + 2],
                                              moe["experts"]))
        served = KIND.expert_layer({"model": {"moe_layers": held}}, part, layer, u)
        routed = routed + served - shared                      # the rank's routed part alone
    assert rel_err(h + cfg.residual_multiplier * (routed + shared), want) < TOL
    # and one rank's part alone is not the layer's: the absent picks are left out
    assert rel_err(served - shared, reference_experts(whole, u, cfg, shared=False)) > 0.3


def test_the_vocabulary_is_tied_scaled_in_and_divided_out_and_a_slice_is_its_rows(engine):
    """One matrix is the embedding (times ``embedding_multiplier``) and the
    head (over ``logits_scaling``); an engine on its first 96 rows gives, for
    ids from the slice, the whole vocabulary's logits at those columns."""
    cfg, params = engine.model_config, engine.params
    assert "lm_head" not in params and cfg.tie_word_embeddings
    ids = np.random.default_rng(4).integers(0, 96, 24, dtype=np.int32)
    whole = serve(engine, [[(81, ids)]])[81][0]
    engine.flush(81)
    want = np.asarray(reference_logits(params, jnp.asarray(ids[None]), cfg))[0, -1]
    assert rel_err(whole, want) < TOL
    cut = dataclasses.replace(cfg, vocab_size=96)
    sliced = dict(params, model=dict(params["model"], embed_tokens=params["model"]["embed_tokens"][:96]))
    small = second_engine(CASE, engine, cfg=cut)
    small.params = sliced
    part = serve(small, [[(82, ids)]])[82][0]
    assert part.shape == (96,) and rel_err(part, whole[:96]) < TOL
    # neither multiplier is 1 here: without either the logits are elsewhere
    plain = dataclasses.replace(cfg, embedding_multiplier=1.0)
    assert rel_err(np.asarray(reference_logits(params, jnp.asarray(ids[None]), plain))[0, -1],
                   want) > 1e-2


class TestServing(conformance.Slots, conformance.NotKV):
    def recorded(self, engine, tokens):
        """The first feed-forward's router picks k columns for every real row."""
        cfg = engine.model_config
        assert cfg.held == cfg.num_local_experts
        first = layer_params(engine.params, cfg, 0)[1]
        assert first["router"]["weight"].shape == (cfg.hidden_size, cfg.num_local_experts)
