"""Laguna through the v2 ragged engine at the debug preset: the served logits
against the plain float32 reference, and the pieces alone.

The served path keeps the full layers' keys and values in the engine's paged
pools and the window layers' in the window pool - blocks a sequence gives
back as they fall behind its window of 8, a ring of a table - and reads both
through the paged attention call (the XLA gather here; the kernel,
interpreted, in ``tests/unit/ops/test_paged_attention_window.py``); the routed
experts are one share behind the whole router. The reference
(``models/laguna.reference_logits``) runs whole sequences under a ``[S, S]``
mask a layer kind, every held expert on every token. They share no line.

Tolerances: float32 engines on the CPU differ from the reference by the
order of float32 additions (relative L2 errors of 2-6e-7 were read when this
was written); ``TOL`` = 2e-5. A bfloat16 engine (bfloat16 pools of both kinds)
is the benchmark's rehearsal, ``benchmark/tests/test_laguna_cell.py``: not
here, where every second counts against the tier-1 run's limit.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.models import LAGUNA_CONFIGS
from deepspeed_tpu.models import laguna
from deepspeed_tpu.models.laguna import (FULL, WINDOW, layer_params, param_shapes,
                                         reference_attention, reference_logits, reference_moe)

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import Burst, Case, Gateway, Plan, Refused, count, rel_err

DEBUG = LAGUNA_CONFIGS["laguna-debug"]
BLOCK = 4
KIND = model_runner.LagunaKind
W = DEBUG.sliding_window


def _tokens():
    rng = np.random.RandomState(11)
    return [rng.randint(0, DEBUG.vocab_size, size=80).astype(np.int32) for _ in range(4)]


CASE = Case(
    preset="laguna-debug", block=BLOCK, rows=16, context=128, tokens=_tokens,
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    refused=tuple(Refused(field, value, field.split("_")[0]) for field, value in (
        ("gating", False), ("gating", "per-element"), ("moe_router_logit_softcapping", 30.0),
        ("moe_apply_router_weight_on_input", True), ("attention_bias", True),
        ("tie_word_embeddings", True),
        ("layer_types", (FULL, WINDOW, WINDOW, FULL) + (WINDOW,) * 8),
        ("layer_types", (WINDOW, FULL, WINDOW, WINDOW) * 3),
        ("mlp_layer_types", ("sparse", "dense") + ("sparse",) * 10),
        ("num_attention_heads_per_layer", (6, 8, 8, 4) + (6, 8, 8, 8) * 2))),
    prefill=((37, 30, (13, 16, 8)),       # cuts inside the window; then far past 3 x W
             (5, 6, (5,)),                # shorter than the window throughout
             (16, 4, (16,))),             # one whole chunk of the budget
    # at every offset of the window and the block; one length: one program of the reference
    cuts=tuple((cut + 14, 0, (cut, 14)) for cut in (1, 4, 5, 8, 9)),
    # a mixed step: two decode rows (one 3 x W long, one short), a prompt's second chunk and
    # a new prompt, all in one program
    plans={"two_prompts_in_one_chunk_beside_decoding_sequences": Plan(
        [[(10, 0, 0, 16)], [(10, 0, 16, 29), (11, 1, 0, 3)], [(11, 1, 3, 5), (12, 2, 0, 12)],
         [(10, 0, 29, 30), (11, 1, 5, 6), (12, 2, 12, 20), (13, 3, 0, 5)]],
        {10: (0, 29), 11: (1, 5), 12: (2, 20), 13: (3, 5)})},
    burst=Burst(2, 0, 21, (8,)),
    # positions 11-13 and 0-2 in the step that is read; every expert held: each layer one pass
    records=Plan([[(60, 2, 0, 11)], [(60, 2, 11, 14), (61, 3, 0, 3)]], {60: (2, 14), 61: (3, 3)}, {
        "n_ctx_seq_tokens": 14 + 3, "n_win_seq_tokens": (14 - (11 - W + 1)) + 3,
        "n_picks_held": 6 * 4 * 11, "n_groups_live": (1, 16 * 11), "n_share_passes": 11}),
    step_counts=("n_picks_held", "n_picks_zero", "n_groups_live", "n_share_passes",
                 "n_ctx_seq_tokens", "n_win_seq_tokens"),
    scopes=("ds.laguna.full_attn", "ds.laguna.window_attn", "ds.moe_routed", "ds.moe_shared",
            "ds.dense_ffn"),
    gateway=Gateway(((0, 45), (1, 9), (2, 30))))
TOL = CASE.tol


# ---------------------------------------------------------------- the config
def test_the_presets_are_the_published_stack_its_share_and_a_small_one_of_its_pattern():
    full, cut = LAGUNA_CONFIGS["laguna-xs2"], LAGUNA_CONFIGS["laguna-xs2-ep8-20l"]
    assert full.letters == "Fwww" + "fwww" * 9 and cut.letters == "Fwww" + "fwww" * 4
    assert full.segments == (("F", 1), ("wwwf", 9), ("w", 3))
    assert cut.segments == (("F", 1), ("wwwf", 4), ("w", 3))
    assert (cut.held, cut.num_experts, cut.vocab_size) == (32, 256, 100352)
    assert (cut.count(FULL), cut.count(WINDOW), cut.heads(FULL), cut.heads(WINDOW)) == (5, 15, 48, 64)
    assert DEBUG.segments == (("F", 1), ("wwwf", 2), ("w", 3))     # a period is scanned
    assert model_runner.kind_of(full) is KIND and KIND.window(cut) == (512, 15)


def test_the_parameter_count_bears_out_the_gate_a_head():
    """33.44 B at the published keys - the "33.4B" of the catalog row - where
    a gate an element (``D x H d`` a layer) would read 34.07 B."""
    cfg = LAGUNA_CONFIGS["laguna-xs2"]
    n = count(param_shapes(cfg))
    assert n == 33_442_606_848
    by_element = n + sum((h * cfg.head_dim - h) * cfg.hidden_size
                         for h in cfg.num_attention_heads_per_layer)
    assert round(by_element / 1e9, 2) == 34.07
    assert count(param_shapes(LAGUNA_CONFIGS["laguna-xs2-ep8-20l"])) == 3_159_284_480


def test_yarn_over_half_a_head():
    """The full layers rotate 64 of 128 columns at YaRN's frequencies: plain
    where a column turns more than ``beta_fast`` times in the original
    context, over ``factor`` where fewer than ``beta_slow``."""
    cfg = LAGUNA_CONFIGS["laguna-xs2"]
    inv, factor = cfg.rope(FULL)
    plain = 1.0 / (500000 ** (np.arange(0, 64, 2) / 64))
    assert inv.shape == (32,) and factor == pytest.approx(0.1 * np.log(64) + 1)
    assert inv[0] == pytest.approx(1.0) and inv[-1] == pytest.approx(plain[-1] / 64, rel=1e-6)
    assert np.all(inv <= plain * (1 + 1e-6)) and np.all(inv >= plain / 64 * (1 - 1e-6))
    inv, factor = cfg.rope(WINDOW)
    assert inv.shape == (64,) and factor == 1.0
    assert inv[1] == pytest.approx(1.0 / 10000 ** (2 / 128))


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="not among the 16 routed"):
        dataclasses.replace(DEBUG, experts_held=4, first_expert_held=14)
    assert dataclasses.replace(DEBUG, gating="per-head").gating == "per-head"


def test_the_flax_module_is_the_reference(model, engine, reference, tokens):
    seq = tokens[0][:80]
    got = jax.jit(lambda params, ids: model.apply({"params": params}, ids))(
        engine.params, jnp.asarray(seq)[None])
    assert np.array_equal(np.asarray(got[0]), reference(seq))


# ------------------------------------------------------------ the served path
def test_a_rewind_into_what_the_window_released_is_allowed_then_refused_to_go_on(engine, tokens):
    prompt, k = tokens[2][:21], 8
    engine.put([20], [prompt[:16]])
    first = int(np.argmax(engine.put([20], [prompt[16:]])[0]))
    engine.decode_burst([20], [[first]], k)
    desc = engine.state_manager.query(20)
    assert desc.seen_tokens == 21 + k
    assert len(desc.window_blocks) <= engine.window_pool.bound(1)
    # an ending's rewind crosses into what the window released
    engine.rewind(20, k)
    assert desc.window_stale and desc.seen_tokens == 21
    with pytest.raises(ValueError, match="rewound past the blocks its window had released"):
        engine.put([20], [[1]])
    engine.flush(20)
    assert engine.window_pool.in_use == 0


# -------------------------------------------------------------- pieces alone
def _batch(pos, n_rows, ring):
    S = 2
    seq = np.full(n_rows, S - 1, np.int32)
    seq[:len(pos)] = 0
    token_pos = np.zeros(n_rows, np.int32)
    token_pos[:len(pos)] = pos
    return {"token_seq": jnp.asarray(seq), "token_pos": jnp.asarray(token_pos),
            "block_tables": jnp.asarray([list(range(1, 21)), [0] * 20], jnp.int32),
            "seq_state": jnp.asarray([ring, [0] * len(ring)], jnp.int32)}


@pytest.mark.parametrize("kind", [FULL, WINDOW])
def test_the_served_attention_layer_is_the_references_and_its_gate_a_head_is_seen(engine, kind):
    cfg, layer, S = engine.model_config, 1, 32
    x = jax.random.normal(jax.random.PRNGKey(3), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"][laguna.STACKS[kind]])
    with jax.default_matmul_precision("highest"):
        want = reference_attention(lp, x[None], cfg, kind)[0]
    pool = jnp.zeros((cfg.count(kind), 21, BLOCK, cfg.num_key_value_heads * cfg.head_dim))
    ring = list(range(1, 8))
    got, kc, vc = [], pool, pool
    step = jax.jit(lambda x, kc, vc, batch: KIND.attention_layer(
        engine.params, cfg, kind, jnp.int32(layer), x, kc, vc, batch))
    for r0 in range(0, S, 16):      # a ring of 7 holds a chunk of 16 under a window of 8
        y, kc, vc = step(x[r0:r0 + 16], kc, vc, _batch(np.arange(r0, r0 + 16), 16, ring))
        got.append(y)
    assert rel_err(jnp.concatenate(got), want) < TOL
    ungated = {**lp, "g_proj": {"kernel": jnp.zeros_like(lp["g_proj"]["kernel"])}}
    with jax.default_matmul_precision("highest"):
        half = reference_attention(ungated, x[None], cfg, kind)[0]      # sigmoid(0): a half
    assert rel_err(half, want) > 0.05                                   # 0.08 read


def test_a_window_layer_without_its_lower_edge_is_seen(engine):
    cfg, S = engine.model_config, 24
    x = jax.random.normal(jax.random.PRNGKey(4), (1, S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[0], engine.params["model"]["window_layers"])
    with jax.default_matmul_precision("highest"):
        windowed = reference_attention(lp, x, cfg, WINDOW)[0]
        whole = reference_attention(lp, x, dataclasses.replace(cfg, sliding_window=S), WINDOW)[0]
    assert rel_err(whole[:W], windowed[:W]) < 1e-6          # the first W rows see everything
    assert min(rel_err(whole[i], windowed[i]) for i in range(W, S)) > 0.01


def test_the_served_expert_layer_is_the_references(engine):
    cfg, layer = engine.model_config, 3
    x = jax.random.normal(jax.random.PRNGKey(8), (16, cfg.hidden_size))
    fp = jax.tree.map(lambda w: w[layer], engine.params["model"]["moe"])
    with jax.default_matmul_precision("highest"):
        want = reference_moe(fp, x, cfg)
    assert rel_err(KIND.expert_layer(engine.params, cfg, jnp.int32(layer), x), want) < TOL


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
        params = {"model": {"moe": jax.tree.map(lambda w: w[None], held)}}
        served = served + KIND.expert_layer(params, part, jnp.int32(0), x) - shared
    assert rel_err(summed, whole) < TOL
    assert rel_err(served, whole) < TOL


def test_layer_params_cuts_each_layers_attention_and_feed_forward(engine):
    cfg, params = engine.model_config, engine.params
    attn, ffn = layer_params(params, cfg, 0)
    assert attn["q_proj"]["kernel"].shape == (64, 6 * 16) and "gate" not in ffn
    attn, ffn = layer_params(params, cfg, 6)                 # letters Fwwwfwww...: the fifth 'w'
    assert np.array_equal(np.asarray(attn["g_proj"]["kernel"]),
                          np.asarray(params["model"]["window_layers"]["g_proj"]["kernel"][4]))
    assert np.array_equal(np.asarray(ffn["gate"]["weight"]),
                          np.asarray(params["model"]["moe"]["gate"]["weight"][5]))
    attn, _ = layer_params(params, cfg, 8)                   # the third full layer
    assert np.array_equal(np.asarray(attn["o_proj"]["kernel"]),
                          np.asarray(params["model"]["full_layers"]["o_proj"]["kernel"][2]))


class TestServing(conformance.ChunkCuts, conformance.NotKV):
    def retired(self, engine, uid, fed):
        desc = engine.state_manager.query(uid)
        assert len(desc.window_blocks) <= engine.window_pool.bound(1)
        assert desc.seen_tokens == fed and desc.window_first == max(0, fed - W + 1) // BLOCK
        assert fed < 3 * W or engine.window_pool.released > 0

    def idle(self, engine):
        assert engine.window_pool.in_use == 0 and engine.kv_cache.free_blocks == CASE.blocks - 1

    def around_the_traffic(self, gateway, served):
        pool = served.window_pool
        step_rows = served.max_tokens // BLOCK          # one step's rows, kept back once
        assert gateway.gate.usable_window_blocks == pool.free_blocks - step_rows
        yield
        external = gateway.snapshot()["external"]["Serve/WindowPool"]
        yield
        assert pool.in_use == 0 and pool.released > 0          # every block came back
        assert pool.high_water <= 3 * (pool.bound(1) + 1) + step_rows
        assert external["released"] > 0 and external["gate_refused_by_window_blocks"] == 0
