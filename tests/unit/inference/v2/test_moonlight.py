"""Moonlight-16B-A3B (``deepseek_v3``) through the v2 ragged engine at the
debug preset: the served logits against the plain float32 reference.

The served path absorbs ``kv_b_proj`` and attends over a latent paged
cache; the reference (``models/moonlight.reference_logits``) expands it
and has no cache. They share no line, so agreement is evidence.

Tolerances. The engines here run in float32 on the CPU (the default
matmul precision there is full float32), so program and reference differ
by the order of float32 additions only: relative L2 errors of 2-4e-7
were read when this was written. ``TOL`` = 2e-5 is fifty times that and
still three orders under the smallest fault a mutation of the
mathematics makes (the reference on bf16 weights reads 3e-3, the others 0.10-0.35;
``test_each_mutation_of_the_reference_is_caught`` shows each). Logits,
not tokens: with random weights the largest logit changes on rounding.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.inference.v2.modules import heuristics
from deepspeed_tpu.models import (MOONLIGHT_CONFIGS, MoonlightConfig, build_llama, build_model)
from deepspeed_tpu.models import moonlight
from deepspeed_tpu.models.moonlight import param_shapes, reference_logits
from deepspeed_tpu.ops.pallas.paged_mla_attention import (paged_mla_decode_attention,
                                                          xla_paged_mla_attention)
from deepspeed_tpu.utils import tracing

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Gateway, Plan, Refused, count, one_long_prompt,
                                     rel_err, uniform_tokens)

BLOCK = 16

CASE = Case(
    preset="moonlight-debug",
    block=BLOCK, blocks=64, rows=48, sequences=8, context=256, rng=7,
    tokens=uniform_tokens(11, (4, 160)),
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    # what the source switches on elsewhere
    refused=tuple(Refused(*row) for row in (
        ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
        ("topk_method", "greedy"), ("rope_scaling", {"type": "yarn"}))),
    prefill=((40, 0, [40]),),
    plans={"one_prompt_over_several_chunks_beside_decoding_sequences": one_long_prompt()},
    # a prompt of 23 (inside the second block), then bursts of 16 + 16 + 8 = 40 steps over
    # block boundaries at 32 and 48. Logits, and tokens only where the margin is clear: with
    # random weights the largest logit changes on rounding
    burst=Burst(0, 60, 23, (16, 16, 8), {}, 1e-4, 35),
    records=Plan([[(400, 0, 0, 20), (401, 1, 0, 7)]], {400: (0, 20), 401: (1, 7)}),
    step_counts=("n_blocks_named", "n_blocks_fetched"),
    scopes=("ds.mla", "ds.moe_routed", "ds.moe_shared"),
    gateway=Gateway(((0, 60), (1, 9), (2, 33))))
TOL = CASE.tol


# ------------------------------------------------------------------ the model
def test_the_debug_preset_has_every_mechanism():
    cfg = MOONLIGHT_CONFIGS["moonlight-debug"]
    assert cfg.first_k_dense_replace == 1 and cfg.num_moe_layers >= 2
    assert cfg.n_routed_experts >= 8 and cfg.num_experts_per_tok == 3
    assert 1 <= cfg.n_shared_experts <= 2 and cfg.kv_lora_rank > 0
    assert len({cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim}) == 3
    full = MOONLIGHT_CONFIGS["moonlight-16b-a3b"]
    n = count(param_shapes(full))
    assert abs(n - 15.96e9) < 0.01e9, n     # the published parameter count


def test_presets_build_by_name_through_one_registry(model):
    assert isinstance(model.config, MoonlightConfig)
    assert build_model("debug").config == build_llama("debug").config
    assert type(build_model("gpt2-debug")).__name__ == "GPTForCausalLM"
    with pytest.raises(KeyError, match="moonlight-debug"):
        build_model("no-such-preset")
    assert model_runner.kind_of(model.config) is model_runner.MoonlightKind
    assert model_runner.kind_of(build_llama("debug").config) is model_runner.LlamaKind
    assert model_runner.kind_of(build_model("gpt2-debug").config) is model_runner.GPTKind


def test_the_parameter_tree_has_the_checkpoints_names_and_a_live_bias(engine):
    layers = engine.params["model"]["layers"]
    assert set(layers["self_attn"]) == {"q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm",
                                        "kv_b_proj", "o_proj"}
    assert set(layers["mlp"]) == {"gate", "experts", "shared_experts"}
    assert set(layers["mlp"]["gate"]) == {"weight", "e_score_correction_bias"}
    assert set(engine.params["model"]["dense_layers"]["mlp"]) == {"gate_proj", "up_proj",
                                                                  "down_proj"}
    bias = np.asarray(layers["mlp"]["gate"]["e_score_correction_bias"])
    assert np.abs(bias).min() > 0 and bias.std() > 0.03   # zeros would hide a biased weighting


# ------------------------------------------------------ served against reference
def test_the_state_is_one_latent_row_a_token_a_layer(engine):
    cfg = engine.model_config
    assert engine.state_kind == "latent"
    assert engine.kv_cache.k.shape == (3, 64, BLOCK, cfg.kv_lora_rank)
    assert engine.kv_cache.v.shape == (3, 64, BLOCK, 128)
    assert engine.state_bytes_per_token == 3 * (cfg.kv_lora_rank + 128) * 4
    full = MOONLIGHT_CONFIGS["moonlight-16b-a3b"]
    assert sum(model_runner.MoonlightKind.state_rows(full)) == 640   # <= 640 values, 1280 B in bf16
    assert model_runner.LlamaKind.state_rows(build_llama("debug").config) == (32, 32)


@pytest.mark.parametrize("rows,path", [(2, "gathered"), (9, "ragged")])
def test_a_layers_experts_are_read_where_they_lie_in_the_stack(engine, rows, path):
    """The routed experts of all layers are one table of groups to the
    grouped GEMM, which chooses its dispatch on the layer's own expert
    count (8 here: 6 routed rows are gathered, 27 ride ``ragged_dot``):
    the same output as on the layer's experts cut out."""
    from deepspeed_tpu.ops.grouped_gemm import GMM_STATS
    cfg = engine.model_config
    layers = engine.params["model"]["layers"]
    p = jax.tree.map(lambda w: w[1], {k: v for k, v in layers["mlp"].items() if k != "experts"})
    x = jnp.asarray(np.random.default_rng(2).standard_normal((rows, cfg.hidden_size)), jnp.float32)
    GMM_STATS.reset()
    got = model_runner._moonlight_moe(x, p, layers["mlp"]["experts"], 1, cfg)
    cut = jax.tree.map(lambda w: w[1:2], layers["mlp"]["experts"])
    want = model_runner._moonlight_moe(x, p, cut, 0, cfg)
    assert GMM_STATS.snapshot() == {path + "_table": 2}
    assert rel_err(got, want) < 1e-6


def _shared_experts_zeroed(params):
    params = jax.tree.map(lambda x: x, params)
    down = params["model"]["layers"]["mlp"]["shared_experts"]["down_proj"]
    down["kernel"] = jnp.zeros_like(down["kernel"])
    return params


def _bias_zeroed(params):
    params = jax.tree.map(lambda x: x, params)
    gate = params["model"]["layers"]["mlp"]["gate"]
    gate["e_score_correction_bias"] = jnp.zeros_like(gate["e_score_correction_bias"])
    return params


# One deliberate fault of the reference each, made from outside it: (params, cfg) → the
# faulty reference's (params, cfg), or the name of what is patched in ``models/moonlight``.
MUTATIONS = {
    "bf16_weights": lambda p, c: (jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype), p), c),
    "no_routed_scale": lambda p, c: (p, dataclasses.replace(c, routed_scaling_factor=1.0)),
    "no_shared_experts": lambda p, c: (_shared_experts_zeroed(p), c),
    "unbiased_choice": lambda p, c: (_bias_zeroed(p), c),
    "raw_c_kv": "_rms_norm",
}


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_each_mutation_of_the_reference_is_caught(engine, reference, tokens, mutation,
                                                  monkeypatch):
    """The tolerance would catch each piece of the mathematics left out or
    done in less: a reference on weights rounded to bfloat16, a missing
    ``routed_scaling_factor``, a missing shared expert, experts chosen
    without the bias, a cache of un-normalised ``c_kv``."""
    seq = tokens[2][40:88]
    got = engine.put([200], [seq[:-1]])
    got = np.stack([got[0], engine.put([200], [seq[-1:]])[0]])
    engine.flush(200)
    assert rel_err(got, reference(seq)[-2:]) < TOL
    params, cfg = engine.params, engine.model_config
    if mutation == "raw_c_kv":
        rms_norm = moonlight._rms_norm
        monkeypatch.setattr(moonlight, "_rms_norm", lambda x, scale, eps: (
            x if x.shape[-1] == cfg.kv_lora_rank else rms_norm(x, scale, eps)))
    else:
        params, cfg = MUTATIONS[mutation](params, cfg)
    faulty = np.asarray(reference_logits(params, jnp.asarray(seq)[None], cfg))[0]
    assert rel_err(got, faulty[-2:]) > 50 * TOL


# ------------------------------------------------------------------ the router
def test_the_bias_chooses_and_the_unbiased_scores_weigh():
    cfg = dataclasses.replace(MOONLIGHT_CONFIGS["moonlight-debug"], n_shared_experts=1)
    rng = np.random.default_rng(3)
    D, E, I, k = cfg.hidden_size, cfg.n_routed_experts, cfg.moe_intermediate_size, 3
    x = np.abs(rng.standard_normal((5, D))).astype(np.float32)
    gate_w = (rng.standard_normal((D, E)) * 0.2).astype(np.float32)
    gate_w[:, 0] = -0.1      # with x >= 0: expert 0 scores near zero for every token
    bias = np.zeros(E, np.float32)
    scores = 1.0 / (1.0 + np.exp(-(x @ gate_w)))
    unbiased_top = [set(np.argsort(-scores[t])[:k]) for t in range(len(x))]
    lowest = [e for e in range(E) if not any(e in top for top in unbiased_top)][0]
    bias[lowest] = 5.0       # an expert no token would choose is forced into every choice
    w = {n: (rng.standard_normal(s) * 0.1).astype(np.float32)
         for n, s in (("gate_proj", (E, D, I)), ("up_proj", (E, D, I)), ("down_proj", (E, I, D)))}
    zeros = {"gate_proj": {"kernel": np.zeros((D, I), np.float32)},
             "up_proj": {"kernel": np.zeros((D, I), np.float32)},
             "down_proj": {"kernel": np.zeros((I, D), np.float32)}}
    p = {"gate": {"weight": gate_w, "e_score_correction_bias": bias}, "shared_experts": zeros}
    # the layer's experts are the second of two layers' (the other's would be seen at once)
    stack = {n: jnp.stack([jnp.full(v.shape, 1e3, jnp.float32), jnp.asarray(v)])
             for n, v in w.items()}
    got = np.asarray(model_runner._moonlight_moe(jnp.asarray(x), jax.tree.map(jnp.asarray, p),
                                                 stack, 1, cfg))

    def silu(v):
        return v / (1.0 + np.exp(-v))

    want = np.zeros_like(x)
    for t in range(len(x)):
        chosen = np.argsort(-(scores[t] + bias))[:k]
        assert lowest in chosen and set(chosen) != set(np.argsort(-scores[t])[:k])
        weights = scores[t, chosen] / scores[t, chosen].sum() * cfg.routed_scaling_factor
        for e, wt in zip(chosen, weights):
            h = silu(x[t] @ w["gate_proj"][e]) * (x[t] @ w["up_proj"][e])
            want[t] += wt * (h @ w["down_proj"][e])
    assert rel_err(got, want) < 1e-5


# ------------------------------------------------------------------ the kernel
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_the_latent_kernel_matches_its_xla_twin(dtype, tol):
    """Interpret mode; contexts that end at the first row of a block, at
    its last, and inside one; heads share each fetched row. bfloat16: the
    kernel keeps a running max over blocks where the twin takes one
    softmax, and both round probabilities to bf16 (2^-9 a value)."""
    rng = np.random.default_rng(0)
    L, NB, bs, rank, lanes, H, T, MB = 2, 14, 16, 128, 128, 4, 7, 6
    c = jnp.asarray(rng.standard_normal((L, NB, bs, rank)), dtype)
    r = jnp.asarray(rng.standard_normal((L, NB, bs, lanes)), dtype)
    q = jnp.asarray(rng.standard_normal((T, H, rank + lanes)) * 0.1, dtype)
    tab = jnp.asarray(np.stack([rng.permutation(NB - 1)[:MB] + 1 for _ in range(T)]), jnp.int32)
    pos = jnp.asarray([0, 5, 15, 16, 37, 79, 95], jnp.int32)
    got = paged_mla_decode_attention(q, c, r, tab, pos, 1, interpret=True)
    want = xla_paged_mla_attention(q, c, r, tab, pos, jnp.int32(1))
    assert got.shape == (T, H, rank) and got.dtype == dtype
    assert rel_err(got, want) < tol
    other = xla_paged_mla_attention(q, c, r, tab, pos, jnp.int32(0))
    assert rel_err(other, want) > 0.5           # the layer index is read


def test_the_registry_names_the_latent_implementations_and_a_wrong_pin_says_the_state_kind():
    assert {"pallas_paged_mla", "xla_gather_mla"} <= set(heuristics.implementations("attention"))
    q, pool = (8, 4, 160), (3, 64, 16, 32)
    name, _ = heuristics.instantiate_attn(None, 32, 16, q, pool, None, max_blocks=16,
                                          state_kind="latent")
    assert name == "xla_gather_mla"             # rank 32 is no whole lane tile: never the kernel
    name, _ = heuristics.instantiate_attn(None, 128, 16, (8, 4, 128), (2, 64, 16, 256), None,
                                          max_blocks=16)
    assert name in ("pallas_paged", "xla_gather")    # a kv state is never offered a latent one
    for pin, kind in (("pallas_paged", "latent"), ("xla_gather", "latent"),
                      ("xla_gather_mla", "kv"), ("pallas_paged_mla", "latent")):
        with pytest.raises(ValueError, match=f"state kind '{kind}'"):
            heuristics.instantiate_attn(None, 32, 16, q, pool, None, max_blocks=16,
                                        override=pin, state_kind=kind)


# ------------------------------------------------------------------- tracing
def named_blocks(positions, rows):
    """What a program of ``rows`` rows names: a live row the blocks up to
    its position's, a padding row the null block."""
    return sum(p // BLOCK + 1 for p in positions) + rows - len(positions)


def test_step_records_carry_the_attended_context(engine, tokens):
    """... and the blocks the latent attention's rows name and fetch
    (``MoonlightKind.step_counts``): the gather, which serves here, reads
    whatever is named."""
    assert model_runner.MoonlightKind.step_counts == ("n_blocks_named", "n_blocks_fetched")
    engine.put([400, 401], [tokens[0][:20], tokens[1][:7]])
    assert engine.last_step.n_ctx_tokens == 27
    rows = int(engine.last_step.program)
    named = named_blocks(list(range(20)) + list(range(7)), rows)
    assert engine.last_step.counts == {"n_blocks_named": named, "n_blocks_fetched": named}
    engine.put([400, 401], [tokens[0][20:21], tokens[1][7:9]])
    assert engine.last_step.n_ctx_tokens == 21 + 9
    engine.decode_burst([400, 401], [1, 2], 4)
    # step j attends seen + j + 1 positions: 21 and 9 seen
    assert engine.last_step.n_ctx_tokens == (22 + 23 + 24 + 25) + (10 + 11 + 12 + 13)
    named = sum(named_blocks([21 + j, 9 + j], 2) for j in range(4))
    assert engine.last_step.counts["n_blocks_named"] >= named    # + the burst's padding rows
    assert engine.last_step.counts["n_blocks_fetched"] == engine.last_step.counts["n_blocks_named"]
    assert tracing.snapshot()["steps"][-1]["n_ctx_tokens"] == engine.last_step.n_ctx_tokens
    assert tracing.snapshot()["steps"][-1]["counts"] == engine.last_step.counts
    for uid in (400, 401):
        engine.flush(uid)


class TestServing(conformance.NotKV):
    pass
