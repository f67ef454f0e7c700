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

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        KVTierConfig, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig, SpecDecodeConfig)
from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.inference.v2.config_v2 import LoRAServingConfig, QuantizationConfig
from deepspeed_tpu.models import LAGUNA_CONFIGS, build_model
from deepspeed_tpu.models import laguna
from deepspeed_tpu.models.laguna import (FULL, WINDOW, LagunaConfig, layer_params, param_shapes,
                                         reference_attention, reference_logits, reference_moe)
from deepspeed_tpu.utils import tracing

TOL = 2e-5
DEBUG = LAGUNA_CONFIGS["laguna-debug"]
BLOCK = 4
KIND = model_runner.LagunaKind
W = DEBUG.sliding_window
COUNTS = ("n_picks_held", "n_picks_zero", "n_groups_live", "n_share_passes",
          "n_ctx_seq_tokens", "n_win_seq_tokens")


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def engine_config(**over):
    return RaggedInferenceEngineConfig(
        kv_block_size=BLOCK, num_kv_blocks=96,
        state_manager=DSStateManagerConfig(max_ragged_batch_size=16,
                                           max_ragged_sequence_count=4,
                                           max_tracked_sequences=4, max_context=128), **over)


@pytest.fixture(scope="module")
def model():
    return build_model("laguna-debug")


@pytest.fixture(scope="module")
def engine(model):
    return InferenceEngineV2(model=model, config=engine_config(), dtype=jnp.float32,
                             rng=jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.RandomState(11)
    return [rng.randint(0, DEBUG.vocab_size, size=80).astype(np.int32) for _ in range(4)]


@pytest.fixture(scope="module")
def reference(engine):
    """seq → the reference's logits [len(seq), V]. One program for every
    length: the sequence is padded to 80 tokens, which a causal model's rows
    before the padding cannot see."""
    cfg, params = engine.model_config, engine.params    # (a gateway's shutdown takes the engine's)
    program = jax.jit(lambda params, ids: reference_logits(params, ids, cfg))

    def logits(seq):
        padded = np.zeros((1, 80), np.int32)
        padded[0, :len(seq)] = seq
        return np.asarray(program(params, jnp.asarray(padded))[0, :len(seq)])
    return logits


def count(shapes):
    return sum(int(np.prod(s)) for s in jax.tree.leaves(shapes,
                                                         is_leaf=lambda x: isinstance(x, tuple)))


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


@pytest.mark.parametrize("field,value", [
    ("gating", False), ("gating", "per-element"), ("moe_router_logit_softcapping", 30.0),
    ("moe_apply_router_weight_on_input", True), ("attention_bias", True),
    ("tie_word_embeddings", True),
    ("layer_types", (FULL, WINDOW, WINDOW, FULL) + (WINDOW,) * 8),
    ("layer_types", (WINDOW, FULL, WINDOW, WINDOW) * 3),
    ("mlp_layer_types", ("sparse", "dense") + ("sparse",) * 10),
    ("num_attention_heads_per_layer", (6, 8, 8, 4) + (6, 8, 8, 8) * 2),
])
def test_what_is_not_implemented_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=field.split("_")[0]):
        dataclasses.replace(DEBUG, **{field: value})


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
def serve(engine, plan):
    """``plan``: steps of ``[(uid, tokens), ...]`` → {uid: [a row of logits a step]}."""
    out = {}
    for step in plan:
        logits = engine.put([u for u, _ in step], [t for _, t in step])
        for (u, _), row in zip(step, logits):
            out.setdefault(u, []).append(row)
    return out


@pytest.mark.parametrize("prompt,steps,chunks", [
    (37, 30, (13, 16, 8)),       # cuts inside the window; then far past 3 x W
    (5, 6, (5,)),                # shorter than the window throughout
    (16, 4, (16,)),              # one whole chunk of the budget
])
def test_prefill_in_chunks_then_decode_through_both_pools(engine, reference, tokens, prompt,
                                                          steps, chunks):
    seq = tokens[0][:prompt + steps]
    ref = reference(seq)
    at, got = 0, []
    for n in chunks:
        got.append((at + n - 1, engine.put([1], [seq[at:at + n]])[0]))
        at += n
    for i in range(steps):
        got.append((prompt + i, engine.put([1], [seq[prompt + i:prompt + i + 1]])[0]))
    desc = engine.state_manager.query(1)
    assert len(desc.window_blocks) <= engine.window_pool.bound(1)
    assert desc.window_first == max(0, prompt + steps - W + 1) // BLOCK
    engine.flush(1)
    assert engine.window_pool.in_use == 0
    for pos, row in got:
        assert rel_err(row, ref[pos]) < TOL, pos
    assert prompt + steps < 3 * W or engine.window_pool.released > 0


@pytest.mark.parametrize("cut", [1, 4, 5, 8, 9])
def test_a_chunk_cut_at_every_offset_of_the_window_and_the_block(engine, reference, tokens, cut):
    seq = tokens[1][:26]        # one length: one program of the reference
    ref = reference(seq)
    got = serve(engine, [[(2, seq[:cut])], [(2, seq[cut:cut + 14])]])[2]
    engine.flush(2)
    assert rel_err(got[0], ref[cut - 1]) < TOL and rel_err(got[1], ref[cut + 13]) < TOL


def test_two_prompts_in_one_chunk_beside_decoding_sequences(engine, reference, tokens):
    """A mixed step: two decode rows (one 3 x W long, one short), a prompt's
    second chunk and a new prompt, all in one program."""
    a, b, c, d = tokens[0][:30], tokens[1][:6], tokens[2][:20], tokens[3][:5]
    serve(engine, [[(10, a[:16])], [(10, a[16:29]), (11, b[:3])], [(11, b[3:5]), (12, c[:12])]])
    got = serve(engine, [[(10, a[29:]), (11, b[5:]), (12, c[12:]), (13, d)]])
    for uid, seq in ((10, a), (11, b), (12, c), (13, d)):
        assert rel_err(got[uid][0], reference(seq)[-1]) < TOL, uid
        engine.flush(uid)
    assert engine.window_pool.in_use == 0 and engine.kv_cache.free_blocks == 95


def test_decode_bursts_go_through_both_pools(engine, reference, tokens):
    prompt, k = tokens[2][:21], 8
    engine.put([20], [prompt[:16]])
    first = int(np.argmax(engine.put([20], [prompt[16:]])[0]))
    toks = engine.decode_burst([20], [[first]], k)
    desc = engine.state_manager.query(20)
    assert desc.seen_tokens == 21 + k
    assert len(desc.window_blocks) <= engine.window_pool.bound(1)
    seq = np.concatenate([prompt, [first], toks[:-1, 0]]).astype(np.int32)
    ref = reference(seq)
    assert [int(t) for t in toks[:, 0]] == [int(t) for t in np.argmax(ref[21:], axis=-1)]
    # an ending's rewind crosses into what the window released: allowed, then refused to go on
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


# ----------------------------------------------------------- what is refused
@pytest.mark.parametrize("name,over", [
    ("prefix cache", {"prefix_cache": PrefixCacheConfig(enabled=True)}),
    ("KV tier", {"kv_tier": KVTierConfig(enabled=True)}),
    ("speculative decoding", {"spec_decode": SpecDecodeConfig(enabled=True)}),
    ("LoRA serving", {"lora": LoRAServingConfig(enabled=True)}),
    ("weight-only quantization", {"quantization": QuantizationConfig(quantization_mode="wf6af16")}),
    ("tensor/expert-parallel sharding", {"tensor_parallel_degree": 2}),
])
def test_each_subsystem_that_shares_or_moves_blocks_refuses_the_model_by_name(model, name, over):
    with pytest.raises(NotImplementedError, match=name) as e:
        InferenceEngineV2(model=model, config=engine_config(**over), dtype=jnp.float32)
    assert "'kv+window'" in str(e.value) and "'laguna'" in str(e.value)


def test_suspend_is_refused_by_name(engine, tokens):
    engine.put([70], [tokens[0][:5]])
    with pytest.raises(NotImplementedError, match="suspend/resume.*kv\\+window"):
        engine.suspend(70)
    engine.flush(70)
    assert engine.window_pool.in_use == 0


# ------------------------------------------------------------------- tracing
def test_step_records_carry_the_counts_and_the_scopes_are_in_the_program(engine, tokens):
    a, b = tokens[2][:14], tokens[3][:3]
    engine.put([60], [a[:11]])
    syncs = engine.host_syncs
    engine.put([60, 61], [a[11:], b])               # positions 11-13 and 0-2
    assert engine.host_syncs - syncs == 2            # as for any model kind: pack + fetch
    counts = engine.last_step.counts
    assert tuple(counts) == KIND.step_counts == COUNTS
    assert counts["n_ctx_seq_tokens"] == 14 + 3
    assert counts["n_win_seq_tokens"] == (14 - (11 - W + 1)) + 3
    assert counts["n_picks_held"] == 6 * 4 * 11 and 0 < counts["n_groups_live"] <= 16 * 11
    assert counts["n_share_passes"] == 11       # every expert held: each layer one pass
    assert tracing.snapshot()["steps"][-1]["counts"] == counts
    engine.flush(60)
    engine.flush(61)
    lowered = engine._step.lower(engine.params, engine.kv_cache.k, engine.kv_cache.v,
                                 engine.state_extra, engine._batch.finalize_packed()).as_text(
                                     debug_info=True)
    for scope in ("ds.laguna.full_attn", "ds.laguna.window_attn", "ds.moe_routed",
                  "ds.moe_shared", "ds.dense_ffn"):
        assert scope in lowered, scope


# ------------------------------------------------------------------- gateway
def test_the_gateway_serves_it_through_the_same_scheduler(engine, reference, tokens):
    """(The file's last test: the gateway's shutdown destroys the engine it was given.)"""
    from deepspeed_tpu.serving import ServingConfig, ServingGateway
    prompts = [tokens[0][:45], tokens[1][:9], tokens[2][:30]]
    pool = engine.window_pool
    assert pool.in_use == 0
    released, first_seq = pool.released, len(tracing.snapshot()["steps"])
    pool.high_water = 0
    gateway = ServingGateway(engine, config=ServingConfig(default_max_new_tokens=12))
    try:
        assert gateway.gate.usable_window_blocks == pool.free_blocks - 16 // BLOCK
        handles = [gateway.submit(p, max_new_tokens=12) for p in prompts]
        streams = [[int(t) for t in h.result(timeout=300)] for h in handles]
        external = gateway.snapshot()["external"]["Serve/WindowPool"]
    finally:
        gateway.shutdown()
    for prompt, stream in zip(prompts, streams):
        full = np.concatenate([prompt, np.asarray(stream[:-1], np.int32)])
        assert stream == [int(t) for t in np.argmax(reference(full)[len(prompt) - 1:], axis=-1)]
    records = [r for r in tracing.snapshot()["steps"][first_seq:] if r["engine"] == engine.trace_id]
    assert {"burst", "put"} <= {r["kind"] for r in records}
    assert all(r["counts"] is not None for r in records if r["kind"] in ("burst", "put"))
    assert pool.in_use == 0 and pool.released > released   # every block came back
    assert pool.high_water <= 3 * (pool.bound(1) + 1) + 16 // BLOCK
    assert external["released"] > released and external["gate_refused_by_window_blocks"] == 0
