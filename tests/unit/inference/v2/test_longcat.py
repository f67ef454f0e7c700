"""LongCat-Flash through the v2 ragged engine at the debug preset: the
served logits against the plain float32 reference, and the expert share.

The served path absorbs ``kv_b_proj``, attends over a latent paged cache
of **two state layers a model layer** and sends only the held picks
through the grouped matmul; the reference
(``models/longcat.reference_logits``) expands ``kv_b_proj``, has no cache
and applies every held expert to every token. They share no line.

Tolerances as ``test_moonlight.py``: float32 engines on the CPU, so the
two differ by the order of float32 additions (relative L2 errors of
1-4e-7 were read when this was written); ``TOL`` = 2e-5 is fifty times
that and orders under what leaving out a piece of the mathematics makes
(``test_each_mutation_of_the_reference_is_caught``).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, model_runner
from deepspeed_tpu.models import LONGCAT_CONFIGS, LongcatFlashConfig, build_model
from deepspeed_tpu.models import longcat
from deepspeed_tpu.models.longcat import (param_shapes, reference_experts, reference_logits,
                                          reference_router)
from deepspeed_tpu.ops import grouped_gemm
from deepspeed_tpu.ops.grouped_gemm import GMM_STATS, ExpertShare, dropless_moe_ffn
from deepspeed_tpu.utils import tracing

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Gateway, Plan, Refused, count, engine_config,
                                     one_long_prompt, rel_err, two_pool_subsystems,
                                     uniform_tokens)

DEBUG = LONGCAT_CONFIGS["longcat-flash-debug"]
BLOCK = 16

CASE = Case(
    # experts 2..5 of 8: a share that starts past expert 0, as every rank but one does
    preset="longcat-flash-debug", preset_over={"experts_held": 4, "first_expert_held": 2},
    block=BLOCK, blocks=64, rows=48, sequences=8, context=256, rng=7,
    tokens=uniform_tokens(11, (4, 160)),
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    refused=tuple(Refused(*row) for row in (
        ("attention_method", "MHA"), ("q_lora_rank", None), ("zero_expert_type", "copy"),
        ("norm_topk_prob", True), ("router_bias", True), ("rope_scaling", {"type": "yarn"}),
        ("attention_bias", True), ("tie_word_embeddings", True), ("hidden_act", "gelu"),
        ("moe_topk", 13), ("experts_held", 9, "routed"), ("first_expert_held", 6, "routed"))),
    prefill=((40, 0, [40]),),
    plans={"one_prompt_over_several_chunks_beside_decoding_sequences": one_long_prompt()},
    # a prompt of 23, then bursts of 16 + 16 + 8 steps over block boundaries; with random
    # weights the largest logit changes on rounding where the margin is small
    burst=Burst(0, 60, 23, (16, 16, 8), {}, 1e-4, 35),
    records=Plan([[(800, 1, 0, 14)]], {800: (1, 14)}, {
        "n_picks_held": (1, DEBUG.moe_topk * 14 * DEBUG.num_layers),
        "n_groups_live": (1, 4 * DEBUG.num_layers)}),
    step_counts=("n_picks_held", "n_picks_zero", "n_groups_live", "n_share_passes",
                 "n_blocks_named", "n_blocks_fetched"),
    scopes=("ds.mla", "ds.moe_routed"),
    subsystems=two_pool_subsystems("expert_parallel_degree"),
    gateway=Gateway(((0, 60), (1, 9), (2, 33))))
TOL = CASE.tol


def prefill_state(engine, uid, seq, chunks):
    """Feed ``seq`` in ``chunks`` → the rows of both pools that the
    sequence's blocks hold, [state layers, tokens, width] each."""
    fed = 0
    for n in chunks:
        engine.put([uid], [seq[fed:fed + n]])
        fed += n
    blocks = np.asarray(engine.state_manager.query(uid).blocks)
    rows = [np.asarray(pool)[:, blocks].reshape(pool.shape[0], -1, pool.shape[3])[:, :len(seq)]
            for pool in (engine.kv_cache.k, engine.kv_cache.v)]
    engine.flush(uid)
    return rows


# ------------------------------------------------------------------ the model
def test_the_debug_preset_has_every_mechanism():
    assert DEBUG.num_layers == 2 and DEBUG.n_routed_experts == 8 and DEBUG.zero_expert_num == 4
    assert DEBUG.moe_topk == 3 and DEBUG.q_lora_rank and DEBUG.held == 8
    assert DEBUG.mla_scale_q_lora and DEBUG.mla_scale_kv_lora
    assert DEBUG.query_scale != 1.0 and DEBUG.latent_scale != 1.0
    assert len({DEBUG.qk_nope_head_dim, DEBUG.qk_rope_head_dim, DEBUG.v_head_dim}) == 3
    assert abs(count(param_shapes(LongcatFlashConfig())) - 560.7e9) < 0.1e9   # the published 560B
    share = LONGCAT_CONFIGS["longcat-flash-omni-ep32"]
    assert (share.num_layers, share.held, share.vocab_size) == (4, 16, 16384)
    assert abs(2 * count(param_shapes(share)) - 10.345e9) < 0.005e9   # bytes in bf16
    assert share.query_scale == 2.0 and abs(share.latent_scale ** 2 - 12.0) < 1e-9


def test_presets_build_by_name_and_pick_their_kind(model):
    assert isinstance(model.config, LongcatFlashConfig)
    kind = model_runner.kind_of(model.config)
    assert kind is model_runner.LongcatKind and kind.state_kind == "latent"
    assert kind.state_layers(model.config) == 4 and kind.state_rows(model.config) == (32, 128)
    assert kind.step_counts == ("n_picks_held", "n_picks_zero", "n_groups_live",
                                "n_share_passes", "n_blocks_named", "n_blocks_fetched")
    moon = build_model("moonlight-debug").config
    assert model_runner.kind_of(moon) is model_runner.MoonlightKind
    assert model_runner.MoonlightKind.state_layers(moon) == moon.num_hidden_layers
    assert model_runner.LlamaKind.state_layers(build_model("debug").config) == 2
    assert model_runner.MoonlightKind.step_counts == kind.step_counts[4:]
    assert model_runner.LlamaKind.step_counts == ()


def test_the_parameter_tree_has_the_checkpoints_names_and_a_live_bias(engine):
    layers = engine.params["model"]["layers"]
    assert set(layers) == {"input_layernorm", "post_attention_layernorm", "self_attn", "mlps",
                           "mlp"}
    for pair in ("input_layernorm", "post_attention_layernorm", "self_attn", "mlps"):
        assert set(layers[pair]) == {"0", "1"}         # the checkpoint's self_attn.0 / self_attn.1
    assert set(layers["self_attn"]["1"]) == {"q_a_proj", "q_a_layernorm", "q_b_proj",
                                             "kv_a_proj_with_mqa", "kv_a_layernorm",
                                             "kv_b_proj", "o_proj"}
    assert set(layers["mlp"]) == {"router", "experts"}
    assert set(layers["mlp"]["router"]) == {"classifier", "e_score_correction_bias"}
    assert layers["mlps"]["0"]["gate_proj"]["kernel"].shape == (2, 64, 160)
    assert layers["mlp"]["experts"]["gate_proj"].shape == (2, 4, 64, 48)      # the 4 held
    assert layers["mlp"]["router"]["classifier"]["weight"].shape == (2, 64, 12)   # every column
    bias = np.asarray(layers["mlp"]["router"]["e_score_correction_bias"])
    assert np.abs(bias).min() > 0 and 0.01 < bias.std() < 0.1   # about half a mean score


# ------------------------------------------------------ served against reference
def test_the_state_is_two_latent_rows_a_token_a_model_layer(engine):
    cfg = engine.model_config
    assert engine.state_kind == "latent"
    assert engine.kv_cache.k.shape == (4, 64, BLOCK, cfg.kv_lora_rank)
    assert engine.kv_cache.v.shape == (4, 64, BLOCK, 128)
    assert engine.state_bytes_per_token == 2 * cfg.num_layers * (cfg.kv_lora_rank + 128) * 4
    full = LONGCAT_CONFIGS["longcat-flash-omni-ep32"]
    kind = model_runner.LongcatKind
    assert kind.state_layers(full) * sum(kind.state_rows(full)) * 2 == 10240   # B a token, bf16


def test_one_chunk_and_three_leave_the_same_state_in_both_state_layers(engine, tokens):
    seq = tokens[3][20:65]
    whole = prefill_state(engine, 600, seq, (45,))
    parts = prefill_state(engine, 601, seq, (17, 20, 8))
    cfg = engine.model_config
    for a, b in zip(whole, parts):
        assert a.shape[:2] == (2 * cfg.num_layers, 45)
        for layer in range(2 * cfg.num_layers):
            assert np.abs(a[layer]).max() > 0.01      # every state layer was written
            assert rel_err(b[layer], a[layer]) < TOL
    # the two halves of a model layer hold different rows
    assert rel_err(whole[0][0], whole[0][1]) > 0.1


def _router_zeroed(params, what):
    params = jax.tree.map(lambda x: x, params)
    router = params["model"]["layers"]["mlp"]["router"]
    if what == "bias":
        router["e_score_correction_bias"] = jnp.zeros_like(router["e_score_correction_bias"])
    return params


# One deliberate fault of the reference each, made from outside it: (params, cfg) → the
# faulty reference's (params, cfg), or the name of what is patched in ``models/longcat``.
MUTATIONS = {
    "bf16_weights": lambda p, c: (jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype), p), c),
    "no_routed_scale": lambda p, c: (p, dataclasses.replace(c, routed_scaling_factor=1.0)),
    "no_zero_experts_part": "reference_experts",
    "unbiased_choice": lambda p, c: (_router_zeroed(p, "bias"), c),
    "normalised_weights": "reference_router",
    "no_query_scale": lambda p, c: (p, dataclasses.replace(c, mla_scale_q_lora=False)),
    "no_latent_scale": lambda p, c: (p, dataclasses.replace(c, mla_scale_kv_lora=False)),
    "another_share": lambda p, c: (p, dataclasses.replace(c, first_expert_held=3)),
}


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_each_mutation_of_the_reference_is_caught(engine, reference, tokens, mutation,
                                                  monkeypatch):
    """The tolerance would catch each piece of the mathematics left out or
    done otherwise: bf16 weights, a missing scaling factor, the identity
    part left out, experts chosen without the bias, weights normalised,
    either MLA scale left out, and the neighbouring share's experts."""
    seq = tokens[2][40:88]
    got = engine.put([200], [seq[:-1]])
    got = np.stack([got[0], engine.put([200], [seq[-1:]])[0]])
    engine.flush(200)
    assert rel_err(got, reference(seq)[-2:]) < TOL
    params, cfg = engine.params, engine.model_config
    if mutation == "no_zero_experts_part":
        experts = longcat.reference_experts
        monkeypatch.setattr(longcat, "reference_experts",
                            lambda mlp, x, c: experts(mlp, x, c, zero=False))
    elif mutation == "normalised_weights":
        router = longcat.reference_router

        def normalised(mlp, x, c):
            w, margin = router(mlp, x, c)
            return w / w.sum(-1, keepdims=True) * c.routed_scaling_factor, margin
        monkeypatch.setattr(longcat, "reference_router", normalised)
    else:
        params, cfg = MUTATIONS[mutation](params, cfg)
    faulty = np.asarray(reference_logits(params, jnp.asarray(seq)[None], cfg))[0]
    assert rel_err(got, faulty[-2:]) > 50 * TOL


# ------------------------------------------------------------- the expert share
def _layer_mlp(params, layer):
    return jax.tree.map(lambda w: w[layer], params["model"]["layers"]["mlp"])


@pytest.fixture(scope="module")
def whole():
    """The uncut debug model's first expert layer: all 8 routed experts."""
    m = build_model("longcat-flash-debug")
    params = m.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((37, DEBUG.hidden_size)), jnp.float32)
    return m.config, _layer_mlp(params, 0), x


def _rank(cfg, mlp, first, held):
    share = dataclasses.replace(cfg, experts_held=held, first_expert_held=first)
    cut = {**mlp, "experts": jax.tree.map(lambda w: w[first:first + held], mlp["experts"])}
    return share, cut


@pytest.mark.parametrize("ranks", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(whole, ranks):
    """Over all expert ranks, the held parts plus the zero-compute part
    counted once equal the uncut reference's ``M(x)`` - in the reference
    and in the served expert layer alike."""
    cfg, mlp, x = whole
    want = reference_experts(mlp, x, cfg)
    held = cfg.n_routed_experts // ranks
    ref_sum, served_sum = jnp.zeros_like(x), jnp.zeros_like(x)
    for r in range(ranks):
        share, cut = _rank(cfg, mlp, r * held, held)
        ref_sum = ref_sum + reference_experts(cut, x, share, zero=(r == 0))
        stacks = jax.tree.map(lambda w: w[None], cut["experts"])        # one layer's table
        m, counts = model_runner._routed_experts(
            x, model_runner.LongcatKind.router(share, cut), stacks, 0, jnp.ones(x.shape[0], bool))
        served_sum = served_sum + m
        assert int(counts[0]) + int(counts[1]) <= cfg.moe_topk * x.shape[0]
    # every rank computed the identity part of these tokens: count it once
    weights, _ = reference_router(mlp, x, cfg)
    identity = jnp.sum(weights[:, cfg.n_routed_experts:], axis=-1, keepdims=True) * x
    assert rel_err(ref_sum, want) < 1e-6
    assert rel_err(served_sum - (ranks - 1) * identity, want) < 1e-5
    assert float(jnp.abs(identity).max()) > 0.01       # zero-compute picks were made


@pytest.mark.parametrize("pallas", [False, True])
def test_a_token_without_a_held_pick_gives_its_identity_part_and_launches_no_group(pallas,
                                                                                   monkeypatch):
    """Picks that are all zero-compute or absent: exactly ``(sum of the
    zero picks' weights) * x``, whatever the held experts' weights are -
    nothing was multiplied by them - and no held group has a row."""
    monkeypatch.setattr(grouped_gemm, "FORCE_INTERPRET", pallas)
    rng = np.random.default_rng(9)
    T, D, I = 24, 128, 128
    share = ExpertShare(first=2, held=4, routed=8, zero=4)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    # columns 0-1 and 6-7 are absent experts, 8-11 zero-compute, 2-5 held: none picked
    idx = jnp.asarray(rng.choice([0, 1, 6, 7, 8, 9, 10, 11], (T, 3)), jnp.int32)
    vals = jnp.asarray(rng.uniform(0.1, 1.0, (T, 3)), jnp.float32)
    # the Pallas kernel skips what has no row, so even NaN weights leave no trace; the CPU's
    # ragged_dot multiplies every row by every group under a mask, so there they are finite
    fill = np.nan if pallas else 1e3
    stacks = [jnp.full(s, fill, jnp.float32) for s in ((4, D, I), (4, D, I), (4, I, D))]
    GMM_STATS.reset()
    got = dropless_moe_ffn(x, idx, vals, *stacks, num_experts=12, share=share)
    want = jnp.sum(jnp.where(idx >= 8, vals, 0), axis=-1, keepdims=True) * x
    assert np.array_equal(np.asarray(got), np.asarray(want))        # exactly
    assert GMM_STATS.snapshot() == {("pallas" if pallas else "ragged") + "_share": 1}
    held, zero = share.parts(idx)
    assert not bool(held.any()) and bool(zero.any())
    # the same picks with one held pick for one token: only that token meets the weights
    idx1 = idx.at[5, 0].set(3)
    got1 = np.asarray(dropless_moe_ffn(x, idx1, vals, *stacks, num_experts=12, share=share))
    assert not np.isfinite(got1[5]).all() or np.abs(got1[5]).max() > 1e6
    assert np.array_equal(np.delete(got1, 5, 0), np.delete(np.asarray(want), 5, 0))


def test_the_whole_share_is_the_program_every_expert_model_runs_today():
    """``share=None`` and the share that holds every expert of a router
    without zero columns lower to the same result; only the first is what
    Mixtral and Moonlight trace (their programs are unchanged byte for
    byte: PERF.md)."""
    rng = np.random.default_rng(4)
    T, D, I, E, k = 40, 32, 16, 8, 2
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
         for s in ((E, D, I), (E, D, I), (E, I, D))]
    idx = jnp.asarray(np.argsort(rng.standard_normal((T, E)), axis=1)[:, :k], jnp.int32)
    vals = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    plain = dropless_moe_ffn(x, idx, vals, *w, num_experts=E)
    shared = dropless_moe_ffn(x, idx, vals, *w, num_experts=E, share=ExpertShare(0, E, E, 0))
    assert rel_err(shared, plain) < 1e-6
    # a share on a mesh with an expert axis: its held experts split over the ranks and the
    # tokens exchanged on either side (PR 58; it raised before) - the one-device share's answer
    from deepspeed_tpu.parallel.topology import make_mesh_topology
    half = ExpertShare(0, 4, E, 0)
    held = [a[:4] for a in w]
    alone = dropless_moe_ffn(x, idx, vals, *held, num_experts=E, share=half)
    on_mesh = dropless_moe_ffn(x, idx, vals, *held, num_experts=E, share=half,
                               mesh=make_mesh_topology(expert=2, data=1, devices=jax.devices()[:2]))
    assert rel_err(on_mesh, alone) < 1e-6
    with pytest.raises(NotImplementedError, match="tensor"):
        dropless_moe_ffn(x, idx, vals, *held, num_experts=E, share=half,
                         mesh=make_mesh_topology(expert=2, tensor=2, data=1,
                                                 devices=jax.devices()[:4]))


def test_a_pick_of_minus_one_is_no_pick():
    """A padding token's picks are -1: neither held nor zero-compute, so
    the token is no row of any group and gets nothing back."""
    rng = np.random.default_rng(12)
    T, D, I = 16, 32, 16
    share = ExpertShare(first=0, held=4, routed=4, zero=2)    # every routed expert held
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    w = [jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
         for s in ((4, D, I), (4, D, I), (4, I, D))]
    idx = jnp.asarray(rng.integers(0, 6, (T, 2)), jnp.int32)
    vals = jnp.asarray(rng.uniform(0.1, 1.0, (T, 2)), jnp.float32)
    full = np.asarray(dropless_moe_ffn(x, idx, vals, *w, num_experts=6, share=share))
    padded = idx.at[3].set(-1).at[9].set(-1)
    got = np.asarray(dropless_moe_ffn(x, padded, vals, *w, num_experts=6, share=share))
    assert not got[[3, 9]].any() and np.abs(full[[3, 9]]).max() > 0.01
    assert np.array_equal(np.delete(got, [3, 9], 0), np.delete(full, [3, 9], 0))
    held, zero = share.parts(padded)
    assert not bool(held[3].any() | zero[3].any() | held[9].any() | zero[9].any())


def test_the_expert_layer_alone_is_what_a_step_computes(engine, tokens):
    """``LongcatKind.expert_layer``, the seam a check of the expert layer
    alone calls (``benchmark/runners/serve_longcat.py``): each double
    layer's ``M(x)`` with the step programs' router and share, the layer
    index traced, against the reference's expert layer."""
    cfg, params = engine.model_config, engine.params
    x = jnp.asarray(np.random.default_rng(8).standard_normal((21, cfg.hidden_size)), jnp.float32)
    alone = jax.jit(lambda p, l, x: model_runner.LongcatKind.expert_layer(p, cfg, l, x))
    outs = []
    for l in range(cfg.num_layers):
        got = alone(params, jnp.int32(l), x)
        assert rel_err(got, reference_experts(_layer_mlp(params, l), x, cfg)) < 1e-5
        outs.append(np.asarray(got))
    assert rel_err(outs[0], outs[1]) > 0.1              # each layer read its own experts


def test_padding_rows_pick_nothing_and_count_nothing(engine, tokens):
    """A step's padding tokens (the batch is padded to its bucket) are
    outside every group and outside the counts."""
    cfg = engine.model_config
    engine.put([700], [tokens[0][:11]])
    c = engine.last_step.counts
    assert set(c) == set(model_runner.LongcatKind.step_counts)
    picks = cfg.moe_topk * 11 * cfg.num_layers
    assert 0 < c["n_picks_held"] + c["n_picks_zero"] <= picks
    assert 0 < c["n_groups_live"] <= cfg.held * cfg.num_layers
    engine.flush(700)


# ------------------------------------------------------------------- tracing
def test_step_records_carry_the_device_side_counts(engine, tokens):
    """Held picks, zero picks and live held groups of a step, as the
    reference's router gives them for the same tokens, in put, burst and
    async-burst records alike; no host sync is added for them."""
    cfg = engine.model_config
    kind_counts = model_runner.LongcatKind.step_counts
    seq = tokens[1][:14]
    syncs = engine.host_syncs
    engine.put([800], [seq])
    assert engine.host_syncs - syncs == 2            # as for any model kind: pack + fetch
    got = engine.last_step.counts
    # the reference's picks, layer by layer, from its own hidden states
    want = {"n_picks_held": 0, "n_picks_zero": 0, "n_groups_live": 0, "n_share_passes": 0}
    # a pass's rows in this step's program (PR 53): this engine's share is 4 of 8 + 4 columns
    from deepspeed_tpu.ops.grouped_gemm import share_pass_rows
    rows = int(engine.last_step.program)
    cap = share_pass_rows(rows, cfg.moe_topk, ExpertShare(
        cfg.first_expert_held, cfg.held, cfg.n_routed_experts, cfg.zero_expert_num), jnp.float32)
    assert cap < rows * cfg.moe_topk            # the held picks are compacted here
    seen = []
    experts = longcat.reference_experts
    try:
        longcat.reference_experts = lambda mlp, x, c, zero=True: (
            seen.append(reference_router(mlp, x, c)[0]), experts(mlp, x, c, zero))[1]
        reference_logits(engine.params, jnp.asarray(seq)[None], cfg)    # op by op: the patch runs
    finally:
        longcat.reference_experts = experts
    for weights in seen:
        picked = np.asarray(weights[0]) > 0
        held = picked[:, cfg.first_expert_held:cfg.first_expert_held + cfg.held]
        want["n_picks_held"] += int(held.sum())
        want["n_picks_zero"] += int(picked[:, cfg.n_routed_experts:].sum())
        want["n_groups_live"] += int(held.any(axis=0).sum())
        want["n_share_passes"] += -(-int(held.sum()) // cap)
    assert len(seen) == cfg.num_layers and {name: got[name] for name in want} == want
    # ... and the blocks the latent attention's rows name (a padding row the null block)
    # and fetch: the gather, which serves here, reads whatever is named
    block = engine.kv_cache.k.shape[2]
    named = sum(p // block + 1 for p in range(14)) + int(engine.last_step.program) - 14
    assert got["n_blocks_named"] == got["n_blocks_fetched"] == named
    assert tracing.snapshot()["steps"][-1]["counts"] == got
    # tokens and counts come to the host in one device_get: every copy is started before
    # the first is waited for
    fetched = []
    get = jax.device_get
    try:
        jax.device_get = lambda tree: (fetched.append(len(jax.tree.leaves(tree))), get(tree))[1]
        engine.put([800], [tokens[1][14:15]])
    finally:
        jax.device_get = get
    assert fetched == [2] and set(engine.last_step.counts) == set(kind_counts)
    engine.decode_burst([800], [3], 4)
    burst = engine.last_step.counts
    assert engine.last_step.kind == "burst" and burst["n_picks_held"] + burst["n_picks_zero"] <= \
        cfg.moe_topk * 4 * cfg.num_layers
    handle = engine.decode_burst_async([800], [3], 2)
    assert handle._record.counts is None
    handle.fetch()
    assert engine.last_step.kind == "burst_async" and set(engine.last_step.counts) == set(kind_counts)
    engine.flush(800)
    # a kind that counts nothing leaves the field empty
    llama = InferenceEngineV2(model=build_model("debug"), config=engine_config(CASE),
                              dtype=jnp.float32)
    llama.put([1], [seq])
    assert llama.last_step.counts is None and "counts" in tracing.STEP_FIELDS


class TestServing(conformance.NotKV):
    pass
