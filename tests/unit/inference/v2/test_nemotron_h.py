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

from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.models import NEMOTRON_H_CONFIGS
from deepspeed_tpu.models.moonlight import _rms_norm
from deepspeed_tpu.models.nemotron_h import (PUBLISHED_PATTERN, NemotronHConfig, layer_params,
                                             param_shapes, reference_experts, reference_logits,
                                             reference_mamba, reference_router)

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Refused, count, rel_err, slot_batch, two_prompts,
                                     two_pool_subsystems, two_sequences)

DEBUG = NEMOTRON_H_CONFIGS["nemotron-h-debug"]
KIND = model_runner.NemotronHKind
LM, LE = DEBUG.count("M"), DEBUG.count("E")

CASE = Case(
    preset="nemotron-h-debug",
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    refused=tuple(Refused(*row) for row in (
        ("hybrid_override_pattern", "EM-M", None, {"num_hidden_layers": 4}), ("n_group", 2),
        ("topk_group", 2), ("norm_topk_prob", False), ("attention_bias", True),
        ("mamba_proj_bias", True), ("mlp_bias", True), ("use_conv_bias", False),
        ("tie_word_embeddings", True), ("mamba_hidden_act", "gelu"), ("mlp_hidden_act", "silu"),
        ("expand", 4), ("n_shared_experts", 2))),
    prefill=((20, 6, [20]), (75, 5, [32, 32, 11]), (100, 4, [7, 32, 32, 29]), (3, 8, [2, 1])),
    # the sequences with further rows go through the rounds, the others through the
    # all-at-once path, in one program
    plans={"two_prompts_in_one_chunk": two_prompts()},
    burst=Burst(1, 0, 80, (8, 8), {"n_ssm_rows": 8 * LM, "n_state_slots": 8 * LM}),
    # every expert held: a pass a layer
    records=two_sequences({"n_ssm_rows": 29 * LM, "n_state_slots": 2 * LM, "n_picks_zero": 0,
                           "n_picks_held": (1, 29 * DEBUG.num_experts_per_tok * LE),
                           "n_groups_live": (1, DEBUG.held * LE), "n_share_passes": LE}),
    step_counts=("n_picks_held", "n_picks_zero", "n_groups_live", "n_share_passes", "n_ssm_rows",
                 "n_state_slots"),
    scopes=("ds.nemotron.mamba", "ds.nemotron.attn", "ds.nemotron.latent_moe", "ds.moe_routed",
            "ds.moe_shared"),
    subsystems=two_pool_subsystems("expert_parallel_degree"),
    # a slot: a layer's state a head and the convolution's K - 1 rows, float32
    state_extra=("ssm", "conv"), state_step="pallas_ssm_state",
    slot_bytes=LM * 4 * (DEBUG.mamba_num_heads * DEBUG.mamba_head_dim * DEBUG.ssm_state_size
                         + (DEBUG.conv_kernel - 1) * DEBUG.conv_dim),
    kernel_tests=(
        "test_a_slot_is_reused_with_its_stale_state_and_the_next_owner_starts_from_zero",))
TOL = CASE.tol


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
    assert 120.6e9 < count(param_shapes(whole)) < 120.8e9   # the published "120B": 120.67 B
    assert count(param_shapes(cut)) == 4648163712   # ISSUE 38's count: 4.648 B


def test_a_share_outside_the_routers_columns_is_refused():
    with pytest.raises(ValueError, match="not among"):
        dataclasses.replace(DEBUG, experts_held=6, first_expert_held=4)


# --------------------------------------------------------- the pieces alone
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
                                        slot_batch([(0, at, n)], 2, [2]))
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
    batch = slot_batch([(i, b, n) for i, (b, n) in enumerate(zip(before, now))], 7, slots)
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
    batch = slot_batch([(r, b, n) for r, b, n in zip(rows_of_batch, before, now)]
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


class TestServing(conformance.Slots, conformance.NotKV):
    def recorded(self, engine, tokens):
        """The first expert layer reads the embedding alone: its picks are the reference's."""
        cfg = engine.model_config
        x = engine.params["model"]["embed_tokens"][jnp.concatenate([tokens[2][:20], tokens[3][:9]])]
        first = layer_params(engine.params, cfg, 0)
        weights, _ = reference_router(first, _rms_norm(x, first["norm"]["scale"],
                                                       cfg.layer_norm_epsilon), cfg)
        assert int((np.asarray(weights) > 0).sum()) == 29 * cfg.num_experts_per_tok
