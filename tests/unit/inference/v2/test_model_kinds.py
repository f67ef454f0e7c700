"""What a model kind is (``model_runner.ModelKind``) and what the kinds with
a biased top-k router share (``model_runner._route``): every kind answers
the whole seam, every kind is hashed by ``tools/hash_step_programs.py``,
and the one router gives each kind's float32 reference's picks and weights.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import models
from deepspeed_tpu.inference.v2 import model_runner
from deepspeed_tpu.models import lfm2, longcat, nemotron_h
from tools import hash_step_programs

PRESETS = ("debug", "mixtral-debug", "gpt2-debug", "opt-debug", "bloom-debug", "neox-debug",
           "gptj-debug", "falcon-debug", "moonlight-debug", "longcat-flash-debug",
           "minicpm-sala-debug", "nemotron-h-debug", "lfm2-debug", "jamba-debug",
           "solar-open2-debug", "laguna-debug", "ouro-debug", "granite-hybrid-debug")


def _params(preset, shapes_only=False):
    model = models.build_model(preset)

    def init():
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree.map(lambda w: w.astype(jnp.float32), params)

    return model.config, jax.eval_shape(init) if shapes_only else init()


def test_every_kind_derives_the_base_and_answers_the_whole_seam():
    """No caller has to probe a kind: the engine, the tools and
    ``ragged_forward`` read these of any of them."""
    hashed = {model_runner.kind_of(models.build_model(p).config)
              for p in hash_step_programs.PRESETS}
    assert len({k.name for k in model_runner.KINDS}) == len(model_runner.KINDS)
    for kind in model_runner.KINDS:
        assert issubclass(kind, model_runner.ModelKind) and kind.config is not None
        assert kind in hashed, f"no preset of tools/hash_step_programs.py is a {kind.name}"
    for preset in hash_step_programs.PRESETS:
        cfg, params = _params(preset, shapes_only=True)
        kind = model_runner.kind_of(cfg)
        extra = kind.extra_state(cfg, 8, 2, jnp.float32)
        assert (extra is None) == (not kind.slot_state)
        assert set(kind.slot_state) <= set(extra or ())
        assert (kind.seq_rows > 0) == (extra is not None)
        # a second pool is the engine's to lay (its arrays, its ring a sequence): the kind
        # says only that it has window layers, and keeps no state of its own kind beside it
        assert kind.window(cfg) is None or (extra is None and kind.window(cfg)[1] >= 1)
        assert (kind.window(cfg) is not None) == (kind.state_kind == "kv+window")
        if kind.seq_rows:
            assert len(kind.seq_state(cfg, 1, 40)) == kind.seq_rows
        assert all(isinstance(name, str) for name in kind.step_counts)
        assert kind.experts_form(params, None) in (None, "table", "sliced")
        assert kind.state_layers(cfg) >= 1 and len(kind.state_rows(cfg)) == 2


def test_the_hashed_presets_are_the_seventeen_in_their_order():
    """The parent's and a change's outputs of the tool must line up (a new
    kind's preset goes last: its lines are the only ones a diff shows)."""
    assert hash_step_programs.PRESETS == PRESETS


def test_a_config_of_no_kind_is_refused_by_name():
    with pytest.raises(TypeError, match="BertConfig"):
        model_runner.kind_of(models.BERT_CONFIGS["bert-debug"])


def _moonlight_router(mlp, x, cfg):
    """The router as ``models/moonlight.reference_logits`` computes it."""
    scores = jax.nn.sigmoid(x @ mlp["gate"]["weight"])
    _, chosen = jax.lax.top_k(scores + mlp["gate"]["e_score_correction_bias"],
                              cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * cfg.routed_scaling_factor
    return jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
                   * picked[..., None], axis=-2), None


# preset, where the expert layers' stack lies in params["model"], the float32 reference
ROUTERS = {
    "moonlight": ("moonlight-debug", ("layers", "mlp"), _moonlight_router),
    "longcat": ("longcat-flash-debug", ("layers", "mlp"), longcat.reference_router),
    "nemotron_h": ("nemotron-h-debug", ("moe_layers",), nemotron_h.reference_router),
    "lfm2": ("lfm2-debug", ("moe_ffn",), lfm2.reference_router),
}


@pytest.mark.parametrize("name", ROUTERS)
def test_the_one_router_gives_each_kinds_reference_picks_and_weights(name):
    """``_route`` with a kind's ``router`` values against that kind's own
    float32 reference, on its debug preset with a nonzero bias: the same
    columns picked, the same weights on them; the padding row picks none."""
    preset, path, reference = ROUTERS[name]
    cfg, params = _params(preset)
    kind = model_runner.kind_of(cfg)
    assert kind.name == name
    stack = params["model"]
    for key in path:
        stack = stack[key]
    lp = model_runner._layer_of(stack, 0)
    rng = np.random.default_rng(11)
    r = kind.router(cfg, lp)
    # a bias large enough to change the choice: the weights must not see it
    bias = jnp.asarray(rng.uniform(-0.3, 0.3, r.bias.shape), jnp.float32)
    lp = jax.tree.map(lambda w: bias if w is r.bias else w, lp)
    r = kind.router(cfg, lp)
    T = 12
    x = jnp.asarray(rng.standard_normal((T, cfg.hidden_size)), jnp.float32)
    real = jnp.arange(T) < T - 1
    with jax.default_matmul_precision("highest"):
        picks, weights = model_runner._route(x, r, real)
        want, _ = reference(lp, x, cfg)
    picks, weights, want = np.asarray(picks), np.asarray(weights), np.asarray(want)
    assert (picks[-1] == -1).all() and (picks[:-1] >= 0).all()
    got = np.zeros_like(want)
    for t in range(T - 1):
        assert len(set(picks[t])) == r.top_k
        got[t, picks[t]] = weights[t]
    np.testing.assert_array_equal(got[:-1] > 0, want[:-1] > 0)
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=1e-5, atol=1e-7)
    unbiased = np.asarray(model_runner._route(x, r._replace(bias=jnp.zeros_like(bias)))[0])
    assert (np.sort(unbiased[:-1]) != np.sort(picks[:-1])).any()        # the bias did choose


SHARE_KINDS = {"longcat-flash-debug": model_runner.LongcatKind,
               "nemotron-h-debug": model_runner.NemotronHKind,
               "solar-open2-debug": model_runner.SolarOpen2Kind,
               "laguna-debug": model_runner.LagunaKind,
               "granite-hybrid-debug": model_runner.GraniteHybridKind}


def test_the_kinds_behind_a_share_count_their_passes_and_no_other_kind_does():
    """``n_share_passes`` (PR 53: the passes a step's held picks took through
    the grouped matmul, summed over its expert layers) rides the step counts
    of the five kinds whose router has more columns than the rank holds, right
    behind ``EXPERT_COUNTS``; the share of every column (LFM2's, which says
    only that a padding row picks nothing) and the kinds without a share count
    what they counted. (That a step's record carries the number: each of the
    four kinds' own ``test_step_records_carry_...``.)"""
    for preset in PRESETS:
        kind = model_runner.kind_of(models.build_model(preset).config)
        if preset in SHARE_KINDS:
            assert kind is SHARE_KINDS[preset]
            assert kind.step_counts[:4] == model_runner.SHARE_COUNTS == \
                model_runner.EXPERT_COUNTS + ("n_share_passes",)
        else:
            assert "n_share_passes" not in kind.step_counts
    assert model_runner.Lfm2Kind.step_counts[:3] == model_runner.EXPERT_COUNTS


@pytest.mark.parametrize("every_column", [False, True])
def test_without_a_share_and_with_every_column_held_the_tail_lowers_as_before(every_column):
    """``tools/hash_step_programs.py``'s rule on the one function PR 53
    changed: ``dropless_moe_ffn(share=None)`` (Mixtral's, Moonlight's and
    training's tail) and the share that holds every column (``_routed_experts``
    builds it for LFM2's padding rows) lower to the text of the tail as it was
    before the held picks were compacted, transcribed here - every pick a
    row, the take and the ``tk,tkd->td`` sum over all of them."""
    from deepspeed_tpu.ops.grouped_gemm import (ExpertShare, dropless_moe_ffn, expert_share_ffn,
                                                moe_grouped_mlp)
    T, k, D, F, E = 32, 3, 64, 128, 8
    share = ExpertShare(0, E, E) if every_column else None

    def before(x, idx, vals, w1, w3, w2, first):
        live = rows = None
        if share is not None:
            held, _ = share.parts(idx)
            live, rows = held.reshape(-1), max(1, T * k // (share.routed + share.zero))
            idx, vals = idx - share.first, jnp.where(held, vals, 0)
        idx_rep = idx.reshape(-1)
        out_rep = moe_grouped_mlp(jnp.repeat(x, k, axis=0), idx_rep, w1.astype(x.dtype),
                                  w3.astype(x.dtype), w2.astype(x.dtype), num_experts=E,
                                  activation=jax.nn.silu, first_group=first, live=live,
                                  rows_a_group=rows)
        return jnp.einsum("tk,tkd->td", vals.astype(x.dtype), out_rep.reshape(T, k, -1))

    def tail(x, idx, vals, w1, w3, w2, first):
        if share is None:
            return dropless_moe_ffn(x, idx, vals, w1, w3, w2, num_experts=E, first_group=first,
                                    widen_boundary=False)
        return expert_share_ffn(x, idx, vals, w1, w3, w2, share, first_group=first)[0]

    sds = jax.ShapeDtypeStruct
    args = (sds((T, D), jnp.float32), sds((T, k), jnp.int32), sds((T, k), jnp.float32),
            sds((2 * E, D, F), jnp.float32), sds((2 * E, D, F), jnp.float32),
            sds((2 * E, F, D), jnp.float32), sds((), jnp.int32))
    texts = [jax.jit(fn).lower(*args).as_text().replace(fn.__name__, "fn")
             for fn in (before, tail)]
    assert texts[0] == texts[1] and "stablehlo.dot_general" in texts[0]
