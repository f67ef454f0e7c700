"""Mellum 2 on the training path, held to ``models/mellum.py``'s plain float32
reference at ``mellum2-debug`` (two periods of 3 window + 1 full attention
layers, 8 experts top-2): the trainer on one device and on a mesh
``expert=4`` under ZeRO-2, the expert share's exchange, the windowed flash
kernels and the tables of positions."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import mellum
from deepspeed_tpu.models.llama import FULL, SLIDING, einsum_attention, rope_frequencies, rope_scaling_of
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import make_mesh_topology
from deepspeed_tpu.utils import tracing

CFG = mellum.MELLUM_CONFIGS["mellum2-debug"]
IDS = np.random.default_rng(7).integers(0, CFG.vocab_size, (4, 32)).astype(np.int32)


def _engine(mesh_axes, bf16=False):
    """The trainer over ``mellum2-debug`` on seed 3's weights."""
    groups.destroy_mesh()
    devices = jax.devices()[:4 if mesh_axes else 1]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=mellum.build_mellum(CFG, remat=True, remat_policy="moe"),
        model_parameters=mellum.seeded_params(CFG, 3),
        mesh=make_mesh_topology(devices=devices, **mesh_axes),
        config={"train_batch_size": 4, "train_micro_batch_size_per_gpu": 4,
                "gradient_accumulation_steps": 1, "bf16": {"enabled": bf16},
                "optimizer": {"type": "Adam", "params": {"lr": 3e-4}},
                "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 9})
    engine._materialize_state(IDS, IDS)     # parameters and state now, not at the first step
    return engine


def _nll(engine, ids):
    """Per-position NLL of the training forward (its logits: 32 positions are
    one loss chunk) on the engine's parameters, under its mesh."""
    logits = jax.jit(lambda p: engine.module.apply({"params": p}, ids))(engine.params)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, jnp.asarray(ids)[:, 1:, None], axis=-1)[..., 0]


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


@pytest.fixture(scope="module")
def reference():
    """The reference's loss, per-position NLL and gradient on the weights every
    engine of this file starts from."""
    params = _host(mellum.seeded_params(CFG, 3))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: mellum.reference_loss(p, jnp.asarray(IDS), CFG)))(params)
    nll, _ = jax.jit(lambda p: mellum.reference_nll(p, jnp.asarray(IDS), CFG))(params)
    return params, float(loss), np.asarray(nll), _host(grads)


@pytest.mark.parametrize("mesh_axes", [{}, {"expert": 4}], ids=["one_device", "expert4_zero2"])
def test_trainer_agrees_with_the_reference(reference, mesh_axes):
    """(a), (b): loss, per-position NLL and every leaf's gradient of the
    trainer's float32 step against the reference's, then two optimizer steps
    whose second loss is the reference's on the weights it starts from. On
    ``expert=4`` the experts' stacks stay on their rank, the rest is ZeRO-2
    over the four, and the step record carries the exchange's counts.

    Tolerances: float32 everywhere, the same mathematics in another order
    (flash-free einsum attention, the grouped matmul, the exchange's
    reduce-scatter): 2e-5 on a loss of ~6, 2e-4 on an NLL, a gradient leaf
    within 1e-4 of its largest entry (+1e-7). A window taken as full, a pick
    left out or an unnormalised weight moves each by 1e-2 and more."""
    params, want_loss, want_nll, want_grads = reference
    engine = _engine(mesh_axes)
    try:
        for a, b in zip(jax.tree.leaves(_host(engine.params)), jax.tree.leaves(params)):
            np.testing.assert_array_equal(a, b)           # the same seeded weights
        loss = engine(IDS, IDS)
        engine.backward(loss)
        grads = _host(engine._grads_acc)
        engine.zero_grad()
        assert abs(float(loss) - want_loss) < 2e-5
        np.testing.assert_allclose(np.asarray(_nll(engine, IDS)), want_nll, atol=2e-4)
        for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                     jax.tree.leaves(want_grads)):
            np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max() + 1e-7,
                                       err_msg=jax.tree_util.keystr(path))
        first = float(engine.train_batch(batch=(IDS[None], IDS[None])))
        assert abs(first - want_loss) < 2e-5
        stepped = _host(engine.params)
        second = float(engine.train_batch(batch=(IDS[None], IDS[None])))
        want_second = float(jax.jit(
            lambda p: mellum.reference_loss(p, jnp.asarray(IDS), CFG))(stepped))
        assert abs(second - want_second) < 2e-5 and second < first
        counts = [s["counts"] for s in tracing.snapshot()["steps"]
                  if s["kind"] == "train" and s["engine"] == engine.trace_id][-1]
        if mesh_axes:
            layers, picks = CFG.num_hidden_layers, IDS.size * CFG.num_experts_per_tok
            assert counts["n_expert_rows"] == layers * picks
            assert counts["n_share_passes"] == layers and counts["rows_beyond_passes"] == 0
            assert picks // 4 <= counts["expert_rows_max_rank"] <= picks
            spec = engine._param_specs["model"]["layers"]["moe_mlp"]["deepspeed_moe"]
            assert "expert" in spec["experts_w1"] and "expert" not in spec["gate"]["wg"]["kernel"]
            opt = engine._opt_specs["model"]["layers"]["self_attn"]["q_proj"]["kernel"]
            assert "expert" in jax.tree.leaves(tuple(opt))    # ZeRO over the expert axis too
        else:
            assert counts is None
    finally:
        engine.destroy()
        groups.destroy_mesh()


def test_bf16_expert4_is_the_reference_to_rounding(reference):
    """The job's own precision on ``expert=4`` (bf16 parameters and
    activations, the exchange in bf16; fp32 master, moments, router and loss):
    the first step's loss is the float32 reference's within 2e-2 - bf16's 3
    significant digits on a loss of ~6 through 8 layers, the dense cell's
    tolerance likewise - and three steps fall."""
    engine = _engine({"expert": 4}, bf16=True)
    try:
        losses = [float(engine.train_batch(batch=(IDS[None], IDS[None]))) for _ in range(3)]
    finally:
        engine.destroy()
        groups.destroy_mesh()
    assert abs(losses[0] - reference[1]) < 2e-2
    assert losses[2] < losses[1] < losses[0]


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, a kernel's own
    (its blocks are no arrays of the program) left out → (its jaxpr, the equation)."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for value in () if eqn.primitive.name == "pallas_call" else eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _pass_loops(jaxpr, shapes):
    """The exchange's two loops over passes in a differentiated step's jaxpr,
    forward then backward, each as (grouped products, stacks' gradients by the
    kernel's name, the float32 arrays of ``shapes`` its body makes by
    ``broadcast_in_dim`` or adds, the primitives that made its carry's arrays
    of ``shapes``)."""
    def big(var):
        aval = getattr(var, "aval", None)
        return aval is not None and aval.dtype == jnp.float32 and aval.shape in shapes

    loops = []
    for outer, eqn in _eqns(jaxpr):
        if eqn.primitive.name != "while":
            continue
        inside = [e for _, e in _eqns(eqn.params["body_jaxpr"].jaxpr)]
        named = lambda e, name: e.primitive.name == "pallas_call" and e.params["name"] == name
        products = sum(e.primitive.name == "ragged_dot_general" or named(e, "gmm_ragged_dot")
                       for e in inside)
        if not products:
            continue            # a loop of another part of the program
        made_by = {v: e.primitive.name for e in outer.eqns for v in e.outvars}
        loops.append((
            products, sum(named(e, "gmm_dw") for e in inside),
            [e.primitive.name for e in inside
             if e.primitive.name in ("broadcast_in_dim", "add", "add_any") and big(e.outvars[0])],
            [made_by.get(v) for v in eqn.invars[eqn.params["cond_nconsts"]
                                                + eqn.params["body_nconsts"]:] if big(v)]))
    return loops


@pytest.mark.parametrize("row_kernels,act", [(False, "silu"), (True, "silu"), (False, "gelu")],
                         ids=["jnp_rows", "row_kernels", "jnp_rows_gated_gelu"])
def test_shares_add_up_to_the_uncut_layer_and_no_pick_is_lost(monkeypatch, row_kernels, act):
    """(c): one expert layer under ``expert=4``. What rank ``r``'s two experts
    add, by the reference's loop over experts, summed over the four ranks is
    the reference's uncut layer, and the exchange's output is that sum
    (float32: 1e-5), its gradients in the rows, the picks' weights and the
    three stacks those of the plain sum over experts. With the pass's margin
    taken away and a router that sends every pick to one rank, that rank runs
    four passes where an even router takes one, and output and gradients are
    still exact: a pass has a static size, their number is the router's.
    ``row_kernels``: once with the rows moved by the ``jnp`` forms (this
    backend's choice), once through the two Pallas row kernels and the Pallas
    grouped matmul, interpreted - the path a TPU takes. ``act``: the
    configuration's SiLU, and a gated GELU (the written backward pulls the
    cotangent through whatever ``activation`` it is handed).

    **The backward as it is written** (its jaxpr): a pass multiplies three
    grouped products and the stacks' gradients in two (the derived transpose:
    six and three) - ``y = h w2`` is not made again, ``w1`` and ``w3`` stand
    side by side -, its loop is handed float32 ``[T_a, D]`` and ``[held, K, N]``
    arrays that nobody filled (``jax.lax.empty``) and no array of zeros, and
    through the kernels its body neither makes nor adds one: the first pass
    writes, a later one adds where it is written."""
    from deepspeed_tpu.ops import grouped_gemm as gg
    monkeypatch.setattr(gg, "FORCE_INTERPRET", row_kernels)
    params = _host(mellum.seeded_params(CFG, 11))
    p = mellum.layer_params(params, CFG, 3)
    h = jnp.asarray(np.random.default_rng(3).standard_normal((4, 32, CFG.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = mellum.reference_experts(p, h, CFG)
        shares = [mellum.reference_experts(p, h, CFG, experts=range(2 * r, 2 * r + 2))[0]
                  for r in range(4)]
        np.testing.assert_allclose(sum(shares), whole, atol=1e-5)
        m = mellum._rms(h, p["post_attention_layernorm"]["scale"], CFG.rms_norm_eps)
        flat = m.reshape(-1, CFG.hidden_size)
        _, picks, weights = mellum.reference_route(p, flat, CFG)
    moe = p["moe_mlp"]["deepspeed_moe"]
    stacks = tuple(moe[name] for name in ("experts_w1", "experts_w3", "experts_w2"))
    mesh = make_mesh_topology(expert=4, devices=jax.devices()[:4])
    share = gg.ExpertShare(0, CFG.num_experts, CFG.num_experts)
    activation = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]

    def exchange(picks):
        def loss(x, w, *stacks):
            out, counts = jax.jit(lambda *a: gg.expert_share_exchange_ffn(
                *a, share, mesh, activation=activation))(x, picks, w, *stacks)
            return jnp.sum(out * jnp.cos(out)), (out, counts)
        return loss

    def plain(picks):
        def loss(x, w, w1, w3, w2):
            each = jnp.stack([(activation(x @ w1[e]) * (x @ w3[e])) @ w2[e]
                              for e in range(CFG.num_experts)])
            out = sum(w[:, j:j + 1] * each[picks[:, j], jnp.arange(x.shape[0])]
                      for j in range(picks.shape[1]))
            return jnp.sum(out * jnp.cos(out)), (out, None)
        return loss

    def both(picks):
        """→ the exchange's output and counts, after holding output and the five
        gradients to the plain sum's."""
        (_, (out, counts)), grads = jax.value_and_grad(
            exchange(picks), argnums=range(5), has_aux=True)(flat, weights, *stacks)
        with jax.default_matmul_precision("highest"):
            (_, (want, _)), want_grads = jax.value_and_grad(
                plain(picks), argnums=range(5), has_aux=True)(flat, weights, *stacks)
        np.testing.assert_allclose(out, want, atol=1e-5)
        for name, got, wanted in zip(("x", "topk_vals", "w1", "w3", "w2"), grads, want_grads):
            np.testing.assert_allclose(got, wanted, atol=1e-5 + 1e-5 * np.abs(wanted).max(),
                                       err_msg=name)
        # every held pick's row went through each of the two kernels once, and no padding
        assert (counts[..., 2] == 2 * counts[..., 0] * row_kernels).all()
        return out, counts

    out, counts = both(picks)
    if act == "silu":
        np.testing.assert_allclose(out.reshape(whole.shape), whole, atol=1e-5)
    assert int(counts[..., 0].sum()) == picks.size and counts[0, :, 1].tolist() == [1, 1, 1, 1]
    for r in range(4):      # a rank's count is the picks of its two experts
        assert int(counts[0, r, 0]) == int(((picks >= 2 * r) & (picks < 2 * r + 2)).sum())

    monkeypatch.setattr(gg, "MESH_SHARE_MARGIN", 1.0)
    monkeypatch.setattr(gg, "MESH_SHARE_SMALL", 0)      # the margin's rule at this size too
    crowded = jnp.stack([jnp.zeros_like(picks[:, 0]), jnp.ones_like(picks[:, 0])], axis=1)
    _, counts = both(crowded)                            # the backward walks the four passes too
    rows = gg.mesh_share_rows(picks.shape[0], 2, share, 4, jnp.float32)
    assert rows == picks.size // 4                       # an even router's share, no room
    assert counts[0, :, 0].tolist() == [picks.size, 0, 0, 0]
    assert counts[0, :, 1].tolist() == [4, 0, 0, 0]      # four passes on the one rank, none lost

    (held, K, N), Ta = (d // 4 if i == 0 else d for i, d in enumerate(stacks[0].shape)), len(flat)
    forward, backward = _pass_loops(jax.make_jaxpr(jax.grad(
        lambda *a: exchange(picks)(*a)[0], argnums=range(5)))(flat, weights, *stacks).jaxpr,
        {(Ta, K), (held, K, N), (held, N, K), (held, K, 2 * N)})
    # g | u, dhu and the rows' cotangent (w1 and w3 side by side: one product each way), then
    # dw2 and dw1 | dw3 - grouped products like the others where ragged_dot multiplies
    assert forward[:2] == ((3, 0)) and backward[:2] == ((3, 2) if row_kernels else (5, 0))
    assert forward[3] == ["empty"] and backward[3] == ["empty"] * 3
    if row_kernels:
        assert forward[2] == [] and backward[2] == []
    else:       # the ``jnp`` forms add a pass to what stands, and make no zeros either
        assert "broadcast_in_dim" not in forward[2] + backward[2]


def _masked_softmax(q, k, v, window, seg):
    """Attention as a mask, float32: [B, S, H, D]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(q.shape[1])[None, :]
    seen = (j <= i) & (j > i - window)
    if seg is not None:
        seen = seen[None] & (seg[:, :, None] == seg[:, None, :])
        seen = seen[:, None]
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("seq,window,block_q,block_k,segments", [
    (64, 8, 16, 16, False),         # smaller than the block
    (64, 16, 16, 16, True),         # the block
    (64, 24, 16, 16, True),         # no multiple of it
    (60, 20, 16, 32, True),         # a padded sequence, unequal blocks
    (64, 64, 16, 16, False),        # the whole sequence: the causal kernel's answer
])
def test_windowed_flash_kernels_against_a_masked_softmax(seq, window, block_q, block_k, segments):
    """(d): ``flash_window_fwd`` / ``_dkv`` / ``_dq``, interpreted, forward and
    the gradients of q, k and v: float32 inputs, so 5e-6 (the online softmax's
    other order of sums)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, flash_schedule
    rng = np.random.default_rng(seq + window)
    q, k, v = (jnp.asarray(rng.standard_normal((2, seq, 2, 32)), jnp.float32) for _ in range(3))
    seg = jnp.asarray(np.sort(rng.integers(0, 3, (2, seq)), axis=1), jnp.int32) if segments else None

    def kernels(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window, block_q=block_q,
                              block_k=block_k, segment_ids=seg, interpret=True, force_pallas=True)
        return jnp.sum(out * jnp.cos(out)), out

    def plain(q, k, v):
        out = _masked_softmax(q, k, v, window, seg)
        return jnp.sum(out * jnp.cos(out)), out

    (_, got), got_grads = jax.value_and_grad(kernels, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=5e-6)
    band, causal = (flash_schedule(seq, w, True, block_q, block_k) for w in (window, None))
    assert band["tiles"] <= causal["tiles"] and band["needed"] <= causal["needed"]
    if window == seq:
        full = flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k,
                               segment_ids=seg, interpret=True, force_pallas=True)
        np.testing.assert_allclose(got, full, atol=1e-6)


def test_the_cell_walks_15_of_36_block_pairs():
    """... and inside them the backward kernels compute 1.5 times the band's
    pairs in pieces of 512, two thirds of that under a mask; the forward walks
    a block a piece, all fifteen crossed: twice the pairs, all masked."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_schedule
    band, full = flash_schedule(8192, 1024), flash_schedule(8192)
    assert (band["tiles"], full["tiles"]) == (15, 36)
    assert band["pairs"] == {"skipped": 49 * 1024 * 1024, "whole": 0, "crossed": 15 * 1024 * 1024}
    assert band["needed"] == 1024 * 1025 // 2 + 7168 * 1024
    back = flash_schedule(8192, 1024, backward=True)
    assert back["pairs"] == {"skipped": (256 - 45) * 512 * 512, "whole": 15 * 512 * 512,
                             "crossed": 30 * 512 * 512}
    assert round(back["computed_over_needed"], 2) == 1.5
    assert round(band["computed_over_needed"], 2) == 2.0


def test_positions_by_kind_and_a_window_of_the_whole_sequence():
    """(e): the full layers' table is YaRN's (``yarn_inv_freq``, cos and sin
    times the attention factor), the sliding layers' the plain one; and a
    sliding layer whose window is the whole sequence is a full layer's
    attention (float32: equal to 1e-6)."""
    from deepspeed_tpu.models.laguna import yarn_inv_freq
    llama = CFG.to_llama()
    assert llama.layer_kinds == (SLIDING, SLIDING, SLIDING, FULL) * 2
    rope = CFG.rope_parameters[FULL]
    inv = yarn_inv_freq(CFG.head_dim, rope["rope_theta"], rope["factor"],
                        rope["original_max_position_embeddings"], rope["beta_fast"],
                        rope["beta_slow"])
    angles = np.outer(np.arange(64, dtype=np.float32), inv)
    cos, sin = rope_frequencies(CFG.head_dim, 64, llama.rope_theta, rope_scaling_of(llama, FULL))
    np.testing.assert_allclose(cos, np.cos(angles) * rope["attention_factor"], rtol=1e-6)
    np.testing.assert_allclose(sin, np.sin(angles) * rope["attention_factor"], rtol=1e-6, atol=1e-7)
    assert rope["attention_factor"] == pytest.approx(0.1 * math.log(rope["factor"]) + 1)
    cos, _ = rope_frequencies(CFG.head_dim, 64, llama.rope_theta, rope_scaling_of(llama, SLIDING))
    plain = 1.0 / (500000.0 ** (np.arange(0, CFG.head_dim, 2, dtype=np.float32) / CFG.head_dim))
    np.testing.assert_allclose(cos, np.cos(np.outer(np.arange(64, dtype=np.float32), plain)),
                               rtol=1e-6)
    for got, want in zip(mellum.rope_tables(CFG, FULL, 64),
                         rope_frequencies(CFG.head_dim, 64, llama.rope_theta,
                                          rope_scaling_of(llama, FULL))):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 32, 4, 32)), jnp.float32) for _ in range(3))
    np.testing.assert_allclose(einsum_attention(q, k, v, window=32), einsum_attention(q, k, v),
                               atol=1e-6)
    assert float(jnp.abs(einsum_attention(q, k, v, window=8) - einsum_attention(q, k, v)).max()) > 1e-2


def test_what_the_configuration_cannot_honour_is_refused_by_name():
    for setting, name in ((dict(attention_bias=True), "attention_bias"),
                          (dict(mlp_layer_types=("dense",) + ("sparse",) * 27), "mlp_layer_types"),
                          (dict(norm_topk_prob=False), "norm_topk_prob"),
                          (dict(use_sliding_window=False), "use_sliding_window"),
                          (dict(tie_word_embeddings=True), "tie_word_embeddings")):
        with pytest.raises(ValueError, match=name):
            mellum.MellumConfig(**setting)
    published = mellum.MELLUM_CONFIGS["mellum2-12b"]
    assert published.layer_types == ((SLIDING,) * 3 + (FULL,)) * 7
    llama = published.to_llama()
    assert (llama.moe_num_experts, llama.moe_top_k, llama.moe_intermediate_size) == (64, 8, 896)
    assert llama.sliding_window == 1024 and llama.head_dim == 128 and not llama.moe_drop_tokens
