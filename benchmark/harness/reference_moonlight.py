"""The plain reference of the ``moonlight-16b-a3b`` configuration
(``model_type: deepseek_v3``): a pre-norm decoder whose attention is
multi-head latent attention, whose first ``first_k_dense_replace``
layers have a gated (SwiGLU) feed-forward and whose other layers have
``n_routed_experts`` small routed experts beside shared ones behind a
sigmoid ``noaux_tc`` router. Equations: DeepSeek-V2 (arXiv 2405.04434,
section 2.1), DeepSeek-V3 (arXiv 2412.19437, section 2.1.2), and
``modeling_deepseek.py`` of the source.

Like ``harness/reference.py`` it is straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``, independent
of ``deepspeed_tpu``, with no kernel and no cache, reading the sizes
from the configuration's file and the weights as a tree of arrays; a
layer's bf16 weights are upcast inside that layer's program, one expert
at a time, so that float32 copies of at most one layer's attention and
one expert exist.

It **expands** where the served program absorbs: ``kv_b_proj`` is applied
to the normalised compressed row to give every head its own 128-wide key
part and value, the rotated 64-wide key part is broadcast to the heads,
and ordinary causal softmax attention runs over 192-wide queries and
keys. The served program never forms those keys and values.

Weight tree (``deepspeed_tpu/models/moonlight.py`` documents it; matrices
are ``[in, out]``, stacked over the layers of their group)::

    model/embed_tokens   model/norm/scale   lm_head/kernel
    model/{dense_layers,layers}/{input,post_attention}_layernorm/scale
    model/{dense_layers,layers}/self_attn/{q_proj,kv_a_proj_with_mqa,kv_b_proj,o_proj}/kernel
    model/{dense_layers,layers}/self_attn/kv_a_layernorm/scale
    model/dense_layers/mlp/{gate,up,down}_proj/kernel
    model/layers/mlp/gate/{weight,e_score_correction_bias}
    model/layers/mlp/experts/{gate,up,down}_proj [L, E, in, out]
    model/layers/mlp/shared_experts/{gate,up,down}_proj/kernel
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512  # attention scores are formed for this many queries at a time
HEAD_COLUMNS = 32768  # logits_at applies the head to this many vocabulary entries at a time


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, H, d]. ``apply_rotary_pos_emb`` of the source: the pairs
    (2i, 2i+1) are first moved to (i, i + d/2), then rotated by halves."""
    S, d = x.shape[1], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rotated * sin


def _causal_attention(q, k, v, scale):
    """[B, S, H, dq] x [B, S, H, dq] x [B, S, H, dv], a block of queries at a time."""
    S = q.shape[1]
    key_pos = jnp.arange(S)
    out = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        visible = key_pos[None, :] <= (start + jnp.arange(qb.shape[1]))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def _attention(lp, h, *, heads, rank, nope, rope, vdim, eps, theta):
    B, S, _ = h.shape
    a = lp["self_attn"]
    x = _rms_norm(h, lp["input_layernorm"]["scale"].astype(F32), eps)
    q = (x @ a["q_proj"]["kernel"].astype(F32)).reshape(B, S, heads, nope + rope)
    kv_a = x @ a["kv_a_proj_with_mqa"]["kernel"].astype(F32)
    c_kv = _rms_norm(kv_a[..., :rank], a["kv_a_layernorm"]["scale"].astype(F32), eps)
    kv = (c_kv @ a["kv_b_proj"]["kernel"].astype(F32)).reshape(B, S, heads, nope + vdim)
    k_rope = _rope(kv_a[..., None, rank:], theta)                       # [B, S, 1, rope]
    queries = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, heads, rope))],
                           axis=-1)
    out = _causal_attention(queries, keys, kv[..., nope:], 1.0 / math.sqrt(nope + rope))
    return h + out.reshape(B, S, heads * vdim) @ a["o_proj"]["kernel"].astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


def _router(x, gate, top_k, scaling):
    """→ (weights [B, S, E], margin [B, S]): sigmoid scores; the top k
    chosen on score + bias; the chosen weighted by their *unbiased* scores,
    normalised to sum to one, times ``routed_scaling_factor``; zero
    elsewhere. ``margin``: by how much the last expert chosen leads the
    first one left out, in score + bias - what a perturbation of the scores
    has to exceed to change the chosen set."""
    scores = jax.nn.sigmoid(x @ gate["weight"].astype(F32))
    ranked, chosen = jax.lax.top_k(scores + gate["e_score_correction_bias"].astype(F32),
                                   top_k + 1)
    chosen = chosen[..., :top_k]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scaling
    weights = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32) * picked[..., None],
                      axis=-2)
    return weights, ranked[..., top_k - 1] - ranked[..., top_k]


@functools.partial(jax.jit, static_argnames=("heads", "rank", "nope", "rope", "vdim", "eps",
                                             "theta"))
def _dense_layer(layers, layer, h, **kw):
    """One leading layer; ``layers`` is the stacked tree, ``layer`` its index."""
    lp = jax.tree.map(lambda x: x[layer], layers)
    h = _attention(lp, h, **kw)
    x = _rms_norm(h, lp["post_attention_layernorm"]["scale"].astype(F32), kw["eps"])
    m = lp["mlp"]
    return h + _swiglu(x, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                       m["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("heads", "rank", "nope", "rope", "vdim", "eps",
                                             "theta", "top_k", "scaling", "router"))
def _expert_layer(layers, layer, h, *, top_k, scaling, router=_router, **kw):
    """One expert layer → (h, the router's margin [B, S]): every routed
    expert is applied to every token, one expert at a time, and weighted
    (zero where the router did not choose it); the shared experts are
    applied to every token. ``router``: :func:`_router`, or a control's
    (``benchmark/tests/control_moonlight.py``)."""
    experts = layers["mlp"]["experts"]      # [L, E, in, out]: one expert is read at a time
    lp = jax.tree.map(lambda x: x[layer], {
        **{k: v for k, v in layers.items() if k != "mlp"},
        "mlp": {k: v for k, v in layers["mlp"].items() if k != "experts"}})
    h = _attention(lp, h, **kw)
    x = _rms_norm(h, lp["post_attention_layernorm"]["scale"].astype(F32), kw["eps"])
    weights, margin = router(x, lp["mlp"]["gate"], top_k, scaling)

    def one(acc, e):
        out = _swiglu(x, experts["gate_proj"][layer, e], experts["up_proj"][layer, e],
                      experts["down_proj"][layer, e])
        return acc + out * jnp.take(weights, e, axis=-1)[..., None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(weights.shape[-1]))
    sh = lp["mlp"]["shared_experts"]
    return h + routed + _swiglu(x, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                                sh["down_proj"]["kernel"]), margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, h, *, eps):
    h = _rms_norm(h, params["model"]["norm"]["scale"].astype(F32), eps)
    return h @ params["lm_head"]["kernel"].astype(F32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


def layer_kwargs(model):
    """The configuration file's ``model`` → the static sizes of a layer."""
    for key in ("n_group", "topk_group"):
        if model.get(key, 1) != 1:
            raise ValueError(f"{key} = {model[key]}: group-limited routing is not in this reference")
    if model.get("q_lora_rank") is not None or model.get("rope_scaling") is not None:
        raise ValueError("q_lora_rank and rope_scaling are not in this reference")
    if model.get("scoring_func") != "sigmoid" or model.get("topk_method") != "noaux_tc" \
            or not model.get("norm_topk_prob"):
        raise ValueError("this reference routes sigmoid / noaux_tc / norm_topk_prob only")
    attn = dict(heads=model["num_attention_heads"], rank=model["kv_lora_rank"],
                nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
                vdim=model["v_head_dim"], eps=float(model["rms_norm_eps"]),
                theta=float(model["rope_theta"]))
    moe = dict(attn, top_k=int(model["num_experts_per_tok"]),
               scaling=float(model["routed_scaling_factor"]))
    return attn, moe


def hidden(params, ids, model):
    """ids [B, S] → (the last layer's output [B, S, D], float32, and the
    router margins [expert layers, B, S])."""
    attn, moe = layer_kwargs(model)
    n_dense = int(model["first_k_dense_replace"])
    margins = []
    with jax.default_matmul_precision("highest"):
        h = _embed(params["model"]["embed_tokens"], ids)
        for i in range(n_dense):
            h = _dense_layer(params["model"]["dense_layers"], jnp.int32(i), h, **attn)
        for i in range(int(model["num_hidden_layers"]) - n_dense):
            h, margin = _expert_layer(params["model"]["layers"], jnp.int32(i), h, **moe)
            margins.append(margin)
    return h, jnp.stack(margins)


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    kernel = params["lm_head"]["kernel"]
    with jax.default_matmul_precision("highest"):
        # a slice of the head at a time: all of it in float32 would be 1.3 GB
        return jnp.concatenate([
            _head({"model": {"norm": params["model"]["norm"]},
                   "lm_head": {"kernel": kernel[:, start:start + HEAD_COLUMNS]}},
                  rows, eps=float(model["rms_norm_eps"]))
            for start in range(0, kernel.shape[1], HEAD_COLUMNS)], axis=-1)


def rows_at(params, ids, positions, model):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D], the router margins there [expert layers, B, n])."""
    h, margins = hidden(params, ids, model)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.take_along_axis(margins, positions[None], axis=2))


def logits_at(params, ids, positions, model):
    """→ (next-token logits [B, n, V] at ``positions`` only: the head (2048
    x 163840 here) is applied to the compared rows, not to every position;
    the router margins there)."""
    rows, margins = rows_at(params, ids, positions, model)
    return head_at(params, rows, model), margins


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden(params, ids, model)[0], eps=float(model["rms_norm_eps"]))
