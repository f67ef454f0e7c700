"""The plain reference of the block that every configuration here uses:
a pre-norm decoder layer with rotary grouped-query attention and either
a gated (SwiGLU) feed-forward or a top-k mixture of such feed-forwards
(Mistral-7B and Mixtral-8x7B as published).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes): no kernels, no cache, no batching of
ragged sequences. It is independent of ``deepspeed_tpu``: it reads the
sizes from the configuration's file and the weights as a tree of arrays,
applied layer by layer with that layer's bf16 weights upcast, so that
at most one layer (one expert, for the mixture) exists in float32.

Weight tree (the names the system's checkpoints use)::

    model/embed_tokens [V, D]      model/norm/scale [D]      lm_head/kernel [D, V]
    model/layers/{input,post_attention}_layernorm/scale [L, D]
    model/layers/self_attn/{q,k,v,o}_proj/kernel [L, in, out]
    model/layers/mlp/{gate,up,down}_proj/kernel [L, in, out]                  (dense)
    model/layers/moe_mlp/deepspeed_moe/gate/wg/kernel [L, D, E]               (mixture)
    model/layers/moe_mlp/deepspeed_moe/experts_w{1,3,2} [L, E, in, out]       (gate, up, down)
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 1024  # attention scores are formed for this many queries at a time


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [B, S, H, Dh]; rotate-half convention (first half with second)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]          # [S, half]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention, [B, S, H, Dh] with K/V already expanded
    to H heads, a block of queries at a time."""
    S, scale = q.shape[1], 1.0 / math.sqrt(q.shape[-1])
    key_pos = jnp.arange(S)
    out = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        visible = key_pos[None, :] <= (start + jnp.arange(qb.shape[1]))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


def _mixture(x, p, layer, top_k):
    """Softmax over all experts, the top k renormalised to sum to one;
    every expert is applied to every token and weighted (zero where it
    was not chosen), one expert at a time. ``p`` holds all layers'
    experts [L, E, in, out]; one expert of ``layer`` is read at a time."""
    probs = jax.nn.softmax(x @ p["gate"]["wg"]["kernel"][layer].astype(F32), axis=-1)  # [B, S, E]
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(top_idx, probs.shape[-1], dtype=F32)
                      * top_vals[..., None], axis=-2)                            # [B, S, E]

    def one(acc, e):
        out = _swiglu(x, p["experts_w1"][layer, e], p["experts_w3"][layer, e],
                      p["experts_w2"][layer, e])
        return acc + out * weights[..., e, None], None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(probs.shape[-1]))
    return acc


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "top_k"))
def _layer(layers, layer, h, *, heads, kv_heads, eps, theta, top_k):
    """One decoder layer; ``layers`` is the stacked tree, ``layer`` its index."""
    B, S, _ = h.shape
    moe = layers.get("moe_mlp")
    lp = jax.tree.map(lambda x: x[layer], {k: v for k, v in layers.items() if k != "moe_mlp"})
    attn = lp["self_attn"]
    x = _rms_norm(h, lp["input_layernorm"]["scale"].astype(F32), eps)
    q = (x @ attn["q_proj"]["kernel"].astype(F32)).reshape(B, S, heads, -1)
    k = (x @ attn["k_proj"]["kernel"].astype(F32)).reshape(B, S, kv_heads, -1)
    v = (x @ attn["v_proj"]["kernel"].astype(F32)).reshape(B, S, kv_heads, -1)
    positions = jnp.arange(S)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    h = h + _attention(q, k, v).reshape(B, S, -1) @ attn["o_proj"]["kernel"].astype(F32)
    x = _rms_norm(h, lp["post_attention_layernorm"]["scale"].astype(F32), eps)
    if moe is not None:
        return h + _mixture(x, moe["deepspeed_moe"], layer, top_k)
    mlp = lp["mlp"]
    return h + _swiglu(x, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"],
                       mlp["down_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, h, *, eps):
    h = _rms_norm(h, params["model"]["norm"]["scale"].astype(F32), eps)
    return h @ params["lm_head"]["kernel"].astype(F32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@jax.jit
def _next_token_loss(logits, ids):
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def hidden(params, ids, model):
    """ids [B, S] → the last layer's output [B, S, D], float32."""
    kw = dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"],
              eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
              top_k=int(model.get("num_experts_per_tok", 0)))
    with jax.default_matmul_precision("highest"):
        h = _embed(params["model"]["embed_tokens"], ids)
        layers = params["model"]["layers"]
        for i in range(model["num_hidden_layers"]):
            h = _layer(layers, jnp.int32(i), h, **kw)
    return h


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden(params, ids, model), eps=float(model["rms_norm_eps"]))


def loss(params, ids, model):
    """Mean next-token cross-entropy over ids [B, S]."""
    with jax.default_matmul_precision("highest"):
        return _next_token_loss(logits(params, ids, model), ids)
