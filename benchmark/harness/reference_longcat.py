"""The plain reference of the ``longcat-flash-omni-ep32`` configuration
(LongCat-Flash-Omni's language model): **double layers** of two latent
attentions ``A_0, A_1`` (low-rank query, the two ``mla_scale_*`` factors)
and two dense SwiGLUs ``F_0, F_1`` with a shortcut expert layer ``M``
beside the second half, behind a softmax router over ``n_routed_experts``
+ ``zero_expert_num`` columns whose zero-compute experts return their
input::

    h1 = h  + A_0(RMS_a0(h));   x = RMS_p0(h1);   m = M(x)
    h2 = h1 + F_0(x)
    h3 = h2 + A_1(RMS_a1(h2))
    h4 = h3 + F_1(RMS_p1(h3)) + m

    M(x) = sum over the moe_topk columns j with the largest s_j + bias_j of
           6 s_j E_j(x),  s = softmax(x W_r),  E_j = SwiGLU_j (j routed), x (j zero-compute)

It is given the configuration's **share** of an expert-parallel
deployment: the file's ``n_routed_experts`` experts are held here, from
``share.first_expert_held``, of ``published.n_routed_experts`` routed
columns. ``M`` sums the picks whose expert is held and the zero-compute
picks; what the absent experts would add is left out, as in the served
program.

Like ``harness/reference_moonlight.py`` it is straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
independent of ``deepspeed_tpu``, with no kernel and no cache, reading
the sizes from the configuration's file; bf16 weights are upcast inside
the program of the half layer or the one expert that uses them. It
**expands** ``kv_b_proj`` where the served program absorbs it, and
applies every held expert to every token where the served program runs a
grouped matmul over the held picks.

Weight tree (``deepspeed_tpu/models/longcat.py`` documents it; matrices
are ``[in, out]``, stacked over the double layers; ``i`` in {0, 1} names
the half)::

    model/embed_tokens   model/norm/scale   lm_head/kernel
    model/layers/{input,post_attention}_layernorm/i/scale                       [L, D]
    model/layers/self_attn/i/{q_a_proj,q_b_proj,kv_a_proj_with_mqa,kv_b_proj,o_proj}/kernel [L, ...]
    model/layers/self_attn/i/{q_a,kv_a}_layernorm/scale                         [L, ...]
    model/layers/mlps/i/{gate,up,down}_proj/kernel                              [L, in, out]
    model/layers/mlp/router/classifier/weight [L, D, C]   .../router/e_score_correction_bias [L, C]
    model/layers/mlp/experts/{gate,up,down}_proj [L, held, in, out]
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256  # attention scores are formed for this many queries at a time
DENSE_COLUMNS = 3072  # a dense SwiGLU is applied this many columns of its width at a time


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, H, d]: the pairs (2i, 2i+1) are first moved to (i, i + d/2),
    then rotated by halves."""
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


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


ATTENTION = ("heads", "rank", "nope", "rope", "vdim", "eps", "theta", "q_scale", "kv_scale")


@functools.partial(jax.jit, static_argnames=ATTENTION + ("half",))
def _attention_half(layers, layer, half, h, *, heads, rank, nope, rope, vdim, eps, theta,
                    q_scale, kv_scale):
    """``h + A_half(RMS(h))`` of double layer ``layer``; ``half``: "0" | "1".
    A sequence at a time (64 heads of float32 scores for three long
    sequences at once would be the check's largest buffers by far)."""
    a = jax.tree.map(lambda w: w[layer].astype(F32), layers["self_attn"][half])
    norm = layers["input_layernorm"][half]["scale"][layer].astype(F32)

    def one(h):                                                         # [S, D]
        S, h = h.shape[0], h[None]
        x = _rms_norm(h, norm, eps)
        c_q = _rms_norm(x @ a["q_a_proj"]["kernel"], a["q_a_layernorm"]["scale"], eps)
        q = (c_q @ a["q_b_proj"]["kernel"]).reshape(1, S, heads, nope + rope) * q_scale
        kv_a = x @ a["kv_a_proj_with_mqa"]["kernel"]
        c_kv = _rms_norm(kv_a[..., :rank], a["kv_a_layernorm"]["scale"], eps) * kv_scale
        kv = (c_kv @ a["kv_b_proj"]["kernel"]).reshape(1, S, heads, nope + vdim)
        k_rope = _rope(kv_a[..., None, rank:], theta)                   # [1, S, 1, rope], unscaled
        queries = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
        keys = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (1, S, heads, rope))],
                               axis=-1)
        out = _causal_attention(queries, keys, kv[..., nope:], 1.0 / math.sqrt(nope + rope))
        return (h + out.reshape(1, S, heads * vdim) @ a["o_proj"]["kernel"])[0]

    return jax.lax.map(one, h)


@functools.partial(jax.jit, static_argnames=("eps", "half"))
def _post_norm(layers, layer, half, h, *, eps):
    return _rms_norm(h, layers["post_attention_layernorm"][half]["scale"][layer].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("half", "width"))
def _dense_columns(layers, layer, half, x, start, *, width):
    """Columns ``start .. start + width`` of a dense SwiGLU's hidden
    layer, through its down projection: the SwiGLU is a sum over them."""
    m = layers["mlps"][half]
    cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(w[layer], start, width, axis)  # noqa: E731
    return _swiglu(x, cut(m["gate_proj"]["kernel"], 1), cut(m["up_proj"]["kernel"], 1),
                   cut(m["down_proj"]["kernel"], 0))


def _dense_half(layers, layer, half, x):
    """``F_half(x)``, ``DENSE_COLUMNS`` of its width at a time: float32
    copies of a quarter of the 0.45 GB the three matrices take in bf16."""
    width = layers["mlps"][half]["gate_proj"]["kernel"].shape[-1]
    step, out = min(DENSE_COLUMNS, width), 0
    for start in range(0, width, step):
        out = jax.block_until_ready(
            out + _dense_columns(layers, layer, half, x, jnp.int32(start), width=step))
    return out


def _router(x, router, *, top_k, scaling, routed, first, held):
    """→ (weights [B, S, C], margin [B, S]). Softmax over every column;
    the top k chosen on score + bias; the chosen weighted by their
    *unbiased* scores, not normalised, times ``routed_scaling_factor``;
    zero elsewhere.

    ``margin``: what a perturbation of score + bias has to exceed to
    change **this share's** result. Swapping a chosen column for one left
    out matters only if one of the two is computed here - a held expert
    (``first .. first + held``) or a zero-compute column (``>= routed``) -
    since two absent experts are both left out: the smallest lead of a
    chosen column over one left out, over the pairs of which one is
    computed here."""
    scores = jax.nn.softmax(x @ router["classifier"]["weight"].astype(F32), axis=-1)
    biased = scores + router["e_score_correction_bias"].astype(F32)
    ranked, chosen = jax.lax.top_k(biased, top_k)
    is_chosen = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32), axis=-2) > 0
    weights = jnp.where(is_chosen, scores * scaling, 0.0)
    column = jnp.arange(scores.shape[-1])
    here = ((column >= first) & (column < first + held)) | (column >= routed)
    inf = jnp.inf
    chosen_min = ranked[..., -1]
    chosen_min_here = jnp.min(jnp.where(is_chosen & here, biased, inf), axis=-1)
    out_max = jnp.max(jnp.where(is_chosen, -inf, biased), axis=-1)
    out_max_here = jnp.max(jnp.where(is_chosen | ~here, -inf, biased), axis=-1)
    return weights, jnp.minimum(chosen_min_here - out_max, chosen_min - out_max_here)


ROUTING = ("top_k", "scaling", "routed", "first", "held")


@functools.partial(jax.jit, static_argnames=ROUTING + ("router",))
def _experts(layers, layer, x, *, router=_router, **kw):
    """``M(x)`` as this share gives it → (m, the router's margin [B, S]):
    every held expert applied to every token, one at a time, weighted
    (zero where the router did not choose it), plus the zero-compute
    picks' ``(sum of their weights) * x``. ``router``: :func:`_router`, or
    a control's."""
    experts = layers["mlp"]["experts"]           # [L, held, in, out]: one expert is read at a time
    weights, margin = router(x, jax.tree.map(lambda w: w[layer], layers["mlp"]["router"]), **kw)

    def one(acc, e):
        out = _swiglu(x, experts["gate_proj"][layer, e], experts["up_proj"][layer, e],
                      experts["down_proj"][layer, e])
        return acc + out * jnp.take(weights, kw["first"] + e, axis=-1)[..., None], None

    held, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(kw["held"]))
    return held + jnp.sum(weights[..., kw["routed"]:], axis=-1, keepdims=True) * x, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, h, *, eps):
    h = _rms_norm(h, params["model"]["norm"]["scale"].astype(F32), eps)
    return h @ params["lm_head"]["kernel"].astype(F32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


def layer_kwargs(model):
    """The configuration file's ``model`` → the static sizes of a layer:
    (attention's, the expert layer's)."""
    refused = {"attention_method": model.get("attention_method", "MLA") != "MLA",
               "zero_expert_type": model.get("zero_expert_type", "identity") != "identity",
               "rope_scaling": model.get("rope_scaling") is not None,
               "q_lora_rank": model.get("q_lora_rank") is None}
    if any(refused.values()):
        raise ValueError(f"not in this reference: {[k for k, v in refused.items() if v]}")
    D = model["hidden_size"]
    attn = dict(heads=model["num_attention_heads"], rank=model["kv_lora_rank"],
                nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
                vdim=model["v_head_dim"], eps=float(model["rms_norm_eps"]),
                theta=float(model["rope_theta"]),
                q_scale=math.sqrt(D / model["q_lora_rank"]) if model["mla_scale_q_lora"] else 1.0,
                kv_scale=math.sqrt(D / model["kv_lora_rank"]) if model["mla_scale_kv_lora"]
                else 1.0)
    held = int(model["n_routed_experts"])
    moe = dict(top_k=int(model["moe_topk"]), scaling=float(model["routed_scaling_factor"]),
               routed=int(model.get("published", {}).get("n_routed_experts", held)),
               first=int(model.get("share", {}).get("first_expert_held", 0)), held=held)
    return attn, moe


def double_layer(layers, l, h, attn, moe, router=_router):
    """One double layer → (h, the router's margin [B, S], the expert
    layer's input x [B, S, D]). A piece at a time: dispatched ahead of the
    device, the pieces' float32 weights and temporaries are all allocated
    at once (2.5 GB beside a 13 GB engine: memory_stats on the chip,
    PR 32)."""
    l, a, b, done = jnp.int32(l), "0", "1", jax.block_until_ready
    h = done(_attention_half(layers, l, a, h, **attn))
    x = _post_norm(layers, l, a, h, eps=attn["eps"])
    m, margin = done(_experts(layers, l, x, router=router, **moe))   # the shortcut: joins at the end
    h = h + _dense_half(layers, l, a, x)
    h = done(_attention_half(layers, l, b, h, **attn))
    h = done(h + _dense_half(layers, l, b, _post_norm(layers, l, b, h, eps=attn["eps"])) + m)
    return h, margin, x


def hidden(params, ids, model, positions=None, router=_router):
    """ids [B, S] → (the last layer's output [B, S, D], float32; the router
    margins [double layers, B, S]; every expert layer's input [double
    layers, B, n, D] at ``positions`` [B, n], None without them).
    ``router``: :func:`_router`, or a control's."""
    attn, moe = layer_kwargs(model)
    margins, inputs = [], []
    with jax.default_matmul_precision("highest"):
        h = _embed(params["model"]["embed_tokens"], ids)
        for l in range(int(model["num_layers"])):
            h, margin, x = double_layer(params["model"]["layers"], l, h, attn, moe, router)
            margins.append(margin)
            if positions is not None:
                inputs.append(jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1))
    return h, jnp.stack(margins), jnp.stack(inputs) if inputs else None


def experts_at(params, layer, x, model, router=_router):
    """The expert layer of double layer ``layer`` alone, on x [B, n, D] →
    (m = M(x) as this share gives it, float32; the weight a token's held
    picks carry, [B, n]: zero where the router chose no held expert).
    ``router``: :func:`_router`, or a control's."""
    _, moe = layer_kwargs(model)
    layers = params["model"]["layers"]
    with jax.default_matmul_precision("highest"):
        m, _ = _experts(layers, jnp.int32(layer), x, router=router, **moe)
        weights, _ = _router(x, jax.tree.map(lambda w: w[layer], layers["mlp"]["router"]), **moe)
    return m, jnp.sum(weights[..., moe["first"]:moe["first"] + moe["held"]], axis=-1)


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(params, rows, eps=float(model["rms_norm_eps"]))


def layers_at(params, ids, positions, model, router=_router):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D], the router margins there [double layers, B, n],
    every expert layer's input there [double layers, B, n, D])."""
    h, margins, inputs = hidden(params, ids, model, positions, router)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.take_along_axis(margins, positions[None], axis=2), inputs)


def rows_at(params, ids, positions, model):
    return layers_at(params, ids, positions, model)[:2]


def logits_at(params, ids, positions, model):
    rows, margins = rows_at(params, ids, positions, model)
    return head_at(params, rows, model), margins


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return head_at(params, hidden(params, ids, model)[0], model)
