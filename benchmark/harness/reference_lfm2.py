"""The plain reference of the ``lfm2-24b-a2b-10l`` configuration
(``model_type: lfm2_moe``): **each layer an operator and a feed-forward**,
the operator of the kind ``layer_types`` names::

    h <- h + Op_t(rms(h; operator_norm_t));   h <- h + FFN_t(rms(h; ffn_norm_t))     eps = norm_eps
    logits = rms(h; embedding_norm) @ E^T                     (E the embedding: the head is tied)

    conv:  [B | C | x] = u W_in  (each D wide);   g_t = B_t * x_t
           v_t = sum_{j<K} w[j] * g_{t-K+1+j}                 rows before the start: 0; K = conv_L_cache
           y_t = (C_t * v_t) W_out                            no bias, no activation
    full_attention:  q = rms_head(u W_q; q_layernorm), k = rms_head(u W_k; k_layernorm), one scale of
           d over every head's d; q, k rotated in halves at rope_theta; v = u W_v;
           causal softmax(q k / sqrt(d)) v, Hq / Hkv query heads a key-value head;  W_o
    FFN of the num_dense_layers leading layers:  (silu(u W_1) * u W_3) W_2
    FFN of the others:  s = sigmoid(u W_g);  the k picks: the largest of s + expert_bias;
           w_j = routed_scaling_factor s_j / (sum of the picks' s + 1e-6);
           sum_j w_j (silu(u W1_j) * u W3_j) W2_j

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, independent of
``deepspeed_tpu``, with no kernel, no cache, no chunks and no slots,
reading the sizes from the configuration's file; bf16 weights are upcast
inside the program of the one layer or the one expert that uses them, so
that a 2.6k-token sequence fits beside the engine. The convolution is
``K`` shifted products from a zero start where the served program carries
a tail from step to step in a slot; every expert is applied to every token
where the served program runs a grouped matmul over the picks; attention a
block of queries at a time where the served program reads a paged pool.

Weight tree (``deepspeed_tpu/models/lfm2.py`` documents it; matrices
``[in, out]``, the operators and feed-forwards of a kind stacked in layer
order; the checkpoint's ``w1`` / ``w3`` / ``w2`` are ``gate_proj`` /
``up_proj`` / ``down_proj``)::

    model/embed_tokens   model/embedding_norm/scale
    model/conv_layers/{operator_norm/scale, in_proj/kernel, conv_kernel [Lc, K, D], out_proj/kernel}
    model/attn_layers/{operator_norm/scale, {q,k,v,out}_proj/kernel, {q,k}_layernorm/scale}
    model/dense_ffn/{ffn_norm/scale, {gate,up,down}_proj/kernel}
    model/moe_ffn/{ffn_norm/scale, gate/{weight, expert_bias}, experts/{gate,up,down}_proj [Le, E, in, out]}
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # attention scores are formed for this many queries at a time
CONV, ATTENTION = "conv", "full_attention"
TOPK_EPS = 1e-6


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer(stack, i):
    return jax.tree.map(lambda w: w[i].astype(F32), stack)


def conv_operator(p, x, tail):
    """One gated short convolution on the normalised stream x [B, S, D] →
    (y [B, S, D], the tail it leaves [B, K - 1, D]: the last rows of the
    gated stream). ``tail``: what the sequences carried in (zeros at a
    sequence's start); ``p``: the layer's float32 parameters."""
    S, D = x.shape[1], x.shape[2]
    K = p["conv_kernel"].shape[0]
    bcx = x @ p["in_proj"]["kernel"]
    padded = jnp.concatenate([tail, bcx[..., :D] * bcx[..., 2 * D:]], axis=1)
    v = sum(p["conv_kernel"][j] * padded[:, j:j + S] for j in range(K))
    return (bcx[..., D:2 * D] * v) @ p["out_proj"]["kernel"], padded[:, S:]


@functools.partial(jax.jit, static_argnames=("eps",))
def _conv_layer(stack, i, h, *, eps):
    """→ (h + the operator, the operator's input x, its output y, the tail
    the sequences leave), from a sequence's start."""
    p = _layer(stack, i)
    x = _rms_norm(h, p["operator_norm"]["scale"], eps)
    K = p["conv_kernel"].shape[0]
    y, tail = conv_operator(p, x, jnp.zeros((h.shape[0], K - 1, h.shape[2]), F32))
    return h + y, x, y, tail


def _rope(x, theta):
    """x [S, H, d] rotated by halves at positions 0 .. S - 1."""
    S, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(S, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_operator(p, x, *, heads, kv_heads, theta, eps, round_kv=None):
    """One grouped-query attention on one sequence's normalised stream x
    [S, D] → y [S, D]. ``round_kv``: None, or a control's rounding of the
    keys and values that the cache would hold."""
    S = x.shape[0]
    d = x.shape[1] // heads
    q = _rms_norm((x @ p["q_proj"]["kernel"]).reshape(S, heads, d), p["q_layernorm"]["scale"], eps)
    k = _rms_norm((x @ p["k_proj"]["kernel"]).reshape(S, kv_heads, d), p["k_layernorm"]["scale"],
                  eps)
    v = (x @ p["v_proj"]["kernel"]).reshape(S, kv_heads, d)
    q = _rope(q, theta).reshape(S, kv_heads, heads // kv_heads, d)
    k = _rope(k, theta)
    if round_kv is not None:
        k, v = round_kv(k), round_kv(v)
    key_pos, out = jnp.arange(S), []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        scores = jnp.einsum("pkgd,ukd->kgpu", qb, k) / math.sqrt(d)
        visible = key_pos[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgpu,ukd->pkgd", probs, v).reshape(qb.shape[0], -1))
    return jnp.concatenate(out, axis=0) @ p["out_proj"]["kernel"]


ATTENTION_STATIC = ("heads", "kv_heads", "theta", "eps")


@functools.partial(jax.jit, static_argnames=ATTENTION_STATIC + ("round_kv",))
def _attention_layer(stack, i, h, *, round_kv=None, **kw):
    """→ (h + the operator, the operator's input x, its output y), a
    sequence at a time."""
    p = _layer(stack, i)

    def one(h):
        x = _rms_norm(h, p["operator_norm"]["scale"], kw["eps"])
        y = attention_operator(p, x, round_kv=round_kv, **kw)
        return h + y, x, y

    return jax.lax.map(one, h)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(stack, i, h, *, eps):
    p = _layer(stack, i)

    def one(h):                                             # [S, D]: a sequence at a time
        x = _rms_norm(h, p["ffn_norm"]["scale"], eps)
        return h + (jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])) \
            @ p["down_proj"]["kernel"]

    return jax.lax.map(one, h)


def _router(x, gate, *, top_k, scaling):
    """→ (weights [..., E], margin [...]). Sigmoid scores; the top k chosen
    on score + ``expert_bias``; the chosen weighted by their *unbiased*
    scores over their sum (+ 1e-6), times ``routed_scaling_factor``; zero
    elsewhere. ``margin``: what a perturbation of score + bias has to
    exceed to change the choice: the last pick's lead over the first
    column left out."""
    scores = jax.nn.sigmoid(x @ gate["weight"].astype(F32))
    biased = scores + gate["expert_bias"].astype(F32)
    ranked, chosen = jax.lax.top_k(biased, top_k + 1)
    is_chosen = jnp.sum(jax.nn.one_hot(chosen[..., :top_k], scores.shape[-1], dtype=F32),
                        axis=-2) > 0
    picked = jnp.where(is_chosen, scores, 0.0)
    weights = scaling * picked / (picked.sum(-1, keepdims=True) + TOPK_EPS)
    return weights, ranked[..., top_k - 1] - ranked[..., top_k]


ROUTING = ("top_k", "scaling")


@functools.partial(jax.jit, static_argnames=ROUTING + ("router",))
def _experts(stack, i, x, *, router=_router, **kw):
    """The expert feed-forward ``i`` on the normalised stream x [..., D] →
    (y, the router's margin [...], the weight a token's picks carry [...]):
    every expert applied to every token, one at a time, weighted (zero
    where the router did not choose it). ``router``: :func:`_router`, or a
    control's."""
    experts = stack["experts"]                   # [Le, E, in, out]: one expert is read at a time
    gate = jax.tree.map(lambda w: w[i], stack["gate"])
    weights, margin = router(x, gate, **kw)

    def one(acc, e):
        out = (jax.nn.silu(x @ experts["gate_proj"][i, e].astype(F32))
               * (x @ experts["up_proj"][i, e].astype(F32))) @ experts["down_proj"][i, e].astype(F32)
        return acc + out * jnp.take(weights, e, axis=-1)[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(experts["gate_proj"].shape[1]))
    return y, margin, weights.sum(-1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn_norm(stack, i, h, *, eps):
    return _rms_norm(h, stack["ffn_norm"]["scale"][i].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, h, *, eps):
    h = _rms_norm(h, params["model"]["embedding_norm"]["scale"].astype(F32), eps)
    return h @ params["model"]["embed_tokens"].astype(F32).T


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


def layer_kwargs(model):
    """The configuration file's ``model`` → the static sizes of a layer:
    (eps, attention's, the expert feed-forward's)."""
    refused = {"conv_bias": model.get("conv_bias", False),
               "norm_topk_prob": not model.get("norm_topk_prob", True),
               "use_expert_bias": not model.get("use_expert_bias", True),
               "layer_types": any(t not in (CONV, ATTENTION) for t in model["layer_types"])
               or len(model["layer_types"]) != model["num_hidden_layers"],
               "rope_type": model["rope_parameters"].get("rope_type", "default") != "default"}
    if any(refused.values()):
        raise ValueError(f"not in this reference: {[k for k, v in refused.items() if v]}")
    eps = float(model["norm_eps"])
    attn = dict(heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]),
                theta=float(model["rope_parameters"]["rope_theta"]), eps=eps)
    moe = dict(top_k=int(model["num_experts_per_tok"]),
               scaling=float(model["routed_scaling_factor"]))
    return eps, attn, moe


def hidden(params, ids, model, positions=None, router=_router, tap=None):
    """ids [B, S] → (the last layer's output [B, S, D], float32; the router
    margins [expert layers, B, S]; every expert feed-forward's normalised
    input [expert layers, B, n, D] at ``positions`` [B, n], None without
    them). A layer at a time, each waited for: dispatched ahead of the
    device, the layers' float32 weights and temporaries would all be
    allocated at once.

    ``tap(kind, i, x, y, tail)``: called after operator ``i`` of ``kind``
    (``conv`` | ``full_attention``) with what it saw and gave for the whole
    batch (the normalised input, the output and, of a ``conv`` operator,
    the tail the sequences leave; None of an attention).
    ``router``: :func:`_router`, or a control's."""
    eps, attn, moe = layer_kwargs(model)
    m = params["model"]
    n_dense = int(model["num_dense_layers"])
    margins, inputs = [], []
    seen = {CONV: 0, ATTENTION: 0}
    done = jax.block_until_ready
    with jax.default_matmul_precision("highest"):
        h = _embed(m["embed_tokens"], ids)
        for t, kind in enumerate(model["layer_types"]):
            i = jnp.int32(seen[kind])
            if kind == CONV:
                h, x, y, tail = done(_conv_layer(m["conv_layers"], i, h, eps=eps))
            else:
                (h, x, y), tail = done(_attention_layer(m["attn_layers"], i, h, **attn)), None
            if tap is not None:
                tap(kind, seen[kind], x, y, tail)
            seen[kind] += 1
            if t < n_dense:
                h = done(_dense_ffn(m["dense_ffn"], jnp.int32(t), h, eps=eps))
                continue
            e = jnp.int32(t - n_dense)
            x = _ffn_norm(m["moe_ffn"], e, h, eps=eps)
            y, margin, _ = done(_experts(m["moe_ffn"], e, x, router=router, **moe))
            h = h + y
            margins.append(margin)
            if positions is not None:
                inputs.append(jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1))
    return h, jnp.stack(margins), jnp.stack(inputs) if inputs else None


def experts_at(params, layer, x, model, router=_router):
    """Expert feed-forward ``layer`` (its index among the expert layers)
    alone, on the normalised x [B, n, D] → (y, float32; the weight a
    token's picks carry [B, n])."""
    _, _, moe = layer_kwargs(model)
    with jax.default_matmul_precision("highest"):
        y, _, weight = _experts(params["model"]["moe_ffn"], jnp.int32(layer), x, router=router,
                                **moe)
    return y, weight


def conv_at(params, layer, x, model, tail=None):
    """``conv`` operator ``layer`` alone on the normalised x [B, S, D] →
    (y, the tail left [B, K - 1, D])."""
    with jax.default_matmul_precision("highest"):
        p = _layer(params["model"]["conv_layers"], layer)
        K = p["conv_kernel"].shape[0]
        start = jnp.zeros((x.shape[0], K - 1, x.shape[2]), F32) if tail is None else tail
        return conv_operator(p, jnp.asarray(x, F32), start)


def attention_at(params, layer, x, model, round_kv=None):
    """``full_attention`` operator ``layer`` alone on one sequence's
    normalised x [S, D] → y. ``round_kv``: a control's."""
    _, attn, _ = layer_kwargs(model)
    with jax.default_matmul_precision("highest"):
        p = _layer(params["model"]["attn_layers"], layer)
        return attention_operator(p, jnp.asarray(x, F32), round_kv=round_kv, **attn)


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(params, rows, eps=float(model["norm_eps"]))


def layers_at(params, ids, positions, model, router=_router, tap=None):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D], the router margins there [expert layers, B, n],
    every expert feed-forward's input there [expert layers, B, n, D])."""
    h, margins, inputs = hidden(params, ids, model, positions, router, tap)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.take_along_axis(margins, positions[None], axis=2), inputs)


def rows_at(params, ids, positions, model):
    return layers_at(params, ids, positions, model)[:2]


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return head_at(params, hidden(params, ids, model)[0], model)
