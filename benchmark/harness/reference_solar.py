"""The plain reference of the ``solar-open2-ep8-4l`` configuration
(``model_type: solar_open2``): **every layer a mixer and a routed
feed-forward**, the mixer a Kimi-delta-attention (KDA) layer or - at the
layers ``gqa_layers`` names - a gated softmax attention with no positional
term::

    a = rms(h; w_in);  h <- h + Mixer(a);   f = rms(h; w_ff);  h <- h + MoE(f)
    logits = rms(h; w_f) @ W_head                                  (eps = rms_norm_eps)

    KDA (H heads of d; a token t, a head):
        [q' | k' | v]_t = silu(sum_{j<K} w_c[j] * ([W_q | W_k | W_v] a)_{t-K+1+j})   rows before the start: 0
        q = q' / sqrt(|q'|^2 + 1e-6) / sqrt(d);   k = k' / sqrt(|k'|^2 + 1e-6)
        log alpha_t = -exp(A_log[head]) * softplus(W_f2 (W_f1 a_t) + dt_bias)        [d], <= 0
        beta_t = 2 sigmoid(w_beta . a_t)                                             in (0, 2)
        S' = Diag(alpha_t) S_{t-1};   S_t = S' + beta_t k_t (v_t - S'^T k_t)^T       [d, d]
        o_t = S_t^T q_t;   Mixer = W_o (rms(o_t; w_o) * sigmoid(W_g2 (W_g1 a_t) + b_g))
    attention:  q [Hq, d], k, v [Hkv, d] = a W_q, a W_k, a W_v;  o = causal softmax(q k / sqrt(d)) v
        Mixer = W_o (o * sigmoid(a W_gate))
    MoE:  s = sigmoid(f W_r);  the k picks: the largest of s + bias;  w_j = scale s_j / sum of the picks' s
        MoE = sum_j w_j E_j(f) + E_shared(f);   E(x) = W_down(silu(W_gate x) * (W_up x))

It is given the configuration's **share** of an expert-parallel deployment:
the file's ``n_routed_experts`` experts are held here, from
``share.first_expert_held``, of ``published.n_routed_experts`` router
columns. A routed feed-forward sums the picks whose expert is held; what
the absent experts would add is left out, as in the served program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, independent of
``deepspeed_tpu``, with no kernel, no cache, no chunks and no slots, reading
the sizes from the configuration's file; bf16 weights are upcast inside the
program of the one layer or the one expert that uses them. The delta rule
runs **a token at a time** from a zero state; every held expert is applied
to every token where the served program runs a grouped matmul over the held
picks; attention a block of queries at a time.

Departures from the published description, none of the mathematics:
weights ``[in, out]``, the mixers of a kind stacked; KDA's three projections
side by side in one ``qkv_proj`` and its three depth-wise convolutions in
one ``[K, 3 I]`` set of taps. Three forms follow from no key of the config
and are the named families' published ones (the configuration's ``assumed``
says so): KDA's inner forms are Kimi Linear's (arXiv:2510.26692), the
attention's gate the element-wise sigmoid on its output before ``W_o``, the
router's score the sigmoid with a selection bias.

Weight tree (``deepspeed_tpu/models/solar_open2.py`` documents it)::

    model/embed_tokens   model/norm/scale   lm_head/kernel
    model/kda_layers/{input_layernorm/scale, qkv_proj/kernel, conv_kernel [Lk, K, 3 I],
                      b_proj/kernel, f_a_proj/kernel, f_b_proj/kernel, A_log [Lk, H], dt_bias,
                      g_a_proj/kernel, g_b_proj/{kernel, bias}, o_norm/scale [Lk, d], o_proj/kernel}
    model/gqa_layers/{input_layernorm/scale, {q,k,v,gate,o}_proj/kernel}
    model/moe/{post_attention_layernorm/scale, gate/{weight, e_score_correction_bias},
               experts/{gate,up,down}_proj [L, held, in, out], shared_experts/{gate,up,down}_proj/kernel}
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # attention scores are formed for this many queries at a time
KDA, GQA = "k", "g"
STACKS = {KDA: "kda_layers", GQA: "gqa_layers"}
L2_EPS = 1e-6


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer(stack, i):
    return jax.tree.map(lambda w: w[i].astype(F32), stack)


def layer_kinds(model):
    """A letter a layer of the configuration's stack."""
    return "".join(GQA if i in model["gqa_layers"] else KDA
                   for i in range(model["num_hidden_layers"]))


def kda_mixer(p, x, state, tail, *, heads, head_dim, kernel, eps, beta_scale=2.0):
    """One KDA mixer on the normalised stream x [B, S, D], the delta rule a
    token at a time → (y [B, S, D], the state it leaves [B, H, d, d] - key
    rows, value columns -, the convolutions' tail it leaves [B, K - 1, 3 I]).
    ``state`` / ``tail``: what the sequences carried in (zeros at a
    sequence's start); ``p``: the layer's float32 parameters;
    ``beta_scale``: 2 (``kda_allow_neg_eigval``), or a control's."""
    B, S, _ = x.shape
    H, d, K = heads, head_dim, kernel
    I = H * d
    padded = jnp.concatenate([tail, x @ p["qkv_proj"]["kernel"]], axis=1)
    act = jax.nn.silu(sum(p["conv_kernel"][j] * padded[:, j:j + S] for j in range(K)))
    q, k, v = (act[..., i * I:(i + 1) * I].reshape(B, S, H, d) for i in range(3))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(d)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    step = jax.nn.softplus((x @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"] + p["dt_bias"])
    alpha = jnp.exp(-jnp.exp(p["A_log"])[:, None] * step.reshape(B, S, H, d))
    beta = beta_scale * jax.nn.sigmoid(x @ p["b_proj"]["kernel"])               # [B, S, H]

    def one(s, row):
        q_t, k_t, v_t, a_t, b_t = row
        s = a_t[..., None] * s
        seen = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + (b_t[..., None, None] * k_t[..., None]) * (v_t - seen)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    last, o = jax.lax.scan(one, state,
                           tuple(jnp.moveaxis(r, 1, 0) for r in (q, k, v, alpha, beta)))
    o = _rms_norm(jnp.moveaxis(o, 0, 1), p["o_norm"]["scale"], eps).reshape(B, S, I)
    gate = jax.nn.sigmoid((x @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"]
                          + p["g_b_proj"]["bias"])
    return (o * gate) @ p["o_proj"]["kernel"], last, padded[:, S:]


KDA_STATIC = ("heads", "head_dim", "kernel", "eps", "beta_scale")


@functools.partial(jax.jit, static_argnames=KDA_STATIC)
def _kda_layer(stack, i, h, **kw):
    """→ (h + the mixer, the mixer's input x, its output y, the state and
    the tail the sequences leave), from a sequence's start."""
    p = _layer(stack, i)
    B = h.shape[0]
    H, d, K = kw["heads"], kw["head_dim"], kw["kernel"]
    x = _rms_norm(h, p["input_layernorm"]["scale"], kw["eps"])
    y, state, tail = kda_mixer(p, x, jnp.zeros((B, H, d, d), F32),
                               jnp.zeros((B, K - 1, 3 * H * d), F32), **kw)
    return h + y, x, y, state, tail


def attention_mixer(p, x, *, heads, kv_heads, head_dim, gated=True):
    """The attention mixer on the normalised x [B, S, D] → y: causal
    grouped-query softmax attention, no positional term, the output gated
    by ``sigmoid(x W_gate)`` before ``W_o`` (``gated`` false: a control's)."""
    B, S, _ = x.shape
    d = head_dim
    q = (x @ p["q_proj"]["kernel"]).reshape(B, S, kv_heads, heads // kv_heads, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(B, S, kv_heads, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(B, S, kv_heads, d)
    outs = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        n = qb.shape[1]
        scores = jnp.einsum("bpkgd,bukd->bkgpu", qb, k) / math.sqrt(d)
        causal = (start + jnp.arange(n))[:, None] >= jnp.arange(S)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bkgpu,bukd->bpkgd", probs, v).reshape(B, n, heads * d))
    out = jnp.concatenate(outs, axis=1)
    if gated:
        out = out * jax.nn.sigmoid(x @ p["gate_proj"]["kernel"])
    return out @ p["o_proj"]["kernel"]


ATTENTION_STATIC = ("heads", "kv_heads", "head_dim", "eps", "gated")


@functools.partial(jax.jit, static_argnames=ATTENTION_STATIC)
def _attention_layer(stack, i, h, *, eps, **kw):
    """→ (h + the mixer, the mixer's input x, its output y)."""
    p = _layer(stack, i)
    x = _rms_norm(h, p["input_layernorm"]["scale"], eps)
    y = attention_mixer(p, x, **kw)
    return h + y, x, y


def _router(x, router, *, top_k, scaling, first, held):
    """→ (weights [..., E], margin [...]). Sigmoid scores; the top k chosen
    on score + bias; the chosen weighted by their *unbiased* scores over
    their sum, times ``routed_scaling_factor``; zero elsewhere.

    ``margin``: what a perturbation of score + bias has to exceed to change
    **which held experts** this share computes: the smallest lead of a
    chosen column over one left out, over the pairs of which one is a held
    expert (``first .. first + held``) - two absent experts are both left
    out."""
    scores = jax.nn.sigmoid(x @ router["weight"].astype(F32))
    biased = scores + router["e_score_correction_bias"].astype(F32)
    ranked, chosen = jax.lax.top_k(biased, top_k)
    is_chosen = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32), axis=-2) > 0
    picked = jnp.where(is_chosen, scores, 0.0)
    weights = scaling * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    column = jnp.arange(scores.shape[-1])
    here = (column >= first) & (column < first + held)
    inf = jnp.inf
    chosen_min = ranked[..., -1]
    chosen_min_here = jnp.min(jnp.where(is_chosen & here, biased, inf), axis=-1)
    out_max = jnp.max(jnp.where(is_chosen, -inf, biased), axis=-1)
    out_max_here = jnp.max(jnp.where(is_chosen | ~here, -inf, biased), axis=-1)
    return weights, jnp.minimum(chosen_min_here - out_max, chosen_min - out_max_here)


ROUTING = ("top_k", "scaling", "first", "held")


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=ROUTING + ("router",))
def _experts(stack, i, x, *, router=_router, **kw):
    """The routed feed-forward ``i`` on the normalised stream x [..., D], as
    this share gives it → (y, the router's margin [...], the weight a
    token's held picks carry [...]): every held expert applied to every
    token, one at a time, weighted (zero where the router did not choose
    it), and the shared expert. ``router``: :func:`_router`, or a
    control's."""
    experts = stack["experts"]                   # [L, held, in, out]: one expert is read at a time
    p = _layer({k: v for k, v in stack.items() if k != "experts"}, i)
    weights, margin = router(x, p["gate"], **kw)

    def one(acc, e):
        out = _swiglu(x, *(experts[n][i, e].astype(F32)
                           for n in ("gate_proj", "up_proj", "down_proj")))
        return acc + out * jnp.take(weights, kw["first"] + e, axis=-1)[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(kw["held"]))
    s = p["shared_experts"]
    y = y + _swiglu(x, s["gate_proj"]["kernel"], s["up_proj"]["kernel"], s["down_proj"]["kernel"])
    held_weight = jnp.sum(weights[..., kw["first"]:kw["first"] + kw["held"]], axis=-1)
    return y, margin, held_weight


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(stack, i, h, *, eps):
    return _rms_norm(h, stack["post_attention_layernorm"]["scale"][i].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, h, *, eps):
    h = _rms_norm(h, params["model"]["norm"]["scale"].astype(F32), eps)
    return h @ params["lm_head"]["kernel"].astype(F32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


def layer_kwargs(model):
    """The configuration file's ``model`` → the static sizes of a layer:
    (KDA's, attention's, the routed feed-forward's)."""
    linear = model["linear_attn_config"]
    refused = {"kda_use_full_proj": model.get("kda_use_full_proj", False),
               "kda_allow_neg_eigval": not model.get("kda_allow_neg_eigval", True),
               "use_rope": model.get("use_rope", False),
               "use_gqa_gate": not model.get("use_gqa_gate", True),
               "num_kv_heads": linear.get("num_kv_heads") is not None,
               "first_k_dense_replace": model.get("first_k_dense_replace", 0) != 0,
               "n_shared_experts": model.get("n_shared_experts", 1) != 1,
               "norm_topk_prob": not model.get("norm_topk_prob", True),
               "tie_word_embeddings": model.get("tie_word_embeddings", False)}
    if any(refused.values()):
        raise ValueError(f"not in this reference: {[k for k, v in refused.items() if v]}")
    eps = float(model["rms_norm_eps"])
    kda = dict(heads=int(linear["num_heads"]), head_dim=int(linear["head_dim"]),
               kernel=int(linear["short_conv_kernel_size"]), eps=eps)
    attn = dict(heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]), head_dim=int(model["head_dim"]),
                eps=eps)
    moe = dict(top_k=int(model["num_experts_per_tok"]),
               scaling=float(model["routed_scaling_factor"]),
               first=int(model.get("share", {}).get("first_expert_held", 0)),
               held=int(model["n_routed_experts"]))
    return kda, attn, moe


def hidden(params, ids, model, positions=None, router=_router, tap=None):
    """ids [B, S] → (the last layer's output [B, S, D], float32; the router
    margins [L, B, S]; every routed feed-forward's normalised input [L, B,
    n, D] at ``positions`` [B, n], None without them). A layer at a time,
    each waited for: dispatched ahead of the device, the layers' float32
    weights and temporaries would all be allocated at once.

    ``tap(kind, i, x, y, state, tail)``: called after mixer ``i`` of its
    kind with what it saw and gave for the whole batch (the normalised
    input, the output, and - a KDA layer's - the state and the convolutions'
    tail the sequences leave; None for an attention layer). ``router``:
    :func:`_router`, or a control's."""
    kda, attn, moe = layer_kwargs(model)
    m = params["model"]
    margins, inputs = [], []
    seen = dict.fromkeys(STACKS, 0)
    done = jax.block_until_ready
    with jax.default_matmul_precision("highest"):
        h = _embed(m["embed_tokens"], ids)
        for position, kind in enumerate(layer_kinds(model)):
            i, stack = jnp.int32(seen[kind]), m[STACKS[kind]]
            if kind == KDA:
                h, x, y, state, tail = done(_kda_layer(stack, i, h, **kda))
            else:
                (h, x, y), state, tail = done(_attention_layer(stack, i, h, **attn)), None, None
            if tap is not None:
                tap(kind, seen[kind], x, y, state, tail)
            seen[kind] += 1
            x = _norm(m["moe"], jnp.int32(position), h, eps=attn["eps"])
            y, margin, _ = done(_experts(m["moe"], jnp.int32(position), x, router=router, **moe))
            h = h + y
            margins.append(margin)
            if positions is not None:
                inputs.append(jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1))
    return h, jnp.stack(margins), jnp.stack(inputs) if inputs else None


def experts_at(params, layer, x, model, router=_router):
    """Routed feed-forward ``layer`` (the layer's position in the stack)
    alone, on the normalised x [B, n, D] → (y as this share gives it,
    float32; the weight a token's held picks carry [B, n]: zero where the
    router chose no held expert). ``router``: :func:`_router`, or a
    control's."""
    _, _, moe = layer_kwargs(model)
    with jax.default_matmul_precision("highest"):
        y, _, held = _experts(params["model"]["moe"], jnp.int32(layer), x, router=router, **moe)
    return y, held


def kda_at(params, layer, x, model, beta_scale=2.0):
    """KDA layer ``layer``'s mixer (its index among the KDA layers) alone, on
    the normalised x [S, D] from a zero start → (y [S, D], the state [H, d,
    d], the tail [K - 1, 3 I]). ``beta_scale``: 2, or a control's."""
    kda, _, _ = layer_kwargs(model)
    p = _layer(params["model"]["kda_layers"], layer)
    H, d, K = kda["heads"], kda["head_dim"], kda["kernel"]
    with jax.default_matmul_precision("highest"):
        y, state, tail = jax.jit(functools.partial(kda_mixer, beta_scale=beta_scale, **kda))(
            p, jnp.asarray(x, F32)[None], jnp.zeros((1, H, d, d), F32),
            jnp.zeros((1, K - 1, 3 * H * d), F32))
    return y[0], state[0], tail[0]


def attention_at(params, layer, x, model, gated=True):
    """Attention layer ``layer``'s mixer alone on the normalised x [S, D] → y
    [S, D]. ``gated`` false: the control without the gate."""
    _, attn, _ = layer_kwargs(model)
    sizes = {k: v for k, v in attn.items() if k != "eps"}
    p = _layer(params["model"]["gqa_layers"], layer)
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(attention_mixer, gated=gated, **sizes))(
            p, jnp.asarray(x, F32)[None])[0]


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(params, rows, eps=float(model["rms_norm_eps"]))


def layers_at(params, ids, positions, model, router=_router, tap=None):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D], the router margins there [L, B, n], every routed
    feed-forward's input there [L, B, n, D])."""
    h, margins, inputs = hidden(params, ids, model, positions, router, tap)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.take_along_axis(margins, positions[None], axis=2), inputs)


def rows_at(params, ids, positions, model):
    return layers_at(params, ids, positions, model)[:2]


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return head_at(params, hidden(params, ids, model)[0], model)
