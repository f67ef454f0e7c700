"""The plain reference of the ``nemotron3-super-ep4-11l`` configuration
(``model_type: nemotron_h``): **each layer one sublayer alone**, of the kind
its letter in ``hybrid_override_pattern`` names::

    h <- h + F_t(rms(h; w_t));   logits = rms(h; w_f) @ W_head        (eps = layer_norm_epsilon)

    M (Mamba-2):  [z | xBC | dt] = x W_in       widths I | I + 2 G N | H,   I = H P
        xBC_t <- silu(b_c + sum_{j<K} w_c[j] * xBC_{t-K+1+j})     rows before the start: 0
        x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t);  head h reads group h // (H / G)
        Delta_t = softplus(dt_t + dt_bias);  a_t = exp(-Delta_t exp(A_log))
        S_t = a_t S_{t-1} + Delta_t x_t (x) B_t  [H, P, N];   y_t = S_t C_t + D x_t
        out = (w_n * rms_{groups of I / G}(y * silu(z))) W_out
    * (attention): 32 query / 2 key-value heads, causal softmax(q k / sqrt(d)) v, no positional term
    E (LatentMoE):  s = sigmoid(x W_r);  the k picks: the largest of s + bias;
        w_j = routed_scaling_factor s_j / sum of the picks' s;   u = x W_down
        out = (sum_j w_j relu(u W1_j)^2 W2_j) W_up + relu(x Ws1)^2 Ws2

It is given the configuration's **share** of an expert-parallel
deployment: the file's ``n_routed_experts`` experts are held here, from
``share.first_expert_held``, of ``published.n_routed_experts`` router
columns. An ``E`` layer sums the picks whose expert is held; what the
absent experts would add is left out, as in the served program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, independent of
``deepspeed_tpu``, with no kernel, no cache, no chunks and no slots,
reading the sizes from the configuration's file; bf16 weights are upcast
inside the program of the one layer or the one expert that uses them. The
recurrence runs **a token at a time** from a zero state where the served
program takes a chunk at once by a decay mask; every held expert is
applied to every token where the served program runs a grouped matmul
over the held picks; attention a block of queries at a time.

Weight tree (``deepspeed_tpu/models/nemotron_h.py`` documents it; matrices
``[in, out]``, the layers of a kind stacked in stack order)::

    model/embed_tokens   model/norm/scale   lm_head/kernel
    model/mamba_layers/{norm/scale, in_proj/kernel, conv_kernel [Lm, K, C], conv_bias, dt_bias,
                        A_log, D, gate_norm/scale, out_proj/kernel}
    model/attn_layers/{norm/scale, q_proj, k_proj, v_proj, o_proj}/kernel
    model/moe_layers/{norm/scale, router/{weight, e_score_correction_bias}, latent_down/kernel,
                      latent_up/kernel, experts/{up_proj, down_proj} [Le, held, in, out],
                      shared_experts/{up_proj, down_proj}/kernel}
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # attention scores are formed for this many queries at a time
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
STACKS = {MAMBA: "mamba_layers", ATTENTION: "attn_layers", EXPERTS: "moe_layers"}


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _layer(stack, i):
    return jax.tree.map(lambda w: w[i].astype(F32), stack)


def mamba_mixer(p, x, state, tail, *, heads, head_dim, groups, state_size, kernel, eps):
    """One Mamba-2 mixer on the normalised stream x [B, S, D], the
    recurrence a token at a time → (y [B, S, D], the state it leaves
    [B, H, P, N], the convolution's tail it leaves [B, K - 1, C]).
    ``state`` / ``tail``: what the sequences carried in (zeros at a
    sequence's start); ``p``: the layer's float32 parameters."""
    B, S, _ = x.shape
    H, P, G, N, K = heads, head_dim, groups, state_size, kernel
    I = H * P
    C = I + 2 * G * N
    zxbcdt = x @ p["in_proj"]["kernel"]
    z, xbc, dt = zxbcdt[..., :I], zxbcdt[..., I:I + C], zxbcdt[..., I + C:]
    padded = jnp.concatenate([tail, xbc], axis=1)
    conv = p["conv_bias"] + sum(p["conv_kernel"][j] * padded[:, j:j + S] for j in range(K))
    act = jax.nn.silu(conv)
    xs = act[..., :I].reshape(B, S, H, P)
    b_heads = jnp.repeat(act[..., I:I + G * N].reshape(B, S, G, N), H // G, axis=2)
    c_heads = jnp.repeat(act[..., I + G * N:].reshape(B, S, G, N), H // G, axis=2)
    delta = jax.nn.softplus(dt + p["dt_bias"])                          # [B, S, H]
    decay = jnp.exp(-delta * jnp.exp(p["A_log"]))

    def one(s, row):
        a_t, d_t, x_t, b_t, c_t = row
        s = a_t[..., None, None] * s + (d_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return s, jnp.sum(s * c_t[..., None, :], axis=-1)

    rows = tuple(jnp.moveaxis(r, 1, 0) for r in (decay, delta, xs, b_heads, c_heads))
    last, y = jax.lax.scan(one, state, rows)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs
    y = (y.reshape(B, S, I) * jax.nn.silu(z)).reshape(B, S, G, I // G)
    y = _rms_norm(y, 1.0, eps).reshape(B, S, I) * p["gate_norm"]["scale"]
    return y @ p["out_proj"]["kernel"], last, padded[:, S:]


MAMBA_STATIC = ("heads", "head_dim", "groups", "state_size", "kernel", "eps")


@functools.partial(jax.jit, static_argnames=MAMBA_STATIC)
def _mamba_layer(stack, i, h, **kw):
    """→ (h + the mixer, the mixer's input x, its output y, the state and
    the tail the sequences leave), from a sequence's start."""
    p = _layer(stack, i)
    B = h.shape[0]
    H, P, G, N, K = kw["heads"], kw["head_dim"], kw["groups"], kw["state_size"], kw["kernel"]
    x = _rms_norm(h, p["norm"]["scale"], kw["eps"])
    y, state, tail = mamba_mixer(p, x, jnp.zeros((B, H, P, N), F32),
                                 jnp.zeros((B, K - 1, H * P + 2 * G * N), F32), **kw)
    return h + y, x, y, state, tail


ATTENTION_STATIC = ("heads", "kv_heads", "head_dim", "eps")


@functools.partial(jax.jit, static_argnames=ATTENTION_STATIC)
def _attention_layer(stack, i, h, *, heads, kv_heads, head_dim, eps):
    p = _layer(stack, i)

    def one(h):                                             # [S, D]: a sequence at a time
        S = h.shape[0]
        x = _rms_norm(h, p["norm"]["scale"], eps)
        q = (x @ p["q_proj"]["kernel"]).reshape(S, kv_heads, heads // kv_heads, head_dim)
        k = (x @ p["k_proj"]["kernel"]).reshape(S, kv_heads, head_dim)
        v = (x @ p["v_proj"]["kernel"]).reshape(S, kv_heads, head_dim)
        key_pos, out = jnp.arange(S), []
        for start in range(0, S, QUERY_BLOCK):
            qb = q[start:start + QUERY_BLOCK]
            scores = jnp.einsum("pkgd,ukd->kgpu", qb, k) / math.sqrt(head_dim)
            visible = key_pos[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
            probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
            out.append(jnp.einsum("kgpu,ukd->pkgd", probs, v).reshape(qb.shape[0], -1))
        return h + jnp.concatenate(out, axis=0) @ p["o_proj"]["kernel"]

    return jax.lax.map(one, h)


def _router(x, router, *, top_k, scaling, first, held):
    """→ (weights [..., E], margin [...]). Sigmoid scores; the top k chosen
    on score + bias; the chosen weighted by their *unbiased* scores over
    their sum, times ``routed_scaling_factor``; zero elsewhere.

    ``margin``: what a perturbation of score + bias has to exceed to
    change **which held experts** this share computes: the smallest lead
    of a chosen column over one left out, over the pairs of which one is a
    held expert (``first .. first + held``) - two absent experts are both
    left out. (A swap among the absent picks still moves the sum the
    weights are divided by: that is a change of rounding's size, not a
    pick's.)"""
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


@functools.partial(jax.jit, static_argnames=ROUTING + ("router",))
def _experts(stack, i, x, *, router=_router, **kw):
    """The expert layer ``i`` on the normalised stream x [..., D], as this
    share gives it → (y, the router's margin [...], the weight a token's
    held picks carry [...]): every held expert applied to every token, one
    at a time, weighted (zero where the router did not choose it).
    ``router``: :func:`_router`, or a control's."""
    experts = stack["experts"]                   # [Le, held, in, out]: one expert is read at a time
    p = _layer({k: v for k, v in stack.items() if k != "experts"}, i)
    weights, margin = router(x, p["router"], **kw)
    u = x @ p["latent_down"]["kernel"]

    def one(acc, e):
        out = _relu2(u @ experts["up_proj"][i, e].astype(F32)) \
            @ experts["down_proj"][i, e].astype(F32)
        return acc + out * jnp.take(weights, kw["first"] + e, axis=-1)[..., None], None

    v, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(kw["held"]))
    s = p["shared_experts"]
    y = v @ p["latent_up"]["kernel"] + _relu2(x @ s["up_proj"]["kernel"]) @ s["down_proj"]["kernel"]
    held_weight = jnp.sum(weights[..., kw["first"]:kw["first"] + kw["held"]], axis=-1)
    return y, margin, held_weight


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(stack, i, h, *, eps):
    return _rms_norm(h, stack["norm"]["scale"][i].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, h, *, eps):
    h = _rms_norm(h, params["model"]["norm"]["scale"].astype(F32), eps)
    return h @ params["lm_head"]["kernel"].astype(F32)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


def layer_kwargs(model):
    """The configuration file's ``model`` → the static sizes of a layer:
    (Mamba's, attention's, the expert layer's)."""
    refused = {"n_group": model.get("n_group", 1) != 1,
               "topk_group": model.get("topk_group", 1) != 1,
               "norm_topk_prob": not model.get("norm_topk_prob", True),
               "mamba_hidden_act": model.get("mamba_hidden_act", "silu") != "silu",
               "mlp_hidden_act": model.get("mlp_hidden_act", "relu2") != "relu2",
               "biases": any(model.get(k, False) for k in ("attention_bias", "mamba_proj_bias",
                                                            "mlp_bias", "use_bias")),
               "use_conv_bias": not model.get("use_conv_bias", True),
               "tie_word_embeddings": model.get("tie_word_embeddings", False),
               "hybrid_override_pattern": any(t not in STACKS
                                              for t in model["hybrid_override_pattern"])}
    if any(refused.values()):
        raise ValueError(f"not in this reference: {[k for k, v in refused.items() if v]}")
    eps = float(model["layer_norm_epsilon"])
    mamba = dict(heads=int(model["mamba_num_heads"]), head_dim=int(model["mamba_head_dim"]),
                 groups=int(model["n_groups"]), state_size=int(model["ssm_state_size"]),
                 kernel=int(model["conv_kernel"]), eps=eps)
    attn = dict(heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]), head_dim=int(model["head_dim"]),
                eps=eps)
    moe = dict(top_k=int(model["num_experts_per_tok"]),
               scaling=float(model["routed_scaling_factor"]),
               first=int(model.get("share", {}).get("first_expert_held", 0)),
               held=int(model["n_routed_experts"]))
    return mamba, attn, moe


def hidden(params, ids, model, positions=None, router=_router, tap=None):
    """ids [B, S] → (the last layer's output [B, S, D], float32; the router
    margins [E layers, B, S]; every expert layer's normalised input
    [E layers, B, n, D] at ``positions`` [B, n], None without them). A
    layer at a time, each waited for: dispatched ahead of the device, the
    layers' float32 weights and temporaries would all be allocated at once.

    ``tap(i, x, y, state, tail)``: called after ``M`` layer ``i`` with what
    its mixer saw and gave for the whole batch (the normalised input, the
    output, the state and the convolution's tail the sequences leave).
    ``router``: :func:`_router`, or a control's."""
    mamba, attn, moe = layer_kwargs(model)
    m = params["model"]
    margins, inputs = [], []
    seen = dict.fromkeys(STACKS, 0)
    done = jax.block_until_ready
    with jax.default_matmul_precision("highest"):
        h = _embed(m["embed_tokens"], ids)
        for letter in model["hybrid_override_pattern"]:
            i, stack = jnp.int32(seen[letter]), m[STACKS[letter]]
            if letter == MAMBA:
                h, x, y, state, tail = done(_mamba_layer(stack, i, h, **mamba))
                if tap is not None:
                    tap(seen[letter], x, y, state, tail)
            elif letter == ATTENTION:
                h = done(_attention_layer(stack, i, h, **attn))
            else:
                x = _norm(stack, i, h, eps=attn["eps"])
                y, margin, _ = done(_experts(stack, i, x, router=router, **moe))
                h = h + y
                margins.append(margin)
                if positions is not None:
                    inputs.append(jnp.take_along_axis(x, jnp.asarray(positions)[..., None],
                                                      axis=1))
            seen[letter] += 1
    return h, jnp.stack(margins), jnp.stack(inputs) if inputs else None


def experts_at(params, layer, x, model, router=_router):
    """Expert layer ``layer`` (its index among the ``E`` layers) alone, on
    the normalised x [B, n, D] → (y as this share gives it, float32; the
    weight a token's held picks carry [B, n]: zero where the router chose
    no held expert). ``router``: :func:`_router`, or a control's."""
    _, _, moe = layer_kwargs(model)
    with jax.default_matmul_precision("highest"):
        y, _, held = _experts(params["model"]["moe_layers"], jnp.int32(layer), x, router=router,
                              **moe)
    return y, held


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(params, rows, eps=float(model["layer_norm_epsilon"]))


def layers_at(params, ids, positions, model, router=_router, tap=None):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D], the router margins there [E layers, B, n], every
    expert layer's input there [E layers, B, n, D])."""
    h, margins, inputs = hidden(params, ids, model, positions, router, tap)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.take_along_axis(margins, positions[None], axis=2), inputs)


def rows_at(params, ids, positions, model):
    return layers_at(params, ids, positions, model)[:2]


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return head_at(params, hidden(params, ids, model)[0], model)
