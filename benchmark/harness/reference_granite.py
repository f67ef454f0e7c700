"""The plain reference of the ``granite4-h-small-ep4-10l`` configuration
(``model_type: granitemoehybrid``): **every layer a mixer and a routed
feed-forward**, each under the residual multiplier ``r``::

    h0 = e E[ids];   h <- h + r Mixer_l(rms(h; w_l));   h <- h + r (MoE_l(u) + Shared_l(u)),  u = rms(h; w'_l)
    logits = (rms(h_L; w_f) E^T) / s      (e embedding_multiplier, s logits_scaling, E tied; eps = rms_norm_eps)

    mamba (Mamba-2):  [z | xBC | dt] = x W_in       widths I | I + 2 G N | H,   I = H P
        xBC_t <- silu(b_c + sum_{j<K} w_c[j] * xBC_{t-K+1+j})     rows before the start: 0
        x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t);  head h reads group h // (H / G)   (G = 1 here)
        Delta_t = softplus(dt_t + dt_bias);  a_t = exp(-Delta_t exp(A_log))
        S_t = a_t S_{t-1} + Delta_t x_t (x) B_t  [H, P, N];   y_t = S_t C_t + D x_t
        out = (w_n * rms_{groups of I / G}(y * silu(z))) W_out
    attention: 32 query / 8 key-value heads of 128, causal softmax(a q k) v, a = attention_multiplier
        (1/128, not 1/sqrt(128)), no positional term, no bias
    MoE:  l = u W_r (float32);  the k picks: the largest of l;  w = softmax over those k of l;
        sum_j w_j (silu(u W1_j) * (u W3_j)) W2_j;   Shared: the same form on every token, unweighted

It is given the configuration's **share** of an expert-parallel deployment:
the file's ``num_local_experts`` experts are held here, from
``share.first_expert_held``, of ``published.num_local_experts`` router
columns. A feed-forward sums the picks whose expert is held; what the absent
experts would add is left out, as in the served program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, independent of ``deepspeed_tpu``,
with no kernel, no cache, no chunks, no slots and **no snapshot** (a
sequence's every token is run from token 0), reading the sizes from the
configuration's file; bf16 weights are upcast inside the program of the one
layer or the one expert that uses them. The recurrence runs **a token at a
time** from a zero state; every held expert is applied to every token;
attention a block of queries at a time.

Weight tree (``deepspeed_tpu/models/granite_hybrid.py`` documents it; matrices
``[in, out]``, the layers of a kind stacked in stack order; an expert's
published fused ``input_linear`` held as its halves ``gate_proj`` | ``up_proj``)::

    model/embed_tokens   model/norm/scale            (tied: no lm_head)
    model/mamba_layers/{norm/scale, in_proj/kernel, conv_kernel [Lm, K, C], conv_bias, dt_bias,
                        A_log, D, gate_norm/scale, out_proj/kernel}
    model/attn_layers/{norm/scale, q_proj, k_proj, v_proj, o_proj}/kernel
    model/moe_layers/{norm/scale, router/weight, experts/{gate_proj, up_proj, down_proj}
                      [L, held, in, out], shared_experts/{gate_proj, up_proj, down_proj}/kernel}
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # attention scores are formed for this many queries at a time
MAMBA, ATTENTION = "mamba", "attention"


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer(stack, i):
    return jax.tree.map(lambda w: w[i].astype(F32), stack)


def mamba_mixer(p, x, state, tail, *, heads, head_dim, groups, state_size, kernel, eps):
    """One Mamba-2 mixer on the normalised stream x [B, S, D], the
    recurrence a token at a time → (y [B, S, D], the state it leaves
    [B, H, P, N], the convolution's tail it leaves [B, K - 1, C])."""
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


MAMBA_STATIC = ("heads", "head_dim", "groups", "state_size", "kernel", "eps", "residual")


@functools.partial(jax.jit, static_argnames=MAMBA_STATIC)
def _mamba_layer(stack, i, h, *, residual, **kw):
    """→ (h + r * the mixer, the mixer's input x, its output y, the state
    and the tail the sequences leave), from a sequence's start."""
    p = _layer(stack, i)
    B = h.shape[0]
    H, P, G, N, K = kw["heads"], kw["head_dim"], kw["groups"], kw["state_size"], kw["kernel"]
    x = _rms_norm(h, p["norm"]["scale"], kw["eps"])
    y, state, tail = mamba_mixer(p, x, jnp.zeros((B, H, P, N), F32),
                                 jnp.zeros((B, K - 1, H * P + 2 * G * N), F32), **kw)
    return h + residual * y, x, y, state, tail


ATTENTION_STATIC = ("heads", "kv_heads", "head_dim", "eps", "scale", "residual")


def _attention_mixer(p, x, *, heads, kv_heads, head_dim, scale):
    """The attention mixer of one sequence on the normalised x [S, D] → y [S, D]."""
    S = x.shape[0]
    q = (x @ p["q_proj"]["kernel"]).reshape(S, kv_heads, heads // kv_heads, head_dim)
    k = (x @ p["k_proj"]["kernel"]).reshape(S, kv_heads, head_dim)
    v = (x @ p["v_proj"]["kernel"]).reshape(S, kv_heads, head_dim)
    key_pos, out = jnp.arange(S), []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        scores = jnp.einsum("pkgd,ukd->kgpu", qb, k) * scale
        visible = key_pos[None, :] <= (start + jnp.arange(qb.shape[0]))[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgpu,ukd->pkgd", probs, v).reshape(qb.shape[0], -1))
    return jnp.concatenate(out, axis=0) @ p["o_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=ATTENTION_STATIC)
def _attention_layer(stack, i, h, *, heads, kv_heads, head_dim, eps, scale, residual):
    """→ (h + r * the mixer, the mixer's input x, its output y), a sequence at a time."""
    p = _layer(stack, i)

    def one(h):                                             # [S, D]
        x = _rms_norm(h, p["norm"]["scale"], eps)
        y = _attention_mixer(p, x, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                             scale=scale)
        return h + residual * y, x, y

    return jax.lax.map(one, h)


def _router(x, router, *, top_k, first, held):
    """→ (weights [..., E], margin [...]). The ``top_k`` largest logits; the
    softmax over those; zero elsewhere.

    ``margin``: what a perturbation of a logit has to exceed to change
    **which held experts** this share computes: the smallest lead of a
    chosen column over one left out, over the pairs of which one is a held
    expert (``first .. first + held``) - two absent experts are both left
    out. (A swap among the absent picks still moves the sum the weights are
    divided by: a change of a weight's size, not of a pick.)"""
    logits = x @ router["weight"].astype(F32)
    ranked, chosen = jax.lax.top_k(logits, top_k)
    is_chosen = jnp.sum(jax.nn.one_hot(chosen, logits.shape[-1], dtype=F32), axis=-2) > 0
    weights = jax.nn.softmax(jnp.where(is_chosen, logits, -jnp.inf), axis=-1)
    column = jnp.arange(logits.shape[-1])
    here = (column >= first) & (column < first + held)
    inf = jnp.inf
    chosen_min = ranked[..., -1]
    chosen_min_here = jnp.min(jnp.where(is_chosen & here, logits, inf), axis=-1)
    out_max = jnp.max(jnp.where(is_chosen, -inf, logits), axis=-1)
    out_max_here = jnp.max(jnp.where(is_chosen | ~here, -inf, logits), axis=-1)
    return weights, jnp.minimum(chosen_min_here - out_max, chosen_min - out_max_here)


ROUTING = ("top_k", "first", "held")


@functools.partial(jax.jit, static_argnames=ROUTING + ("router",))
def _experts(stack, i, x, *, router=_router, **kw):
    """Layer ``i``'s feed-forward on the normalised stream x [..., D], as this
    share gives it → (y, the router's margin [...], the weight a token's held
    picks carry [...]): every held expert applied to every token, one at a
    time, weighted (zero where the router did not choose it), and the shared
    expert. ``router``: :func:`_router`, or a control's."""
    experts = stack["experts"]                   # [L, held, in, out]: one expert is read at a time
    p = _layer({k: v for k, v in stack.items() if k != "experts"}, i)
    weights, margin = router(x, p["router"], **kw)

    def one(acc, e):
        out = (jax.nn.silu(x @ experts["gate_proj"][i, e].astype(F32))
               * (x @ experts["up_proj"][i, e].astype(F32))) @ experts["down_proj"][i, e].astype(F32)
        return acc + out * jnp.take(weights, kw["first"] + e, axis=-1)[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(kw["held"]))
    s = p["shared_experts"]
    y = y + (jax.nn.silu(x @ s["gate_proj"]["kernel"]) * (x @ s["up_proj"]["kernel"])) \
        @ s["down_proj"]["kernel"]
    held_weight = jnp.sum(weights[..., kw["first"]:kw["first"] + kw["held"]], axis=-1)
    return y, margin, held_weight


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(stack, i, h, *, eps):
    return _rms_norm(h, stack["norm"]["scale"][i].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(params, h, *, eps, scaling):
    h = _rms_norm(h, params["model"]["norm"]["scale"].astype(F32), eps)
    return (h @ params["model"]["embed_tokens"].astype(F32).T) / scaling


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(table, ids, *, multiplier):
    return multiplier * table[ids].astype(F32)


def layer_kwargs(model):
    """The configuration file's ``model`` → the static sizes of a layer:
    (Mamba's, attention's, the feed-forward's)."""
    refused = {"biases": any(model.get(k, False) for k in ("attention_bias", "mamba_proj_bias")),
               "mamba_conv_bias": not model.get("mamba_conv_bias", True),
               "tie_word_embeddings": not model.get("tie_word_embeddings", True),
               "position_embedding_type": model.get("position_embedding_type", "nope") != "nope",
               "hidden_act": model.get("hidden_act", "silu") != "silu",
               "layer_types": any(t not in (MAMBA, ATTENTION) for t in model["layer_types"])}
    if any(refused.values()):
        raise ValueError(f"not in this reference: {[k for k, v in refused.items() if v]}")
    eps, r = float(model["rms_norm_eps"]), float(model["residual_multiplier"])
    mamba = dict(heads=int(model["mamba_n_heads"]), head_dim=int(model["mamba_d_head"]),
                 groups=int(model["mamba_n_groups"]), state_size=int(model["mamba_d_state"]),
                 kernel=int(model["mamba_d_conv"]), eps=eps, residual=r)
    attn = dict(heads=int(model["num_attention_heads"]),
                kv_heads=int(model["num_key_value_heads"]),
                head_dim=int(model["hidden_size"]) // int(model["num_attention_heads"]),
                eps=eps, scale=float(model["attention_multiplier"]), residual=r)
    moe = dict(top_k=int(model["num_experts_per_tok"]),
               first=int(model.get("share", {}).get("first_expert_held", 0)),
               held=int(model["num_local_experts"]))
    return mamba, attn, moe


def hidden(params, ids, model, positions=None, router=_router, tap=None, attention_scale=None,
           tap_attention=None):
    """ids [B, S] → (the last layer's output [B, S, D], float32; the router
    margins [L, B, S]; every feed-forward's normalised input [L, B, n, D] at
    ``positions`` [B, n], None without them). A layer at a time, each waited
    for: dispatched ahead of the device, the layers' float32 weights and
    temporaries would all be allocated at once.

    ``tap(i, x, y, state, tail)``: called after mamba layer ``i`` with what
    its mixer saw and gave for the whole batch; ``tap_attention(i, x, y)``:
    likewise after attention layer ``i``. ``router``: :func:`_router`,
    or a control's; ``attention_scale``: a control's, in place of
    ``attention_multiplier``."""
    mamba, attn, moe = layer_kwargs(model)
    if attention_scale is not None:
        attn["scale"] = float(attention_scale)
    m = params["model"]
    r = mamba["residual"]
    margins, inputs = [], []
    seen = {MAMBA: 0, ATTENTION: 0}
    done = jax.block_until_ready
    with jax.default_matmul_precision("highest"):
        h = _embed(m["embed_tokens"], ids, multiplier=float(model["embedding_multiplier"]))
        for position, kind in enumerate(model["layer_types"]):
            i = jnp.int32(seen[kind])
            if kind == MAMBA:
                h, x, y, state, tail = done(_mamba_layer(m["mamba_layers"], i, h, **mamba))
                if tap is not None:
                    tap(seen[kind], x, y, state, tail)
            else:
                h, x, y = done(_attention_layer(m["attn_layers"], i, h, **attn))
                if tap_attention is not None:
                    tap_attention(seen[kind], x, y)
            seen[kind] += 1
            x = _norm(m["moe_layers"], jnp.int32(position), h, eps=attn["eps"])
            y, margin, _ = done(_experts(m["moe_layers"], jnp.int32(position), x, router=router,
                                         **moe))
            h = h + r * y
            margins.append(margin)
            if positions is not None:
                inputs.append(jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1))
    return h, jnp.stack(margins), jnp.stack(inputs) if inputs else None


def experts_at(params, layer, x, model, router=_router):
    """Layer ``layer``'s feed-forward alone, on the normalised x [B, n, D] →
    (y as this share gives it - the held picks and the shared expert, before
    the residual multiplier - float32; the weight a token's held picks carry
    [B, n]: zero where the router chose no held expert)."""
    _, _, moe = layer_kwargs(model)
    with jax.default_matmul_precision("highest"):
        y, _, held = _experts(params["model"]["moe_layers"], jnp.int32(layer), x, router=router,
                              **moe)
    return y, held


def attention_at(params, layer, x, model, scale=None):
    """Attention layer ``layer``'s mixer alone (its index among the attention
    layers), on one sequence's normalised x [S, D] → y [S, D], float32.
    ``scale``: a control's, in place of ``attention_multiplier``."""
    _, attn, _ = layer_kwargs(model)
    p = _layer(params["model"]["attn_layers"], jnp.int32(layer))
    kw = {k: attn[k] for k in ("heads", "kv_heads", "head_dim")}
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(
            _attention_mixer, scale=attn["scale"] if scale is None else float(scale), **kw))(p, x)


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(params, rows, eps=float(model["rms_norm_eps"]),
                     scaling=float(model["logits_scaling"]))


def layers_at(params, ids, positions, model, router=_router, tap=None, attention_scale=None,
              tap_attention=None):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D], the router margins there [L, B, n], every
    feed-forward's input there [L, B, n, D])."""
    h, margins, inputs = hidden(params, ids, model, positions, router, tap, attention_scale,
                                tap_attention)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.take_along_axis(margins, positions[None], axis=2), inputs)


def rows_at(params, ids, positions, model):
    return layers_at(params, ids, positions, model)[:2]


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return head_at(params, hidden(params, ids, model)[0], model)
