"""The plain reference of the ``laguna-xs2-ep8-20l`` configuration
(``model_type: laguna``): **window and full attention layers 3 : 1**, of
different shapes, each with a gate a head and a routed feed-forward behind it
(a dense SwiGLU in the leading layer)::

    a = rms(h; w_in[l]);  h <- h + Attn_l(a);   f = rms(h; w_ff[l]);  h <- h + FFN_l(f)
    logits = rms(h; w_f) @ W_head                                  (eps = rms_norm_eps)

    Attn_l (H_l = num_attention_heads_per_layer[l] query heads, Hkv key-value heads of d):
        q [H_l, d], k, v [Hkv, d] = a W_q, a W_k, a W_v;   q, k <- rope_l(q, k, pos)
          full_attention:    the first partial_rotary_factor * d columns of a head, rotate-half over
                             those, at YaRN's frequencies (yarn_inv_freq), cos and sin times
                             attention_factor; the other columns untouched
          sliding_attention: all d columns, plain
        s_ij = q_i . k_j / sqrt(d)  for j <= i, and in a sliding layer also i - j < sliding_window
        o = softmax(s) v;   Attn = concat_n(sigmoid(a W_g)_n o_n) W_o          (a gate a head)
    FFN_l: mlp_layer_types[l] == "dense": W_down(silu(W_gate f) * (W_up f)); else
        s = sigmoid(f W_r);  the k picks: the largest of s + bias;  w_j = scale s_j / sum of the picks' s
        sum_j w_j E_j(f) + E_shared(f);   E(x) = W_down(silu(W_gate x) * (W_up x))

It is given the configuration's **share** of an expert-parallel deployment:
the file's ``num_experts`` experts are held here, from
``share.first_expert_held``, of ``published.num_experts`` router columns. A
routed feed-forward sums the picks whose expert is held; what the absent
experts would add is left out, as in the served program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, independent of
``deepspeed_tpu``, with no kernel, no cache, no chunks, no pools and no
tables, reading the sizes from the configuration's file; bf16 weights are
upcast inside the program of the one layer or the one expert that uses them.
Attention is a ``[queries, S]`` mask a layer kind over whole sequences, a
block of queries at a time so that it fits; every held expert is applied to
every token where the served program runs a grouped matmul over the held
picks.

Departures from the published description, none of the mathematics: weights
``[in, out]``, the attention layers of a kind stacked. Three forms follow
from no key of the config (the configuration's ``assumed`` says so): the
gate a head, the router's score the sigmoid with a selection bias and the
picks' weights over their sum, no query / key norm.

Weight tree (``deepspeed_tpu/models/laguna.py`` documents it)::

    model/embed_tokens   model/norm/scale   lm_head/kernel
    model/{full,window}_layers/{input_layernorm/scale, {q,k,v,g,o}_proj/kernel}
    model/dense_ffn/{post_attention_layernorm/scale, {gate,up,down}_proj/kernel}
    model/moe/{post_attention_layernorm/scale, gate/{weight, e_score_correction_bias},
               experts/{gate,up,down}_proj [Ls, held, in, out], shared_experts/{gate,up,down}_proj/kernel}
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # attention scores are formed for this many queries at a time
FULL, WINDOW = "full_attention", "sliding_attention"
STACKS = {FULL: "full_layers", WINDOW: "window_layers"}


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer(stack, i):
    return jax.tree.map(lambda w: w[i].astype(F32), stack)


def yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies over ``dim`` rotated columns, as the transformers
    library's ``_compute_yarn_parameters`` computes them (``truncate`` on)."""
    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def rope_of(model, kind, rotated=None, factor=None):
    """→ (inv_freq as a tuple of floats, what cos and sin are multiplied by)
    of a ``kind`` layer, from the file's ``rope_parameters``. ``rotated`` /
    ``factor``: a control's number of rotated columns / attention factor."""
    p = model["rope_parameters"][kind]
    r = int(model["head_dim"] * p.get("partial_rotary_factor", 1)) if rotated is None else rotated
    if p.get("rope_type") == "yarn":
        original = p.get("original_max_position_embeddings",
                         model["rope_parameters"].get("original_max_position_embeddings"))
        scale = p.get("attention_factor") or 0.1 * math.log(p["factor"]) + 1.0
        inv = yarn_inv_freq(r, p["rope_theta"], p["factor"], original,
                            p.get("beta_fast", 32), p.get("beta_slow", 1))
    else:
        scale = 1.0
        inv = 1.0 / (p["rope_theta"] ** (np.arange(0, r, 2, dtype=np.float32) / r))
    return tuple(float(f) for f in inv), float(scale if factor is None else factor)


def _rope(x, inv_freq, factor):
    """x [B, S, H, d] at positions 0 .. S - 1: the first ``2 len(inv_freq)``
    columns of a head rotated by halves, the rest as they are."""
    r = 2 * len(inv_freq)
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)[None, :]
    cos, sin = (jnp.cos(angle) * factor)[None, :, None, :], (jnp.sin(angle) * factor)[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)


def attention_mixer(p, x, *, heads, kv_heads, head_dim, window, inv_freq, factor, gated=True):
    """One attention layer's mixer on the normalised x [B, S, D] → y: causal
    grouped-query softmax attention (``window``: None, or the keys a query
    sees, itself included), the output times ``sigmoid(x W_g)`` a head before
    ``W_o`` (``gated`` false: a control's)."""
    B, S, _ = x.shape
    d = head_dim
    q = _rope((x @ p["q_proj"]["kernel"]).reshape(B, S, heads, d), inv_freq, factor)
    k = _rope((x @ p["k_proj"]["kernel"]).reshape(B, S, kv_heads, d), inv_freq, factor)
    v = (x @ p["v_proj"]["kernel"]).reshape(B, S, kv_heads, d)
    q = q.reshape(B, S, kv_heads, heads // kv_heads, d)
    outs = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        n = qb.shape[1]
        scores = jnp.einsum("bpkgd,bukd->bkgpu", qb, k) / math.sqrt(d)
        i, j = (start + jnp.arange(n))[:, None], jnp.arange(S)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bkgpu,bukd->bpkgd", probs, v).reshape(B, n, heads, d))
    out = jnp.concatenate(outs, axis=1)
    if gated:
        out = out * jax.nn.sigmoid(x @ p["g_proj"]["kernel"])[..., None]
    return out.reshape(B, S, heads * d) @ p["o_proj"]["kernel"]


ATTENTION_STATIC = ("heads", "kv_heads", "head_dim", "window", "inv_freq", "factor", "eps", "gated")


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
    their sum, times ``moe_routed_scaling_factor``; zero elsewhere.

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
    experts = stack["experts"]                   # [Ls, held, in, out]: one expert is read at a time
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


@jax.jit
def _dense(stack, i, x):
    p = _layer(stack, i)
    return _swiglu(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"], p["down_proj"]["kernel"])


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


def layer_kwargs(model, control=None):
    """The configuration file's ``model`` → the static sizes of a layer:
    ({kind: an attention layer's}, the routed feed-forward's). ``control``:
    None, or what a control changes of the attention (``window``: the
    window layers' window, None for none; ``rotated`` / ``factor``: the full
    layers' rotated columns / attention factor; ``gated``)."""
    refused = {"gating": model.get("gating", True) not in (True, "per-head"),
               "moe_router_logit_softcapping": bool(model.get("moe_router_logit_softcapping", 0)),
               "moe_apply_router_weight_on_input":
                   model.get("moe_apply_router_weight_on_input", False),
               "attention_bias": model.get("attention_bias", False),
               "tie_word_embeddings": model.get("tie_word_embeddings", False)}
    if any(refused.values()):
        raise ValueError(f"not in this reference: {[k for k, v in refused.items() if v]}")
    control = dict(control or {})
    eps = float(model["rms_norm_eps"])
    heads = {kind: next((h for k, h in zip(model["layer_types"],
                                           model["num_attention_heads_per_layer"]) if k == kind), 0)
             for kind in STACKS}
    attn = {}
    for kind in STACKS:
        inv_freq, factor = rope_of(model, kind,
                                   **({k: control[k] for k in ("rotated", "factor")
                                       if k in control} if kind == FULL else {}))
        attn[kind] = dict(heads=int(heads[kind]), kv_heads=int(model["num_key_value_heads"]),
                          head_dim=int(model["head_dim"]), eps=eps, inv_freq=inv_freq,
                          factor=factor, gated=control.get("gated", True),
                          window=(control.get("window", int(model["sliding_window"]))
                                  if kind == WINDOW else None))
    moe = dict(top_k=int(model["num_experts_per_tok"]),
               scaling=float(model["moe_routed_scaling_factor"]),
               first=int(model.get("share", {}).get("first_expert_held", 0)),
               held=int(model["num_experts"]))
    return attn, moe


def hidden(params, ids, model, positions=None, router=_router, tap=None, control=None):
    """ids [B, S] → (the last layer's output [B, S, D], float32; the router
    margins [Ls, B, S]; every routed feed-forward's normalised input [Ls, B,
    n, D] at ``positions`` [B, n], None without them). A layer at a time,
    each waited for: dispatched ahead of the device, the layers' float32
    weights and temporaries would all be allocated at once.

    ``tap(kind, i, x, y)``: called after attention layer ``i`` of its kind
    with what it saw and gave for the whole batch (the normalised input, the
    output). ``router``: :func:`_router`, or a control's; ``control``:
    :func:`layer_kwargs`'."""
    attn, moe = layer_kwargs(model, control)
    m = params["model"]
    margins, inputs = [], []
    seen = dict.fromkeys(STACKS, 0)
    dense = sparse = 0
    done = jax.block_until_ready
    with jax.default_matmul_precision("highest"):
        h = _embed(m["embed_tokens"], ids)
        for kind, ffn in zip(model["layer_types"], model["mlp_layer_types"]):
            i = jnp.int32(seen[kind])
            h, x, y = done(_attention_layer(m[STACKS[kind]], i, h, **attn[kind]))
            if tap is not None:
                tap(kind, seen[kind], x, y)
            seen[kind] += 1
            if ffn == "dense":
                x = _norm(m["dense_ffn"], jnp.int32(dense), h, eps=attn[kind]["eps"])
                h = done(h + _dense(m["dense_ffn"], jnp.int32(dense), x))
                dense += 1
                continue
            x = _norm(m["moe"], jnp.int32(sparse), h, eps=attn[kind]["eps"])
            y, margin, _ = done(_experts(m["moe"], jnp.int32(sparse), x, router=router, **moe))
            h = h + y
            sparse += 1
            margins.append(margin)
            if positions is not None:
                inputs.append(jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1))
    return h, jnp.stack(margins), jnp.stack(inputs) if inputs else None


def experts_at(params, layer, x, model, router=_router):
    """Routed feed-forward ``layer`` (its index among the routed layers)
    alone, on the normalised x [B, n, D] → (y as this share gives it,
    float32; the weight a token's held picks carry [B, n]: zero where the
    router chose no held expert). ``router``: :func:`_router`, or a
    control's."""
    _, moe = layer_kwargs(model)
    with jax.default_matmul_precision("highest"):
        y, _, held = _experts(params["model"]["moe"], jnp.int32(layer), x, router=router, **moe)
    return y, held


def attention_at(params, kind, layer, x, model, control=None):
    """Attention layer ``layer`` of ``kind`` (its index among that kind's
    layers) alone on the normalised x [S, D] → y [S, D]. ``control``:
    :func:`layer_kwargs`'."""
    attn, _ = layer_kwargs(model, control)
    sizes = {k: v for k, v in attn[kind].items() if k != "eps"}
    p = _layer(params["model"][STACKS[kind]], layer)
    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(attention_mixer, **sizes))(
            p, jnp.asarray(x, F32)[None])[0]


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(params, rows, eps=float(model["rms_norm_eps"]))


def layers_at(params, ids, positions, model, router=_router, tap=None, control=None):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D], the router margins there [Ls, B, n], every routed
    feed-forward's input there [Ls, B, n, D])."""
    h, margins, inputs = hidden(params, ids, model, positions, router, tap, control)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.take_along_axis(margins, positions[None], axis=2), inputs)


def rows_at(params, ids, positions, model):
    return layers_at(params, ids, positions, model)[:2]


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return head_at(params, hidden(params, ids, model)[0], model)
