"""The plain reference of Mellum 2's training step, the benchmark's copy
(``deepspeed_tpu/models/mellum.py`` holds the program's, with the equations):
loss, per-position NLL, the gradient's norm and each half of a layer alone,
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernel, no mesh of its own, every expert a loop over all the rows, the window a
mask. It is independent of ``deepspeed_tpu``: it reads the sizes from the
configuration's file (the published keys) and the weights as a tree of arrays.

**Computed in blocks so that it fits** beside a trainer's state: a layer at a
time with that layer's bf16 weights upcast (one expert at a time), attention a
block of queries at a time, the head a chunk of positions at a time, and the
gradient a layer at a time - the forward keeps only the stream entering each
layer, and the backward walks the layers from the last, recomputing each one's
forward inside its own ``jax.vjp`` and keeping of its gradient the sum of
squares alone. Arrays that come in sharded over chips stay so (``jax.jit``
follows its arguments): the reference of a four-chip cell runs on the four.

``faults`` (a frozenset of names; empty: the model as published) are the
controls of ``benchmark/tests/control_mellum.py``: what each leaves out or
gets wrong is said at the line that sets it (:func:`knobs`,
:func:`rope_tables`, :func:`rounded_to`). What differs between the two kinds
of layer and between the controls is **data** of one compiled program (the
tables of positions, the window, the router's switches), so the reference
compiles once for both kinds and for every control.

Weight tree (the names the system's checkpoints use; the layers stacked)::

    model/embed_tokens [V, D]   model/norm/scale [D]   lm_head/kernel [D, V]
    model/layers/{input_layernorm,post_attention_layernorm}/scale [L, D]
    model/layers/self_attn/{q,k,v,o}_proj/kernel [L, in, out]
    model/layers/moe_mlp/deepspeed_moe/gate/wg/kernel [L, D, E]
    model/layers/moe_mlp/deepspeed_moe/experts_w{1,3} [L, E, D, I]   experts_w2 [L, E, I, D]
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
QUERY_BLOCK = 512       # attention scores are formed for this many queries at a time
HEAD_CHUNK = 1024       # the head's logits for this many positions at a time
FAULTS = ("window_as_full", "full_as_window", "yarn_left_out", "topk_not_normalised",
          "one_pick_fewer", "one_rank_left_out", "float8")
NONE = frozenset()


def layer_of(params, model, l):
    """Layer ``l``'s parameters, cut out of the stack (still in their dtype)."""
    return jax.tree.map(lambda x: x[l], params["model"]["layers"])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """The transformers library's ``_compute_yarn_parameters`` (truncate on)."""
    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


def rope_tables(model, kind, length, faults=NONE):
    """cos, sin ``[length, d / 2]`` of a layer of ``kind``."""
    p, d = model["rope_parameters"][kind], model["head_dim"]
    gain = 1.0
    if p["rope_type"] == "yarn" and "yarn_left_out" not in faults:
        inv = _yarn_inv_freq(d, p["rope_theta"], p["factor"],
                             p["original_max_position_embeddings"], p["beta_fast"], p["beta_slow"])
        gain = p.get("attention_factor") or 0.1 * math.log(p["factor"]) + 1.0
    else:   # fault yarn_left_out: the full layers rotate by the plain table, no factor
        inv = 1.0 / (p["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float32) / d))
    f = np.outer(np.arange(length, dtype=np.float32), inv)
    return (np.cos(f) * gain).astype(np.float32), (np.sin(f) * gain).astype(np.float32)


def _rotate(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attend(q, k, v, window):
    """Causal softmax attention ``[B, S, H, d]``, keys and values already
    expanded to ``H`` heads: a block of queries at a time against every key
    under the mask (one loop body whatever the layer's kind: ``window``, the
    newest keys a query sees, is a traced number, the sequence's length where
    there is none), each block recomputed in the backward."""
    B, S, H, d = q.shape
    scale = 1.0 / math.sqrt(d)
    QB = min(QUERY_BLOCK, S)
    pad = -S % QB
    k_pos = jnp.arange(S)

    @jax.checkpoint
    def block(qb, q_pos):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] > q_pos[:, None] - window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(B, -1, QB, H, d).swapaxes(0, 1)
    # a padded query (past the sequence) sees key 0 at the least: its row is cut off below
    pos = jnp.minimum(jnp.arange(S + pad), S - 1).reshape(-1, QB)
    out = jax.lax.map(lambda a: block(*a), (qs, pos))
    return out.swapaxes(0, 1).reshape(B, S + pad, H, d)[:, :S]


def knobs(model, kind, length, faults=NONE):
    """What of a layer of ``kind`` is data and not program, so that one
    compiled reference serves both kinds and every control: the tables of
    positions, the window (the sequence's length where there is none), and the
    router's three switches. The controls' faults are set here."""
    cos, sin = rope_tables(model, kind, length, faults)
    window = int(model["sliding_window"]) if kind == SLIDING else length
    if kind == SLIDING and "window_as_full" in faults:
        window = length                          # fault: a sliding layer sees every key before it
    if kind == FULL and "full_as_window" in faults:
        window = int(model["sliding_window"])    # fault: a full layer sees the window alone
    E = int(model["num_experts"])
    return {"cos": jnp.asarray(cos), "sin": jnp.asarray(sin),
            "window": jnp.asarray(window, jnp.int32),
            # fault topk_not_normalised: the picks' probabilities as they are
            "normalise": jnp.asarray("topk_not_normalised" not in faults),
            # fault one_pick_fewer: a token's last pick adds nothing
            "drop_last": jnp.asarray("one_pick_fewer" in faults),
            # fault one_rank_left_out: the last quarter of the experts (a rank of four) adds nothing
            "held": jnp.asarray(E - E // 4 if "one_rank_left_out" in faults else E, jnp.int32),
            # the expert half alone (half_alone) is handed the program's picks: see route
            "picks": jnp.full((1, int(model["num_experts_per_tok"])), -1, jnp.int32)}


def attention_half(lp, h, model, kn):
    """``(P v) W_o`` of a layer on the stream ``h [B, S, D]`` float32 (the
    residual not added); ``kn``: the layer's :func:`knobs`."""
    B, S, _ = h.shape
    H, G, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    at = lp["self_attn"]
    a = _rms(h, lp["input_layernorm"]["scale"], float(model["rms_norm_eps"]))
    q = (a @ at["q_proj"]["kernel"].astype(F32)).reshape(B, S, H, d)
    k = (a @ at["k_proj"]["kernel"].astype(F32)).reshape(B, S, G, d)
    v = (a @ at["v_proj"]["kernel"].astype(F32)).reshape(B, S, G, d)
    q, k = _rotate(q, kn["cos"], kn["sin"]), _rotate(k, kn["cos"], kn["sin"])
    k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
    return _attend(q, k, v, kn["window"]).reshape(B, S, H * d) @ at["o_proj"]["kernel"].astype(F32)


def route(lp, m, model, kn):
    """→ (probabilities ``[T, E]``, picks ``[T, k]``, weights ``[T, k]``, each
    token's margin ``[T]``: how far its k-th probability lies over its
    (k + 1)-th, as a share of the k-th - negated where the picks it was handed
    are not its own).

    A pick is no continuous function of the stream: where two probabilities
    lie within the rounding of the program's router (bf16's of the normalised
    input, ~0.3 % of a probability; 64 seeded logits put ~4 % of a step's
    tokens there) a bf16 program and a float32 reference pick differently and
    that token's row differs by a whole expert. So where one expert layer is
    compared **alone**, ``kn["picks"]`` hands the reference **the program's
    own picks** (``[T, k]``; -1: none given, the reference's own top k): the
    weights are still this router's float32 probabilities at those picks,
    normalised, so what the comparison reads is the experts, the layout, the
    sum and the exchange under one routing. How often the program's picks are
    not the reference's own where the margin is clear is read beside it
    (``experts_picks_differ_share``)."""
    k = int(model["num_experts_per_tok"])
    probs = jax.nn.softmax(
        m @ lp["moe_mlp"]["deepspeed_moe"]["gate"]["wg"]["kernel"].astype(F32), axis=-1)
    top, own = jax.lax.top_k(probs, k + 1)
    margin = (top[:, k - 1] - top[:, k]) / top[:, k - 1]
    picks = jnp.where(kn["picks"] >= 0, kn["picks"], own[:, :k])
    vals = jnp.take_along_axis(probs, picks, axis=-1)
    vals = vals / jnp.where(kn["normalise"], jnp.sum(vals, axis=-1, keepdims=True), 1.0)
    last = jnp.arange(k) == k - 1
    # a token is marked where the picks it was handed are not its own top k
    differ = jnp.any(jnp.sort(picks, axis=-1) != jnp.sort(own[:, :k], axis=-1), axis=-1)
    return (probs, picks, jnp.where(kn["drop_last"] & last[None, :], 0.0, vals),
            jnp.where(differ, -margin, margin))


def experts_half(lp, h, model, kn):
    """``sum_j w_j E_j(m)`` of a layer on the stream ``h`` (the residual not
    added), and the layer's load-balancing term. One expert at a time over
    all the rows, its picks' weights selecting, its forward recomputed in the
    backward."""
    B, S, D = h.shape
    E = int(model["num_experts"])
    moe = lp["moe_mlp"]["deepspeed_moe"]
    m = _rms(h, lp["post_attention_layernorm"]["scale"], float(model["rms_norm_eps"]))
    m = m.reshape(B * S, D)
    probs, picks, weights, margin = route(lp, m, model, kn)

    @jax.checkpoint
    def expert(m, w1, w3, w2, w_e):
        return w_e * ((jax.nn.silu(m @ w1.astype(F32)) * (m @ w3.astype(F32))) @ w2.astype(F32))

    def one(acc, e):
        w_e = jnp.sum(jnp.where((picks == e) & (e < kn["held"]), weights, 0.0), axis=-1,
                      keepdims=True)
        return acc + expert(m, moe["experts_w1"][e], moe["experts_w3"][e],
                            moe["experts_w2"][e], w_e), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(E))
    first = jax.nn.one_hot(picks[:, 0], E, dtype=F32)
    aux = jnp.sum(probs.mean(axis=0) * first.mean(axis=0)) * E
    return out.reshape(B, S, D), aux, margin.reshape(B, S)


def _layer(lp, h, model, kn):
    h = h + attention_half(lp, h, model, kn)
    y, aux, _ = experts_half(lp, h, model, kn)
    return h + y, aux


def _static(model):
    """The configuration's sizes as a hashable the jitted pieces close over."""
    return _Frozen(model)


class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))


@functools.partial(jax.jit, static_argnames=("model",))
def layer_forward(lp, h, kn, *, model):
    with jax.default_matmul_precision("highest"):
        return _layer(lp, h, model, kn)


@functools.partial(jax.jit, static_argnames=("model",))
def layer_backward(lp, h, dh, daux, kn, *, model):
    """→ (the cotangent of the stream entering the layer, the sum of squares
    of the layer's parameter gradient by leaf)."""
    with jax.default_matmul_precision("highest"):
        # differentiated at the parameters as they come (bf16): the arithmetic is float32 -
        # every use upcasts - and a gradient comes back rounded once to its parameter's
        # dtype, which moves a norm by 1e-3 of itself at the most and halves what the
        # backward of 64 experts holds beside a trainer's state
        _, vjp = jax.vjp(lambda p, x: _layer(p, x, model, kn), lp, h)
        dp, dx = vjp((dh, daux))
        return dx, jax.tree.map(lambda g: jnp.sum(jnp.square(g.astype(F32))), dp)


@functools.partial(jax.jit, static_argnames=("model", "half"))
def half_alone(lp, h, ct, kn, *, model, half):
    """One half of a layer alone (``half``: ``attention`` | ``experts``) on
    the stream ``h`` → (its output, the cotangent ``ct`` pulled back to ``h``,
    and for the expert half each token's router margin ``[B, S]``, negated
    where the picks ``kn["picks"]`` handed it are not its own: :func:`route`)."""
    with jax.default_matmul_precision("highest"):
        if half == "attention":
            out, vjp = jax.vjp(lambda x: attention_half(lp, x, model, kn), h)
            return out, vjp(ct)[0], jnp.ones(h.shape[:2], F32)
        def experts(x):
            y, _, margin = experts_half(lp, x, model, kn)
            return y, margin

        out, vjp, margin = jax.vjp(experts, h, has_aux=True)
        return out, vjp(ct)[0], margin


def _head_nll(norm, head, h, ids, eps):
    """Per-position NLL ``[B, S - 1]``, a chunk of positions at a time."""
    x = _rms(h, norm, eps)[:, :-1]
    targets = ids[:, 1:]

    @jax.checkpoint
    def chunk(xc, tc):
        logp = jax.nn.log_softmax(xc @ head.astype(F32), axis=-1)
        return -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]

    return jnp.concatenate([chunk(x[:, i:i + HEAD_CHUNK], targets[:, i:i + HEAD_CHUNK])
                            for i in range(0, x.shape[1], HEAD_CHUNK)], axis=1)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_forward(norm, head, h, ids, *, eps):
    with jax.default_matmul_precision("highest"):
        return _head_nll(norm, head, h, ids, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_backward(norm, head, h, ids, *, eps):
    """The mean NLL's gradient → (the cotangent of the last stream, the sums
    of squares of the norm's and the head's gradients)."""
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda n, w, x: jnp.mean(_head_nll(n, w, x, ids, eps)), argnums=(0, 1, 2))(
            norm, head, h)      # at the parameters' own dtype, as layer_backward
        return (grads[2], jnp.sum(jnp.square(grads[0].astype(F32))),
                jnp.sum(jnp.square(grads[1].astype(F32))))


@jax.jit
def embed(table, ids):
    return table[ids].astype(F32)


@jax.jit
def embed_backward_sumsq(table, ids, dh):
    g = jnp.zeros(table.shape, F32).at[ids.reshape(-1)].add(dh.reshape(-1, dh.shape[-1]))
    return jnp.sum(jnp.square(g))


def rounded_to(params, dtype):
    """Fault ``float8`` (the caller rounds once and hands the rounded tree to
    every reading): every matrix (not the norms' vectors) at ``dtype``'s
    values with one scale a tensor, **op by op** (inside one program the TPU's
    compiler keeps the excess precision of a cast down and back)."""
    def one(x):
        if x.ndim < 2:
            return x
        scale = jnp.maximum(jnp.max(jnp.abs(x.astype(F32))), 1e-30) / float(jnp.finfo(dtype).max)
        low = (x.astype(F32) / scale).astype(dtype)
        return (low.astype(F32) * scale).astype(x.dtype)
    return jax.tree.map(one, params)


def forward(params, ids, model, faults=NONE, coef=0.0):
    """→ (per-position NLL ``[B, S - 1]``, the loss, the streams entering
    each layer and the last one ``[L + 1] x [B, S, D]`` float32)."""
    m = _static(model)
    streams = [embed(params["model"]["embed_tokens"], ids)]
    aux = 0.0
    for l, kind in enumerate(model["layer_types"]):
        h, a = layer_forward(layer_of(params, model, l), streams[-1],
                             knobs(model, kind, ids.shape[1], faults), model=m)
        streams.append(h)
        aux = aux + a
    nll = head_forward(params["model"]["norm"]["scale"], params["lm_head"]["kernel"], streams[-1],
                       ids, eps=float(model["rms_norm_eps"]))
    loss = jnp.mean(nll) + coef * aux / len(model["layer_types"])
    return nll, loss, streams


def grad_norm(params, ids, model, streams, faults=NONE, coef=0.0):
    """The norm of the loss's gradient over every parameter, a layer at a
    time from the last, on the ``streams`` :func:`forward` kept → (the norm,
    {leaf group: its sum of squares})."""
    m = _static(model)
    L = len(model["layer_types"])
    dh, norm_sq, head_sq = head_backward(params["model"]["norm"]["scale"],
                                         params["lm_head"]["kernel"], streams[-1], ids,
                                         eps=float(model["rms_norm_eps"]))
    parts = {"norm": norm_sq, "lm_head": head_sq}
    daux = jnp.asarray(coef / L, F32)
    for l in reversed(range(L)):
        dh, sq = layer_backward(layer_of(params, model, l), streams[l], dh, daux,
                                knobs(model, model["layer_types"][l], ids.shape[1], faults),
                                model=m)
        for path, value in jax.tree_util.tree_leaves_with_path(sq):
            parts[f"layer{l}" + jax.tree_util.keystr(path)] = value
    parts["embed_tokens"] = embed_backward_sumsq(params["model"]["embed_tokens"], ids, dh)
    parts = {k: float(v) for k, v in parts.items()}
    return math.sqrt(sum(parts.values())), parts
