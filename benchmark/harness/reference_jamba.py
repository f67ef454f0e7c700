"""The plain reference of the ``jamba2-3b`` configuration (``model_type:
jamba``): **every layer a mixer and a dense SwiGLU**, the mixer a Mamba-1
state-space layer or, where ``i % attn_layer_period == attn_layer_offset``,
a grouped-query attention with no positional term::

    a = rms(h; w_in);  h <- h + Mixer(a)
    f = rms(h; w_ff);  h <- h + W_down(silu(W_gate f) * (W_up f))
    logits = rms(h; w_f) @ E^T                       (E the embedding: tied;  eps = rms_norm_eps)

    attention:  q [Hq, d], k, v [Hkv, d] = a W_q, a W_k, a W_v
                causal softmax(q k / sqrt(d)) v;  W_o
                (20 query heads over 1 key-value head of 128; no rotary term, no bias, no window)
    Mamba-1:    [x | z] = a W_in                                 widths I | I,   I = mamba_expand D
                x_t <- silu(b_c + sum_{j<K} w_c[j] * x_{t-K+1+j})        rows before the start: 0
                [dt | B | C] = x W_x                             widths R | N | N
                dt = rms(dt; w_dt);  B = rms(B; w_B);  C = rms(C; w_C)
                Delta_t = softplus(dt W_dt + b_dt) [I];   A = -exp(A_log) [N, I]
                S_t = exp(Delta_t * A) * S_{t-1} + (Delta_t * x_t) * B_t[:, None]     [N, I]
                y_t = sum_n S_t[n] C_t[n] + D * x_t;   out = (y_t * silu(z_t)) W_out

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, independent of
``deepspeed_tpu``, with no kernel, no cache, no chunks and no slots, reading
the sizes from the configuration's file; bf16 weights are upcast inside the
program of the one layer that uses them. The recurrence runs **a token at a
time** from a zero state where the served program runs a step's rows
through a slot in a kernel; attention a block of queries at a time. No
departure from the published description is intended; what differs from
the source's modeling file is layout alone: matrices ``[in, out]``, the
mixers of a kind stacked, ``A_log`` a state column a row (``[N, I]``), the
convolution as ``[K, I]`` taps.

Weight tree (``deepspeed_tpu/models/jamba.py`` documents it)::

    model/embed_tokens   model/final_layernorm/scale
    model/mamba_layers/{input_layernorm/scale, in_proj/kernel, conv_kernel [Lm, K, I], conv_bias,
                        x_proj/kernel, dt_layernorm/scale, b_layernorm/scale, c_layernorm/scale,
                        dt_proj/kernel, dt_bias, A_log [Lm, N, I], D, out_proj/kernel}
    model/attn_layers/{input_layernorm/scale, q_proj, k_proj, v_proj, o_proj}/kernel
    model/ffn/{pre_ff_layernorm/scale, gate_proj, up_proj, down_proj}/kernel       [L, ...]
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # attention scores are formed for this many queries at a time
MAMBA, ATTENTION = "mamba", "attention"
STACKS = {MAMBA: "mamba_layers", ATTENTION: "attn_layers"}


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer(stack, i):
    return jax.tree.map(lambda w: w[i].astype(F32), stack)


def layer_kinds(model):
    """The configuration file's ``model`` → each layer's mixer, in order."""
    period, offset = int(model["attn_layer_period"]), int(model["attn_layer_offset"])
    return [ATTENTION if i % period == offset else MAMBA
            for i in range(int(model["num_hidden_layers"]))]


def mamba_mixer(p, x, state, tail, *, state_size, dt_rank, kernel, eps):
    """One Mamba-1 mixer (``p``: one layer's float32 parameters) on x [B, S,
    D] → (y [B, S, D], the state it leaves [B, N, I], the convolution's
    tail it leaves [B, K - 1, I]). ``state`` / ``tail``: what the sequences
    carried in (zeros at a sequence's start)."""
    B, S, _ = x.shape
    I, N, R, K = p["D"].shape[0], state_size, dt_rank, kernel
    xz = x @ p["in_proj"]["kernel"]
    xs, z = xz[..., :I], xz[..., I:]
    padded = jnp.concatenate([tail, xs], axis=1)
    xs = jax.nn.silu(p["conv_bias"] + sum(p["conv_kernel"][j] * padded[:, j:j + S]
                                          for j in range(K)))
    dbc = xs @ p["x_proj"]["kernel"]
    dt = _rms_norm(dbc[..., :R], p["dt_layernorm"]["scale"], eps)
    b = _rms_norm(dbc[..., R:R + N], p["b_layernorm"]["scale"], eps)
    c = _rms_norm(dbc[..., R + N:], p["c_layernorm"]["scale"], eps)
    delta = jax.nn.softplus(dt @ p["dt_proj"]["kernel"] + p["dt_bias"])      # [B, S, I]
    a = -jnp.exp(p["A_log"])                                                # [N, I]

    def one(s, row):
        d_t, x_t, b_t, c_t = row
        s = jnp.exp(d_t[:, None, :] * a) * s + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    last, y = jax.lax.scan(one, state, tuple(jnp.moveaxis(r, 1, 0) for r in (delta, xs, b, c)))
    y = jnp.moveaxis(y, 0, 1) + p["D"] * xs
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"], last, padded[:, S:]


@functools.partial(jax.jit, static_argnames=("state_size", "dt_rank", "kernel", "eps"))
def _mamba_layer(stack, i, h, **kw):
    """→ (h + mixer, the mixer's normalised input, its output, the state
    and the tail the sequences leave), from a zero start."""
    p = _layer(stack, i)
    B, I = h.shape[0], p["D"].shape[0]
    x = _rms_norm(h, p["input_layernorm"]["scale"], kw["eps"])
    y, state, tail = mamba_mixer(p, x, jnp.zeros((B, kw["state_size"], I), F32),
                                 jnp.zeros((B, kw["kernel"] - 1, I), F32), **kw)
    return h + y, x, y, state, tail


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps"))
def _attention_layer(stack, i, h, *, heads, kv_heads, head_dim, eps):
    """→ (h + mixer, the mixer's normalised input, its output)."""
    p = _layer(stack, i)
    B, S, _ = h.shape
    d, group = head_dim, heads // kv_heads
    x = _rms_norm(h, p["input_layernorm"]["scale"], eps)
    q = (x @ p["q_proj"]["kernel"]).reshape(B, S, kv_heads, group, d)
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
    y = jnp.concatenate(outs, axis=1) @ p["o_proj"]["kernel"]
    return h + y, x, y


@functools.partial(jax.jit, static_argnames=("eps",))
def _feed_forward(stack, i, h, *, eps):
    p = _layer(stack, i)
    x = _rms_norm(h, p["pre_ff_layernorm"]["scale"], eps)
    return h + (jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])) \
        @ p["down_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, h, *, eps):
    model = params["model"]
    h = _rms_norm(h, model["final_layernorm"]["scale"].astype(F32), eps)
    return h @ model["embed_tokens"].astype(F32).T


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


def layer_kwargs(model):
    """The configuration file's ``model`` → the static sizes of a layer:
    (Mamba's, attention's)."""
    refused = {"num_experts": model.get("num_experts", 1) != 1,
               "hidden_act": model.get("hidden_act", "silu") != "silu",
               "mamba_proj_bias": model.get("mamba_proj_bias", False),
               "mamba_conv_bias": not model.get("mamba_conv_bias", True),
               "sliding_window": model.get("sliding_window") is not None,
               "tie_word_embeddings": not model.get("tie_word_embeddings", True)}
    if any(refused.values()):
        raise ValueError(f"not in this reference: {[k for k, v in refused.items() if v]}")
    eps = float(model["rms_norm_eps"])
    heads = int(model["num_attention_heads"])
    mamba = dict(state_size=int(model["mamba_d_state"]), dt_rank=int(model["mamba_dt_rank"]),
                 kernel=int(model["mamba_d_conv"]), eps=eps)
    attn = dict(heads=heads, kv_heads=int(model["num_key_value_heads"]),
                head_dim=int(model["hidden_size"]) // heads, eps=eps)
    return mamba, attn


def hidden(params, ids, model, tap=None):
    """ids [B, S] → the last layer's output [B, S, D], float32. A layer at
    a time, each waited for: dispatched ahead of the device, the layers'
    float32 weights and temporaries would all be allocated at once.

    ``tap(kind, i, x, y, state, tail)``: called after mixer ``i`` of its
    kind with what it saw and gave for the whole batch (the normalised
    input, the output, and - a Mamba layer's - the state and the
    convolution's tail the sequences leave; None for an attention layer)."""
    mamba, attn = layer_kwargs(model)
    m = params["model"]
    seen = dict.fromkeys(STACKS, 0)
    done = jax.block_until_ready
    with jax.default_matmul_precision("highest"):
        h = _embed(m["embed_tokens"], ids)
        for position, kind in enumerate(layer_kinds(model)):
            i, stack = jnp.int32(seen[kind]), m[STACKS[kind]]
            if kind == MAMBA:
                h, x, y, state, tail = done(_mamba_layer(stack, i, h, **mamba))
            else:
                (h, x, y), state, tail = done(_attention_layer(stack, i, h, **attn)), None, None
            if tap is not None:
                tap(kind, seen[kind], x, y, state, tail)
            h = done(_feed_forward(m["ffn"], jnp.int32(position), h, eps=attn["eps"]))
            seen[kind] += 1
    return h


def head_at(params, rows, model):
    """rows [B, n, D] of the last layer's output → logits [B, n, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(params, rows, eps=float(model["rms_norm_eps"]))


def rows_at(params, ids, positions, model, tap=None):
    """ids [B, S], positions [B, n] → (the last layer's output at those
    positions [B, n, D]; margins [1, B, n], all one: the model has no
    router, so no position is fragile and ``summarize`` holds every one to
    the tolerance)."""
    h = hidden(params, ids, model, tap)
    positions = jnp.asarray(positions)
    return (jnp.take_along_axis(h, positions[..., None], axis=1),
            jnp.ones((1,) + positions.shape, F32))


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return head_at(params, hidden(params, ids, model), model)
