"""The plain reference of the Ouro configuration (``ouro-2.6b``): one stack
of ``L`` layers applied ``R = total_ut_steps`` times with the same weights,
the model's norm after every pass, the exit gate (the published
``modeling_ouro.py``'s forward; *Scaling Latent Reasoning via Looped
Language Models*)::

    h = E[ids]
    for u in 0 .. R-1:
        for l in 0 .. L-1:
            h <- h + rms(Attn_l(rms(h; n1_l)); n2_l)
            h <- h + rms(SwiGLU_l(rms(h; n3_l)); n4_l)
        h <- rms(h; n_f);   x_u = h;   g_u = sigmoid(x_u w_g + b_g)
    p_u = g_u prod_{j<u} (1 - g_j)  (u < R-1);   p_{R-1} = prod_{j<R-1} (1 - g_j)
    exit step = the first u with sum_{j<=u} p_j >= early_exit_threshold, else R-1
    logits = x_exit W_head

    Attn_l: rotary (by halves, all columns) multi-head causal softmax attention; a pass sees
    the keys its own pass computed, because it computes them from the stream that entered it.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching of ragged sequences; attention a block of query rows at a time.
Independent of ``deepspeed_tpu``: it reads the sizes from the
configuration's file and the weights as a tree of arrays (the names
``deepspeed_tpu/models/ouro.py`` documents), applied layer by layer with that
layer's bf16 weights upcast, so that one layer exists in float32 at a time.

``forward``'s ``passes``, ``leave_out`` and ``lower`` are the controls'
(``benchmark/tests/control_ouro.py``): what a program with that fault would
give; a benchmark run uses none.
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256       # attention scores are formed for this many query rows at a time
LOOP_NORM, SANDWICH_NORMS = "loop_norm", "sandwich_norms"


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, H, d] rotated by halves over all columns, position = row."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """Causal softmax attention, [B, S, H, d] with keys and values already
    expanded to H heads, a block of query rows at a time."""
    S, scale = q.shape[1], 1.0 / math.sqrt(q.shape[-1])
    out = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[:, start:start + QUERY_BLOCK]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        visible = jnp.arange(S)[None, :] <= (start + jnp.arange(qb.shape[1]))[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta", "sandwich"))
def _layer(layers, layer, h, *, heads, kv_heads, eps, theta, sandwich):
    """One block; ``layers`` is the stacked tree, ``layer`` its index."""
    B, S, _ = h.shape
    lp = jax.tree.map(lambda w: w[layer].astype(F32), layers)
    attn, mlp = lp["self_attn"], lp["mlp"]
    x = _rms_norm(h, lp["input_layernorm"]["scale"], eps)
    q = _rope((x @ attn["q_proj"]["kernel"]).reshape(B, S, heads, -1), theta)
    k = _rope((x @ attn["k_proj"]["kernel"]).reshape(B, S, kv_heads, -1), theta)
    v = (x @ attn["v_proj"]["kernel"]).reshape(B, S, kv_heads, -1)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    a = _attention(q, k, v).reshape(B, S, -1) @ attn["o_proj"]["kernel"]
    h = h + (_rms_norm(a, lp["input_layernorm_2"]["scale"], eps) if sandwich else a)
    f = _rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
    m = (jax.nn.silu(f @ mlp["gate_proj"]["kernel"]) * (f @ mlp["up_proj"]["kernel"])) \
        @ mlp["down_proj"]["kernel"]
    return h + (_rms_norm(m, lp["post_attention_layernorm_2"]["scale"], eps) if sandwich else m)


@functools.partial(jax.jit, static_argnames=("eps", "normed"))
def _close_pass(norm, gate, h, *, eps, normed):
    """→ (x_u: the stream the model's norm leaves, g_u)."""
    if normed:
        h = _rms_norm(h, norm["scale"].astype(F32), eps)
    g = jax.nn.sigmoid((h @ gate["kernel"].astype(F32))[..., 0] + gate["bias"].astype(F32)[0])
    return h, g


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


@jax.jit
def _head(kernel, x):
    return x @ kernel.astype(F32)


def exit_steps(gates, threshold):
    """``gates`` [R, ...] → the exit step [...] int32: the first pass whose
    cumulative exit probability reaches ``threshold``, else the last (which
    takes what the others left: its own gate is not read)."""
    R = gates.shape[0]
    if R == 1:
        return jnp.zeros(gates.shape[1:], jnp.int32)
    stay = jnp.cumprod(1.0 - gates[:R - 1], axis=0)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:R - 2]], axis=0)
    reached = jnp.cumsum(gates[:R - 1] * before, axis=0) >= threshold
    return jnp.where(jnp.any(reached, axis=0), jnp.argmax(reached, axis=0), R - 1).astype(jnp.int32)


def forward(params, ids, model, passes=None, leave_out=(), lower=None):
    """ids [B, S] → {"logits" [B, S, V], "passes" [R, B, S, D] (every pass's
    ``x_u``), "gates" [R, B, S], "exit_step" [B, S]}, float32.

    The controls': ``passes``, a number of passes other than the
    configuration's; ``leave_out``, of ``LOOP_NORM`` (the model's norm once,
    after the last pass) and ``SANDWICH_NORMS`` (a sublayer's output joins
    the residual as computed); ``lower(x)``, every layer's leaves, the
    embedding rows, the head and the stream after every layer rounded by it,
    **op by op** (inside one program the chip's compiler may keep the excess
    precision of a cast down and back: PERF.md, PR 45)."""
    R = int(model["total_ut_steps"]) if passes is None else passes
    L, eps = int(model["num_hidden_layers"]), float(model["rms_norm_eps"])
    kw = dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"], eps=eps,
              theta=float(model["rope_theta"]), sandwich=SANDWICH_NORMS not in leave_out)
    m = params["model"]
    low = (lambda x: x) if lower is None else lower
    with jax.default_matmul_precision("highest"):
        h = low(_embed(m["embed_tokens"], ids))
        xs, gs = [], []
        for u in range(R):
            for i in range(L):
                if lower is None:
                    h = _layer(m["layers"], jnp.int32(i), h, **kw)
                else:
                    one = jax.tree.map(lambda w: lower(w[i])[None], m["layers"])
                    h = jax.block_until_ready(lower(_layer(one, jnp.int32(0), h, **kw)))
            h, g = _close_pass(m["norm"], m["early_exit_gate"], h, eps=eps,
                               normed=LOOP_NORM not in leave_out or u == R - 1)
            xs.append(h)
            gs.append(g)
        xs, gs = jnp.stack(xs), jnp.stack(gs)
        exit_step = exit_steps(gs, float(model["early_exit_threshold"]))
        x = jnp.take_along_axis(xs, exit_step[None, ..., None], axis=0)[0]
        return {"logits": _head(low(params["lm_head"]["kernel"]), x), "passes": xs, "gates": gs,
                "exit_step": exit_step}


def logits(params, ids, model):
    """ids [B, S] → next-token logits [B, S, V], float32."""
    return forward(params, ids, model)["logits"]
