"""The plain reference for ``minicpm-sala-16l`` (``openbmb/MiniCPM-SALA``):
float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, whole
sequences, no cache, no chunks, no kernels — and no line of
``deepspeed_tpu``. It reads the served parameter tree (the names of
``deepspeed_tpu/models/minicpm_sala.py``'s docstring) and the
configuration file's ``model`` (the published keys, ``published``, and the
``assumed`` sparse sizes the runner copies into it under ``sparse``).

The equations are ISSUE 34's ("The equations"), in its notation: ``L`` is
the **published** depth in every formula.

- ``h0 = scale_emb * E[token]``; every sublayer adds ``(scale_depth /
  sqrt(L)) * f(rms(h; w))``; logits ``= (rms(h; w_f) / (hidden_size /
  dim_model_base)) @ W_head``.
- ``lightning-attn``: per-head RMS norm on q and k, half-split rotary
  embedding on both, ``o_t = d^-0.5 sum_{u<=t} lambda_h^(t-u) (q_t . k_u)
  v_u`` written as a ``[rows, S]`` decay matrix a pass of query rows
  (``lambda_h = exp(-(8 / H) * (1 - l / L) * h)``), RMS norm over the
  concatenated heads, sigmoid gate, output projection.
- ``minicpm4``: per-head RMS norm on q and k, no rotary embedding;
  InfLLM-v2 selection per key-value head — mean-pooled keys of
  ``kernel_size`` rows every ``kernel_stride``, the group's summed softmax
  over the pooled keys that end at or before the query, ``max_pool1d(5, 4,
  1)`` onto blocks, the first block, the query's and the local window's
  forced, the ``topk`` best blocks read — then one causal softmax over the
  rows of the blocks read; a sequence whose prompt is shorter than
  ``dense_len`` attends densely until its context reaches it.

A long sequence fits because the attention layers run ``ROWS`` query rows
a pass against all the keys under a mask (``lax.map``: one compiled pass)
and the layers run one after the other through two jitted functions (one a
kind of layer), each given its layer's parameters.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 128          # query rows a pass of an attention layer: [heads, ROWS, S] float32 scores
MLP_ROWS = 512      # rows a pass of the feed-forward: [MLP_ROWS, intermediate] float32
SPARSE, LINEAR = "minicpm4", "lightning-attn"


def _f32(x):
    return x.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(w)


def _rope(x, theta):
    """x [S, H, d]: dim i rotates with dim i + d/2, position = the row."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _row_blocks(x, rows):
    """x [S, ...] → [ceil(S / rows), rows, ...], zero-padded."""
    pad = -x.shape[0] % rows
    x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x.reshape((-1, rows) + x.shape[1:])


def _by_rows(fn, rows, S, *arrays):
    """``fn(first row, *blocks)`` over passes of ``rows`` rows of the
    arrays (one compiled pass, mapped), the results laid back to [S, ...]."""
    blocks = [_row_blocks(a, rows) for a in arrays]
    firsts = rows * jnp.arange(blocks[0].shape[0])
    out = jax.lax.map(lambda args: fn(args[0], *args[1:]), (firsts, *blocks))
    return out.reshape((-1,) + out.shape[2:])[:S]


def sizes(model):
    """The sizes the formulas read, from the configuration's ``model``."""
    sparse = model["sparse"]
    return dict(H=model["num_attention_heads"], Hkv=model["num_key_value_heads"],
                d=model["head_dim"], eps=model["rms_norm_eps"], theta=model["rope_theta"],
                L=model["published"]["num_hidden_layers"],
                residual=model["scale_depth"] / math.sqrt(model["published"]["num_hidden_layers"]),
                ks=sparse["kernel_size"], st=sparse["kernel_stride"], bs=sparse["block_size"],
                topk=sparse["topk"], init=sparse["init_blocks"],
                local=sparse["window_size"] // sparse["block_size"], dense_len=sparse["dense_len"])


def layer_ids(model):
    """The published index of every layer that runs."""
    return model.get("layer_ids") or list(range(len(model["mixer_types"])))


def log_decay(model, position):
    z = sizes(model)
    slope = (8.0 / z["H"]) * (1.0 - layer_ids(model)[position] / z["L"])
    return np.asarray([-slope * h for h in range(z["H"])], np.float32)


def layer_tree(params, model, position):
    """The parameters of the layer at ``position`` of the stack."""
    kinds = model["mixer_types"]
    if kinds[position] == SPARSE:
        return params["model"]["sparse_layers"][str(kinds[:position].count(SPARSE))]
    i = kinds[:position].count(LINEAR)
    return jax.tree.map(lambda w: w[i], params["model"]["linear_layers"])


def block_scores(q, k, z):
    """q [S, H, d], k [S, Hkv, d] (normalised) → b [Hkv, S, NB]: block i's
    score for the query at row p; +inf forced, -inf past p's block."""
    S, H, d = q.shape
    Hkv, ks, st, bs = z["Hkv"], z["ks"], z["st"], z["bs"]
    NB = -(-S // bs)
    J = max(0, (S - ks) // st + 1)
    rows = jnp.arange(S)
    own = rows // bs
    if J:
        starts = st * jnp.arange(J)
        window = starts[:, None] + jnp.arange(ks)[None, :]                    # [J, ks]
        kbar = k[window].mean(axis=1)                                         # [J, Hkv, d]

        def summed(r0, qb):                                                   # qb [ROWS, H, d]
            at = r0 + jnp.arange(ROWS)
            on = (starts + ks)[None, :] <= (at + 1)[:, None]                  # [ROWS, J]
            s = jnp.einsum("pkgd,jkd->pkgj", qb.reshape(ROWS, Hkv, H // Hkv, d), kbar)
            s = jax.nn.softmax(jnp.where(on[:, None, None], s / math.sqrt(d), -jnp.inf), axis=-1)
            return jnp.where(on[:, None], s.sum(axis=2), 0.0)                 # [ROWS, Hkv, J]

        s = jnp.moveaxis(_by_rows(summed, ROWS, S, q), 0, 1)                  # [Hkv, S, J]
        per = bs // st
        # max_pool1d(kernel per + 1, stride per, padding 1): 5, 4, 1 as published
        b = jax.lax.reduce_window(s, -jnp.inf, jax.lax.max, (1, 1, per + 1), (1, 1, per),
                                  ((0, 0), (0, 0), (1, per * NB - J)))
        b = jnp.maximum(b, 0.0)
    else:
        b = jnp.zeros((Hkv, S, NB), jnp.float32)
    blocks = jnp.arange(NB)[None, :]
    forced = (blocks < z["init"]) | ((blocks <= own[:, None]) & (blocks > own[:, None] - z["local"]))
    b = jnp.where(forced, jnp.inf, b)
    return jnp.where(blocks <= own[:, None], b, -jnp.inf)


def selection(q, k, z):
    """→ (chosen [Hkv, S, NB] bool, margin [Hkv, S]: the lead of the last
    block chosen over the first left out; +inf where none is left out)."""
    b = block_scores(q, k, z)
    NB = b.shape[-1]
    if NB <= z["topk"]:
        return b > -jnp.inf, jnp.full(b.shape[:-1], jnp.inf)
    ranked, index = jax.lax.top_k(b, z["topk"] + 1)
    hot = jax.nn.one_hot(index[..., :z["topk"]], NB, dtype=jnp.int8).sum(axis=-2) > 0
    last, nxt = ranked[..., z["topk"] - 1], ranked[..., z["topk"]]
    return hot & (b > -jnp.inf), jnp.where(nxt > -jnp.inf, last - nxt, jnp.inf)


def sparse_mixer(a, x, sparse_from, z):
    """x [S, D] (the normalised stream) → (y [S, D], chosen [Hkv, S, NB],
    margin [S]: the smaller of the key-value heads', +inf at dense rows)."""
    S = x.shape[0]
    H, Hkv, d, bs, eps = z["H"], z["Hkv"], z["d"], z["bs"], z["eps"]
    q = _rms((x @ _f32(a["q_proj"]["kernel"])).reshape(S, H, d), a["q_norm"]["scale"], eps)
    k = _rms((x @ _f32(a["k_proj"]["kernel"])).reshape(S, Hkv, d), a["k_norm"]["scale"], eps)
    v = (x @ _f32(a["v_proj"]["kernel"])).reshape(S, Hkv, d)
    chosen, margin = selection(q, k, z)
    dense = jnp.arange(S) < sparse_from
    margin = jnp.where(dense, jnp.inf, margin.min(axis=0))
    keys = jnp.arange(S)

    def attend(r0, qb, reads, dense_rows):          # [ROWS, H, d], [ROWS, Hkv, NB], [ROWS]
        at = r0 + jnp.arange(ROWS)
        scores = jnp.einsum("pkgd,ukd->pkgu", qb.reshape(ROWS, Hkv, H // Hkv, d), k)
        reads = jnp.repeat(reads, bs, axis=-1)[..., :S] | dense_rows[:, None, None]
        reads = reads & (keys[None, None, :] <= at[:, None, None])
        probs = jax.nn.softmax(jnp.where(reads[:, :, None], scores / math.sqrt(d), -jnp.inf),
                               axis=-1)
        probs = jnp.where(reads[:, :, None], probs, 0.0)        # a padded row reads nothing
        return jnp.einsum("pkgu,ukd->pkgd", probs, v).reshape(ROWS, H * d)

    o = _by_rows(attend, ROWS, S, q, jnp.moveaxis(chosen, 0, 1), dense)
    o = o * jax.nn.sigmoid(x @ _f32(a["o_gate_proj"]["kernel"]))
    return o @ _f32(a["o_proj"]["kernel"]), chosen, margin


def linear_mixer(a, x, decay_log, z):
    """x [S, D] → y [S, D], by the decay matrix."""
    S = x.shape[0]
    H, d, eps = z["H"], z["d"], z["eps"]

    def heads(name):
        return (x @ _f32(a[name]["kernel"])).reshape(S, H, d)

    q = _rope(_rms(heads("q_proj"), a["q_norm"]["scale"], eps), z["theta"])
    k = _rope(_rms(heads("k_proj"), a["k_norm"]["scale"], eps), z["theta"])
    v = heads("v_proj")
    keys = jnp.arange(S)

    def attend(r0, qb):                                                       # qb [ROWS, H, d]
        apart = ((r0 + jnp.arange(ROWS))[:, None] - keys[None, :]).astype(jnp.float32)
        decay = jnp.where(apart >= 0, jnp.exp(decay_log[:, None, None] * jnp.maximum(apart, 0)), 0)
        scores = jnp.einsum("phd,uhd->hpu", qb, k) * decay
        return jnp.einsum("hpu,uhd->phd", scores, v).reshape(ROWS, H * d)

    o = _by_rows(attend, ROWS, S, q)
    o = _rms(o / math.sqrt(d), a["o_norm"]["scale"], eps)
    o = o * jax.nn.sigmoid(x @ _f32(a["o_gate_proj"]["kernel"]))
    return o @ _f32(a["o_proj"]["kernel"])


def _mlp(lp, h, z):
    m = lp["mlp"]

    def rows(r0, hb):
        x = _rms(hb, lp["post_attention_layernorm"]["scale"], z["eps"])
        return (jax.nn.silu(x @ _f32(m["gate_proj"]["kernel"]))
                * (x @ _f32(m["up_proj"]["kernel"]))) @ _f32(m["down_proj"]["kernel"])

    return h + z["residual"] * _by_rows(rows, MLP_ROWS, h.shape[0], h)


@functools.lru_cache(maxsize=None)
def _layer_fns(frozen):
    z = dict(frozen)

    def sparse(lp, h, sparse_from):
        with jax.default_matmul_precision("highest"):
            x = _rms(h, lp["input_layernorm"]["scale"], z["eps"])
            y, chosen, margin = sparse_mixer(lp["self_attn"], x, sparse_from, z)
            return _mlp(lp, h + z["residual"] * y, z), x, y, chosen, margin

    def linear(lp, h, decay_log):
        with jax.default_matmul_precision("highest"):
            x = _rms(h, lp["input_layernorm"]["scale"], z["eps"])
            return _mlp(lp, h + z["residual"] * linear_mixer(lp["self_attn"], x, decay_log, z), z)

    return jax.jit(sparse), jax.jit(linear)


def sequence(params, ids, prompt_len, model, tap=None):
    """One sequence ids [S] whose first ``prompt_len`` tokens are its
    prompt → (h [S, D] after the last layer, before the final norm;
    margin [sparse layers, S]). ``tap``: called, a sparse layer, with
    ``(x, y, chosen, margin)`` — the mixer's input (the normalised
    stream), its output, the blocks read and the margins."""
    z = sizes(model)
    sparse_fn, linear_fn = _layer_fns(tuple(sorted(z.items())))
    sparse_from = jnp.int32(0 if prompt_len >= z["dense_len"] else z["dense_len"] - 1)
    h = model["scale_emb"] * _f32(params["model"]["embed_tokens"][jnp.asarray(ids)])
    margins = []
    for position, mixer in enumerate(model["mixer_types"]):
        lp = layer_tree(params, model, position)
        if mixer == SPARSE:
            h, x, y, chosen, margin = sparse_fn(lp, h, sparse_from)
            margins.append(margin)
            if tap is not None:
                tap(x, y, chosen, margin)
        else:
            h = linear_fn(lp, h, jnp.asarray(log_decay(model, position)))
    return h, jnp.stack(margins)


def rows_at(params, ids, positions, model, tap=None):
    """ids [b, S] (zero-padded: what follows a sequence's last token does
    not reach it), positions [b, n], whose first is each sequence's
    prefill end → (the last layer's rows there [b, n, D]; margins [sparse
    layers, b, n]). A sequence at a time."""
    rows, margins = [], []
    for i in range(ids.shape[0]):
        at = jnp.asarray(positions[i])
        h, margin = sequence(params, ids[i], int(positions[i][0]) + 1, model, tap)
        rows.append(h[at])
        margins.append(margin[:, at])
    return jnp.stack(rows), jnp.stack(margins, axis=1)


def head_at(params, rows, model):
    """Rows [b, n, D] of the last layer → logits [b, n, V]."""
    with jax.default_matmul_precision("highest"):
        h = _rms(rows, params["model"]["norm"]["scale"], model["rms_norm_eps"])
        h = h / (model["hidden_size"] / model["dim_model_base"])
        return h @ _f32(params["lm_head"]["kernel"])


def logits(params, ids, model, prompt_len=None):
    """ids [b, S] → logits [b, S, V]: the whole forward (tests at debug size)."""
    S = ids.shape[1]
    rows = [sequence(params, ids[i], S if prompt_len is None else prompt_len, model)[0]
            for i in range(ids.shape[0])]
    return head_at(params, jnp.stack(rows), model)
