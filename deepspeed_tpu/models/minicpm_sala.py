"""MiniCPM-SALA (``openbmb/MiniCPM-SALA``): a dense decoder whose layers
are of two kinds in an irregular order (``mixer_types``) —

- ``minicpm4``: grouped-query softmax attention with **InfLLM-v2
  block-sparse selection**: a query reads the first block, its own and
  the blocks of a local window, and the highest-scoring of the rest (at
  most ``sparse_topk`` blocks in all), chosen per key-value head from
  mean-pooled keys; no rotary embedding, per-head RMS norm on queries
  and keys, a sigmoid output gate;
- ``lightning-attn``: Lightning linear attention, a per-head ``[d, d]``
  state with a fixed per-head, per-layer decay; rotary embedding, per-head
  RMS norm on queries and keys, an RMS norm over the concatenated output,
  a sigmoid output gate —

with MiniCPM's muP scalings (``scale_emb`` on the embedding,
``scale_depth / sqrt(published depth)`` on every residual branch,
``hidden_size / dim_model_base`` under the head).

The equations (``D`` hidden, ``H`` heads, ``Hkv`` key-value heads, ``d``
head size, ``L`` the **published** depth, ``l`` a layer's published index;
``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``)::

    h0 = scale_emb * E[token]
    h += (scale_depth / sqrt(L)) * mixer(rms(h; w));  h += (scale_depth / sqrt(L)) * mlp(rms(h; w))
    logits = (rms(h; w_f) / (D / dim_model_base)) @ W_head
    mlp(x) = W_down (silu(W_gate x) * (W_up x))

    lightning-attn:  q, k, v = W_q x, W_k x, W_v x  [H, d] each;  q, k = rope(rms_d(q)), rope(rms_d(k))
        S_t = lambda_h S_{t-1} + k_t^T v_t;   o_t = d^-0.5 q_t S_t       lambda_h = exp(-(8/H)(1 - l/L) h)
        y = W_out (rms(o; w_o) * sigmoid(W_g x))                          (the norm over all H*d)

    minicpm4:  q = rms_d(W_q x) [H, d];  k = rms_d(W_k x), v = W_v x [Hkv, d];  no rope
        p < sparse_from (dense):  o = causal softmax attention, scale d^-0.5
        else: kbar_j = mean(k[stride j : stride j + kernel])   for every j with stride j + kernel <= p + 1
              s = sum over the group's query heads of softmax_j(d^-0.5 q . kbar_j)
              b_i = max of s_j over the kernels that overlap block i;  +inf for the first
              ``init_blocks`` blocks, the block of p and the ``window / block - 1`` before it
              o = causal softmax attention over the rows of the ``topk`` blocks with the largest b
        y = W_o (o * sigmoid(W_g x))

``sparse_from`` is 0 for a sequence whose prompt has ``dense_len`` tokens
or more and ``dense_len - 1`` otherwise (a shorter prompt is prefilled
densely and decodes densely until its context reaches ``dense_len``).

Parameter tree. The sparse layers are few and lie one by one in the
stack, so each keeps its own tree (``sparse_layers/<i>``, ``i`` its index
among the sparse layers); the linear layers are stacked (``Ll`` of them),
which the serving scan reads in place a layer at a time::

    model/embed_tokens [V, D]     model/norm/scale [D]     lm_head/kernel [D, V]
    model/sparse_layers/<i>/{input,post_attention}_layernorm/scale     [D]
    model/sparse_layers/<i>/self_attn/{q,o_gate}_proj/kernel           [D, H*d]
    model/sparse_layers/<i>/self_attn/{k,v}_proj/kernel                [D, Hkv*d]
    model/sparse_layers/<i>/self_attn/{q,k}_norm/scale                 [d]
    model/sparse_layers/<i>/self_attn/o_proj/kernel                    [H*d, D]
    model/sparse_layers/<i>/mlp/{gate,up,down}_proj/kernel             [in, out]
    model/linear_layers/{input,post_attention}_layernorm/scale         [Ll, D]
    model/linear_layers/self_attn/{q,k,v,o_gate}_proj/kernel           [Ll, D, H*d]
    model/linear_layers/self_attn/{q,k}_norm/scale                     [Ll, d]
    model/linear_layers/self_attn/o_norm/scale                         [Ll, H*d]
    model/linear_layers/self_attn/o_proj/kernel                        [Ll, H*d, D]
    model/linear_layers/mlp/{gate,up,down}_proj/kernel                 [Ll, in, out]

Matrices are stored ``[in, out]`` (``x @ kernel``). Serving only:
``inference/v2`` runs this model through ``model_runner.SalaKind`` (paged
keys and values of the sparse layers, a pool of pooled keys, a slot pool
of linear states); :func:`reference_logits` is the plain float32 forward
over whole sequences. Training this model is not implemented.
"""

import dataclasses
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.moonlight import _Tree, _initializer, _rms_norm

SPARSE, LINEAR = "minicpm4", "lightning-attn"

# openbmb/MiniCPM-SALA config.json, mixer_types
PUBLISHED_MIXER_TYPES = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LINEAR for i in range(32))


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32             # the layers that run: len(mixer_types)
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_scale: str = "1/sqrt(d)"
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_norm: bool = True
    use_output_gate: bool = True
    attn_use_output_gate: bool = True
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXER_TYPES    # as run
    # the published index of every layer that runs (None: 0 .. len - 1) and the published
    # depth: what the decay and the residual scale are functions of, whatever the cut
    layer_ids: Optional[Tuple[int, ...]] = None
    published_num_hidden_layers: int = 32
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 524288
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # InfLLM v2's sizes (MiniCPM4's sparse_config; the source's config.json has none)
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        ids = tuple(range(len(self.mixer_types))) if self.layer_ids is None \
            else tuple(self.layer_ids)
        object.__setattr__(self, "layer_ids", ids)
        ks, st, bs = self.sparse_kernel_size, self.sparse_kernel_stride, self.sparse_block_size
        unsupported = {
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act != "silu",
            "qk_norm": not self.qk_norm,
            "attn_use_rope": self.attn_use_rope,
            "lightning_use_rope": not self.lightning_use_rope,
            "use_output_norm": not self.use_output_norm,
            "use_output_gate": not self.use_output_gate,
            "attn_use_output_gate": not self.attn_use_output_gate,
            "lightning_scale": self.lightning_scale != "1/sqrt(d)",
            "lightning_nkv (grouped linear heads)": self.lightning_nkv != self.lightning_nh,
            "lightning_nh": self.lightning_nh != self.num_attention_heads,
            "lightning_head_dim": self.lightning_head_dim != self.head_dim,
            "mixer_types": any(m not in (SPARSE, LINEAR) for m in self.mixer_types)
            or SPARSE not in self.mixer_types or LINEAR not in self.mixer_types,
            "num_hidden_layers": self.num_hidden_layers != len(self.mixer_types),
            "layer_ids": len(ids) != len(self.mixer_types)
            or any(not 0 <= i < self.published_num_hidden_layers for i in ids),
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0,
            # a kernel is two strides long and a block a whole number of strides: the
            # pooled keys are then kept a stride-group of rows at a time
            "sparse_kernel_size": ks != 2 * st,
            "sparse_block_size": bs % st != 0 or bs < ks,
            "sparse_window_size": self.sparse_window_size % bs != 0 or self.sparse_window_size < bs,
            "sparse_dense_len": self.sparse_dense_len % bs != 0,
            "sparse_topk": self.sparse_topk < self.sparse_init_blocks
            + self.sparse_window_size // bs,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"MiniCPMSalaConfig: unsupported setting of {bad}")

    @property
    def sparse_positions(self):
        """The positions in the stack of the sparse layers."""
        return tuple(i for i, m in enumerate(self.mixer_types) if m == SPARSE)

    @property
    def linear_positions(self):
        return tuple(i for i, m in enumerate(self.mixer_types) if m == LINEAR)

    @property
    def embedding_multiplier(self):
        return self.scale_emb

    @property
    def residual_scale(self):
        return self.scale_depth / math.sqrt(self.published_num_hidden_layers)

    @property
    def logit_divisor(self):
        return self.hidden_size / self.dim_model_base

    def log_decay(self, position):
        """log lambda_h, h = 0 .. H-1, of the layer at ``position`` of the stack."""
        l = self.layer_ids[position]
        slope = (8.0 / self.num_attention_heads) * (1.0 - l / self.published_num_hidden_layers)
        return [-slope * h for h in range(self.num_attention_heads)]

    def sparse_from(self, prompt_len):
        """The first position of a sequence that attends sparsely."""
        return 0 if prompt_len >= self.sparse_dense_len else self.sparse_dense_len - 1


MINICPM_SALA_CONFIGS = {
    # one of two pipeline stages (benchmark/configs/minicpm-sala-16l.json): every width as
    # published, the even-numbered layers of the published 32 - minicpm4 at 0, 16, 22, 30
    "minicpm-sala-16l": MiniCPMSalaConfig(
        num_hidden_layers=16, mixer_types=PUBLISHED_MIXER_TYPES[0::2],
        layer_ids=tuple(range(0, 32, 2))),
    # both mixers at a size the CPU tests run: 2 minicpm4 + 4 lightning-attn in an irregular
    # order, 2 key-value heads over 4, and sparse sizes cut so that a context of a few dozen
    # tokens crosses dense_len and has more blocks than topk
    "minicpm-sala-debug": MiniCPMSalaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=160, num_hidden_layers=6,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, lightning_nh=4,
        lightning_nkv=4, lightning_head_dim=16, dim_model_base=16,
        mixer_types=(SPARSE, LINEAR, LINEAR, LINEAR, SPARSE, LINEAR),
        layer_ids=(0, 1, 3, 4, 6, 7), published_num_hidden_layers=8,
        max_position_embeddings=512, sparse_kernel_size=8, sparse_kernel_stride=4,
        sparse_block_size=16, sparse_topk=4, sparse_init_blocks=1, sparse_window_size=32,
        sparse_dense_len=64),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, H, Hkv, d, F = (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
                       cfg.head_dim, cfg.intermediate_size)
    Ll = len(cfg.linear_positions)

    def mlp(lead):
        return {"gate_proj": {"kernel": lead + (D, F)}, "up_proj": {"kernel": lead + (D, F)},
                "down_proj": {"kernel": lead + (F, D)}}

    def layer(lead, kv_width, extra):
        attn = {"q_proj": {"kernel": lead + (D, H * d)}, "k_proj": {"kernel": lead + (D, kv_width)},
                "v_proj": {"kernel": lead + (D, kv_width)},
                "o_gate_proj": {"kernel": lead + (D, H * d)},
                "q_norm": {"scale": lead + (d,)}, "k_norm": {"scale": lead + (d,)},
                "o_proj": {"kernel": lead + (H * d, D)}, **extra}
        return {"input_layernorm": {"scale": lead + (D,)},
                "post_attention_layernorm": {"scale": lead + (D,)},
                "self_attn": attn, "mlp": mlp(lead)}

    sparse = {str(i): layer((), Hkv * d, {}) for i in range(len(cfg.sparse_positions))}
    linear = layer((Ll,), H * d, {"o_norm": {"scale": (Ll, H * d)}})
    return {"model": {"embed_tokens": (cfg.vocab_size, D), "norm": {"scale": (D,)},
                      "sparse_layers": sparse, "linear_layers": linear},
            "lm_head": {"kernel": (D, cfg.vocab_size)}}


class MiniCPMSalaForCausalLM(nn.Module):
    config: MiniCPMSalaConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward, every
        sequence taken as a prompt of S tokens."""
        shapes = param_shapes(self.config)
        params = {name: _Tree(value, _initializer, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_minicpm_sala(preset_or_config="minicpm-sala-debug", **overrides) -> MiniCPMSalaForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, MiniCPMSalaConfig) \
        else MINICPM_SALA_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return MiniCPMSalaForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _f32(x):
    return x.astype(jnp.float32)


def _rope_halves(x, theta):
    """x [B, S, H, d]: the rotary embedding over all d dims, dim i paired
    with dim i + d/2 (the half-split layout), position = the row."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def reference_block_scores(q, k, cfg):
    """The selection's block scores of whole sequences. q [B, S, H, d],
    k [B, S, Hkv, d] (normalised) → b [B, Hkv, S, NB] float32: for the
    query at row p, block i's score — the largest, over the kernels that
    overlap the block and end at or before p, of the group's summed
    softmax over those kernels; +inf for the forced blocks (the first
    ``init_blocks``, the block of p and the local window's before it),
    -inf for the blocks past p's."""
    B, S, H, d = q.shape
    Hkv = k.shape[2]
    ks, st, bs = cfg.sparse_kernel_size, cfg.sparse_kernel_stride, cfg.sparse_block_size
    NB = -(-S // bs)
    J = max(0, (S - ks) // st + 1)
    rows = jnp.arange(S)
    own = rows // bs
    blocks = jnp.arange(NB)
    if J:
        kbar = jnp.stack([k[:, st * j:st * j + ks].mean(axis=1) for j in range(J)], axis=1)
        qg = q.reshape(B, S, Hkv, H // Hkv, d)
        s = jnp.einsum("bpkgd,bjkd->bkgpj", qg, kbar) / math.sqrt(d)
        ended = (st * jnp.arange(J) + ks)[None, :] <= (rows + 1)[:, None]        # [S, J]
        s = jax.nn.softmax(jnp.where(ended, s, -jnp.inf), axis=-1)
        s = jnp.where(ended, s, 0.0).sum(axis=2)            # a row with no kernel yet: nan → 0
        # max_pool1d(kernel 5, stride 4, padding 1) at the published sizes: block i is
        # overlapped by the kernels per*i - 1 .. per*i + per - 1
        per = bs // st
        right = per * (NB - 1) + per - J
        b = jax.lax.reduce_window(s, -jnp.inf, jax.lax.max, (1, 1, 1, per + 1),
                                  (1, 1, 1, per), ((0, 0), (0, 0), (0, 0), (1, right)))
        b = jnp.maximum(b, 0.0)                             # a block no ended kernel overlaps
    else:
        b = jnp.zeros((B, Hkv, S, NB), jnp.float32)
    local = cfg.sparse_window_size // bs
    forced = (blocks[None, :] < cfg.sparse_init_blocks) | (
        (blocks[None, :] <= own[:, None]) & (blocks[None, :] > own[:, None] - local))
    b = jnp.where(forced, jnp.inf, b)
    return jnp.where(blocks[None, :] <= own[:, None], b, -jnp.inf)


def reference_selection(q, k, cfg):
    """→ (chosen [B, Hkv, S, NB] bool: the blocks the query at row p reads
    — the ``topk`` with the largest score, all of them where the context
    has no more; margin [B, Hkv, S]: by how much the last block chosen
    leads the first one left out, +inf where none is left out)."""
    b = reference_block_scores(q, k, cfg)
    NB = b.shape[-1]
    if NB <= cfg.sparse_topk:
        return b > -jnp.inf, jnp.full(b.shape[:-1], jnp.inf)
    ranked, index = jax.lax.top_k(b, cfg.sparse_topk + 1)
    chosen = jnp.sum(jax.nn.one_hot(index[..., :cfg.sparse_topk], NB, dtype=jnp.int32),
                     axis=-2) > 0
    last, nxt = ranked[..., cfg.sparse_topk - 1], ranked[..., cfg.sparse_topk]
    margin = jnp.where(nxt > -jnp.inf, last - nxt, jnp.inf)
    return chosen & (b > -jnp.inf), margin


def reference_sparse_attention(a, x, cfg, sparse_from, rows=None):
    """One ``minicpm4`` layer's mixer on whole sequences: x [B, S, D] (the
    normalised stream), ``sparse_from`` [B] → (y [B, S, D], margin
    [B, S]: the selection's, the smaller of the key-value heads'; +inf at
    dense rows). ``rows``: query rows a pass (None: all at once)."""
    B, S, _ = x.shape
    H, Hkv, d, bs, eps = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                          cfg.sparse_block_size, cfg.rms_norm_eps)
    q = _rms_norm((x @ _f32(a["q_proj"]["kernel"])).reshape(B, S, H, d),
                  _f32(a["q_norm"]["scale"]), eps)
    k = _rms_norm((x @ _f32(a["k_proj"]["kernel"])).reshape(B, S, Hkv, d),
                  _f32(a["k_norm"]["scale"]), eps)
    v = (x @ _f32(a["v_proj"]["kernel"])).reshape(B, S, Hkv, d)
    chosen, margin = reference_selection(q, k, cfg)
    dense = jnp.arange(S)[None, :] < jnp.asarray(sparse_from)[:, None]             # [B, S]
    margin = jnp.where(dense, jnp.inf, margin.min(axis=1))
    qg = q.reshape(B, S, Hkv, H // Hkv, d)
    out = []
    step = S if rows is None else rows
    for r0 in range(0, S, step):
        r1 = min(S, r0 + step)
        p = jnp.arange(r0, r1)
        scores = jnp.einsum("bpkgd,bukd->bkgpu", qg[:, r0:r1], k[:, :r1]) / math.sqrt(d)
        reads = jnp.repeat(chosen[:, :, r0:r1], bs, axis=-1)[..., :r1]             # [B,Hkv,p,u]
        reads = jnp.where(dense[:, None, r0:r1, None], True, reads)
        reads = reads & (jnp.arange(r1)[None, :] <= p[:, None])
        probs = jax.nn.softmax(jnp.where(reads[:, :, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bkgpu,bukd->bpkgd", probs, v[:, :r1]).reshape(B, r1 - r0, H * d))
    o = jnp.concatenate(out, axis=1) * jax.nn.sigmoid(x @ _f32(a["o_gate_proj"]["kernel"]))
    return o @ _f32(a["o_proj"]["kernel"]), margin


def reference_linear_attention(a, x, log_decay, cfg, rows=None):
    """One ``lightning-attn`` layer's mixer on whole sequences, by the
    decay matrix: ``o_t = d^-0.5 sum_{u <= t} lambda_h^(t-u) (q_t . k_u)
    v_u``, which is the recurrence written out. x [B, S, D] → y."""
    B, S, _ = x.shape
    H, d, eps = cfg.num_attention_heads, cfg.head_dim, cfg.rms_norm_eps

    def heads(name):
        return (x @ _f32(a[name]["kernel"])).reshape(B, S, H, d)

    q = _rope_halves(_rms_norm(heads("q_proj"), _f32(a["q_norm"]["scale"]), eps), cfg.rope_theta)
    k = _rope_halves(_rms_norm(heads("k_proj"), _f32(a["k_norm"]["scale"]), eps), cfg.rope_theta)
    v = heads("v_proj")
    log_decay = jnp.asarray(log_decay, jnp.float32)
    out = []
    step = S if rows is None else rows
    for r0 in range(0, S, step):
        r1 = min(S, r0 + step)
        apart = (jnp.arange(r0, r1)[:, None] - jnp.arange(r1)[None, :]).astype(jnp.float32)
        decay = jnp.where(apart >= 0, jnp.exp(log_decay[:, None, None] * jnp.maximum(apart, 0)), 0)
        scores = jnp.einsum("bphd,buhd->bhpu", q[:, r0:r1], k[:, :r1]) * decay[None]
        out.append(jnp.einsum("bhpu,buhd->bphd", scores, v[:, :r1]).reshape(B, r1 - r0, H * d))
    o = jnp.concatenate(out, axis=1) / math.sqrt(d)
    o = _rms_norm(o, _f32(a["o_norm"]["scale"]), eps) * jax.nn.sigmoid(
        x @ _f32(a["o_gate_proj"]["kernel"]))
    return o @ _f32(a["o_proj"]["kernel"])


def reference_recurrence(q, k, v, log_decay):
    """The linear mixer's recurrence, a token at a time: q, k, v [S, H, d]
    (normalised and rotated), log lambda [H] → o [S, H, d] before the
    ``d^-0.5``. What the decay matrix and the served chunks are forms of."""
    lam = jnp.exp(jnp.asarray(log_decay, jnp.float32))[:, None, None]

    def one(state, qkv):
        q_t, k_t, v_t = qkv
        state = lam * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hd,hde->he", q_t, state)

    d = q.shape[-1]
    return jax.lax.scan(one, jnp.zeros((q.shape[1], d, d), jnp.float32), (q, k, v))[1]


def layer_params(params, cfg, position):
    """The parameters of the layer at ``position`` of the stack (a linear
    layer's cut out of the linear stack)."""
    model = params["model"]
    if cfg.mixer_types[position] == SPARSE:
        return model["sparse_layers"][str(cfg.sparse_positions.index(position))]
    i = cfg.linear_positions.index(position)
    return jax.tree.map(lambda w: w[i], model["linear_layers"])


def reference_logits(params, input_ids, cfg, prompt_len=None, positions=None, rows=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no chunks: the linear layers by the decay
    matrix, the sparse layers by an explicit top-k over block scores and a
    mask over all rows. ``prompt_len`` (an int or [B]; None: S): how many
    of a sequence's tokens are its prompt — rows before it are prefilled,
    rows from it on decoded, and that decides ``sparse_from``. ``rows``:
    query rows a pass in the attention layers (None: all), so that a long
    sequence fits.

    Departures from the source's modeling file: weights ``[in, out]``;
    the linear layers stacked; float32 throughout; no attention-mask
    argument, no dropout."""
    eps, scale = cfg.rms_norm_eps, cfg.residual_scale
    B, S = input_ids.shape
    prompt = jnp.broadcast_to(jnp.asarray(S if prompt_len is None else prompt_len), (B,))
    sparse_from = jnp.where(prompt >= cfg.sparse_dense_len, 0, cfg.sparse_dense_len - 1)
    with jax.default_matmul_precision("highest"):
        h = cfg.scale_emb * _f32(params["model"]["embed_tokens"][input_ids])
        for position, mixer in enumerate(cfg.mixer_types):
            lp = layer_params(params, cfg, position)
            x = _rms_norm(h, _f32(lp["input_layernorm"]["scale"]), eps)
            if mixer == SPARSE:
                y, _ = reference_sparse_attention(lp["self_attn"], x, cfg, sparse_from, rows)
            else:
                y = reference_linear_attention(lp["self_attn"], x, cfg.log_decay(position), cfg,
                                               rows)
            h = h + scale * y
            x = _rms_norm(h, _f32(lp["post_attention_layernorm"]["scale"]), eps)
            m = lp["mlp"]
            h = h + scale * ((jax.nn.silu(x @ _f32(m["gate_proj"]["kernel"]))
                              * (x @ _f32(m["up_proj"]["kernel"]))) @ _f32(m["down_proj"]["kernel"]))
        h = _rms_norm(h, _f32(params["model"]["norm"]["scale"]), eps) / cfg.logit_divisor
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ _f32(params["lm_head"]["kernel"])
