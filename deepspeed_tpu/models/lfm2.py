"""LFM2-MoE (``model_type: lfm2_moe``; e.g. ``LiquidAI/LFM2-24B-A2B``): a
decoder whose layers are each **an operator and a feed-forward**, the
operator of the kind ``layer_types`` names -

- ``conv``: a **gated short convolution**: the stream is projected to three
  parts, one gates the second *before* a depth-wise causal convolution of
  ``conv_L_cache`` taps, the third gates it *after*; no bias, no activation;
- ``full_attention``: grouped-query softmax attention with an RMS norm over
  each query and key head and rotary positions in halves -

and the feed-forward a dense SwiGLU in the ``num_dense_layers`` leading
layers and, after them, ``num_experts`` whole SwiGLU experts behind a
sigmoid router with a selection bias and no shared expert.

The equations (``D`` hidden, ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``,
``eps`` = ``norm_eps``)::

    h <- h + Op_t(rms(h; operator_norm_t));   h <- h + FFN_t(rms(h; ffn_norm_t))
    logits = rms(h; embedding_norm) @ E^T                   (E the embedding: the head is tied)

    conv:  [B | C | x] = u W_in            each D wide
           g_t = B_t * x_t
           v_t = sum_{j<K} w[j] * g_{t-K+1+j}          (rows before the start: 0), K = conv_L_cache
           y_t = (C_t * v_t) W_out

    full_attention:  q [Hq, d] = rms_head(u W_q; q_layernorm),  k [Hkv, d] likewise (k_layernorm)
           (one scale of d, over every head's d);  q, k rotated in halves at theta;  v = u W_v
           causal softmax(q k / sqrt(d)) v, Hq / Hkv query heads a key-value head;  W_o

    dense FFN:  (silu(u W_1) * u W_3) W_2                   intermediate_size wide
    expert FFN: s = sigmoid(u W_g) [E] in float32;  the k picks: the largest of s + expert_bias;
           w_j = routed_scaling_factor * s_j / (sum of the picks' s + 1e-6)     (norm_topk_prob)
           sum_j w_j (silu(u W1_j) * u W3_j) W2_j           moe_intermediate_size wide

The state a sequence carries through a ``conv`` layer is **the convolution's
tail**: the last ``K - 1`` rows of ``g``, the same at token 10 and at token
100,000.

Parameter tree: the operators of a kind and the feed-forwards of a kind are
stacked, each in layer order (``Lc`` conv, ``La`` attention operators; ``Ld``
dense, ``Le`` expert feed-forwards; layer ``t``'s feed-forward is dense row
``t`` or expert row ``t - Ld``), matrices ``[in, out]``; the checkpoint's
``w1`` / ``w3`` / ``w2`` are ``gate_proj`` / ``up_proj`` / ``down_proj``::

    model/embed_tokens [V, D]      model/embedding_norm/scale [D]      (no lm_head: tied)
    model/conv_layers/operator_norm/scale [Lc, D]     .../in_proj/kernel [Lc, D, 3 D]
    model/conv_layers/conv_kernel [Lc, K, D]          .../out_proj/kernel [Lc, D, D]
    model/attn_layers/operator_norm/scale [La, D]     .../{q,k,v,out}_proj/kernel [La, in, out]
    model/attn_layers/{q,k}_layernorm/scale [La, d]
    model/dense_ffn/ffn_norm/scale [Ld, D]            .../{gate,up,down}_proj/kernel [Ld, in, out]
    model/moe_ffn/ffn_norm/scale [Le, D]              .../gate/weight [Le, D, E]
    model/moe_ffn/gate/expert_bias [Le, E]          .../experts/{gate,up,down}_proj [Le, E, in, out]

Serving only: ``inference/v2`` runs this model through
``model_runner.Lfm2Kind`` (paged keys and values of the attention operators,
a slot a sequence of convolution tails); :func:`reference_logits` is the
plain float32 forward over whole sequences.
"""

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.moonlight import _Tree, _rms_norm
from deepspeed_tpu.models.nemotron_h import _uniform, segments_of

CONV, ATTENTION = "conv", "full_attention"
TOPK_EPS = 1e-6     # what the source adds to the sum the picks' scores are divided by

# LiquidAI/LFM2-24B-A2B config.json, layer_types: attention at layers 2, 6, ..., 38
PUBLISHED_LAYER_TYPES = (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9 + (ATTENTION, CONV)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776          # the leading dense layers' width
    moe_intermediate_size: int = 1536       # one expert's width
    num_hidden_layers: int = 40             # the layers that run: len(layer_types)
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unsupported = {
            "layer_types": not self.layer_types
            or any(t not in (CONV, ATTENTION) for t in self.layer_types),
            "num_hidden_layers": self.num_hidden_layers != len(self.layer_types),
            "conv_bias": self.conv_bias,
            "conv_L_cache": self.conv_L_cache < 2,
            "norm_topk_prob": not self.norm_topk_prob,
            "use_expert_bias": not self.use_expert_bias,
            "tie_word_embeddings": not self.tie_word_embeddings,
            "num_attention_heads": self.hidden_size % self.num_attention_heads != 0
            or (self.hidden_size // self.num_attention_heads) % 2 != 0,
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"Lfm2MoeConfig: unsupported setting of {bad}")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("Lfm2MoeConfig: num_dense_layers exceeds the layers")
        if not 0 < self.num_experts_per_tok <= self.num_experts:
            raise ValueError("Lfm2MoeConfig: num_experts_per_tok exceeds num_experts")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def num_moe_layers(self):
        return self.num_hidden_layers - self.num_dense_layers

    def count(self, layer_type):
        return self.layer_types.count(layer_type)

    @property
    def letters(self):
        """A letter a layer: ``c`` / ``a`` its operator (conv, attention),
        upper case where its feed-forward is dense. The published stack is
        ``CC`` then ``accc`` nine times, ``ac``."""
        operators = ("c" if t == CONV else "a" for t in self.layer_types)
        return "".join(c.upper() if i < self.num_dense_layers else c
                       for i, c in enumerate(operators))

    @property
    def segments(self):
        """The stack as ``[(unit, repeats), ...]`` over :attr:`letters`, cut
        as ``NemotronHConfig.segments`` cuts its pattern: the longest
        stretch of a repeating unit wherever the letters repeat, single
        layers (the leading ones) elsewhere."""
        return segments_of(self.letters)


LFM2_CONFIGS = {
    "lfm2-24b-a2b": Lfm2MoeConfig(),
    # stage 0 of a four-stage pipeline (benchmark/configs/lfm2-24b-a2b-10l.json): every
    # width, all 64 experts and the whole vocabulary as published, the published layers 0-9
    # (the two leading dense layers and two whole periods)
    "lfm2-24b-a2b-10l": Lfm2MoeConfig(num_hidden_layers=10,
                                      layer_types=PUBLISHED_LAYER_TYPES[:10]),
    # every mechanism at a size the CPU tests run: a dense leading layer of each operator's
    # kind, then a period that is scanned twice and a tail that is not; a head of 16, two
    # query heads a key-value head, 8 experts of which 3 are picked
    "lfm2-debug": Lfm2MoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
        num_hidden_layers=9,
        layer_types=(CONV, ATTENTION) + (ATTENTION, CONV, CONV) * 2 + (CONV,),
        num_attention_heads=4, num_key_value_heads=2, num_dense_layers=2, num_experts=8,
        num_experts_per_tok=3, max_position_embeddings=512),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, K, d = cfg.hidden_size, cfg.conv_L_cache, cfg.head_dim
    Lc, La, Ld, Le = cfg.count(CONV), cfg.count(ATTENTION), cfg.num_dense_layers, \
        cfg.num_moe_layers
    q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    F, I, E = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.num_experts
    model = {"embed_tokens": (cfg.vocab_size, D), "embedding_norm": {"scale": (D,)}}
    if Lc:
        model["conv_layers"] = {
            "operator_norm": {"scale": (Lc, D)}, "in_proj": {"kernel": (Lc, D, 3 * D)},
            "conv_kernel": (Lc, K, D), "out_proj": {"kernel": (Lc, D, D)}}
    if La:
        model["attn_layers"] = {
            "operator_norm": {"scale": (La, D)}, "q_proj": {"kernel": (La, D, q)},
            "k_proj": {"kernel": (La, D, kv)}, "v_proj": {"kernel": (La, D, kv)},
            "out_proj": {"kernel": (La, q, D)},
            "q_layernorm": {"scale": (La, d)}, "k_layernorm": {"scale": (La, d)}}
    if Ld:
        model["dense_ffn"] = {
            "ffn_norm": {"scale": (Ld, D)}, "gate_proj": {"kernel": (Ld, D, F)},
            "up_proj": {"kernel": (Ld, D, F)}, "down_proj": {"kernel": (Ld, F, D)}}
    if Le:
        model["moe_ffn"] = {
            "ffn_norm": {"scale": (Le, D)},
            "gate": {"weight": (Le, D, E), "expert_bias": (Le, E)},
            "experts": {"gate_proj": (Le, E, D, I), "up_proj": (Le, E, D, I),
                        "down_proj": (Le, E, I, D)}}
    return {"model": model}


EXPERT_STACKS = ("gate_proj", "up_proj", "down_proj")     # the leaves [Le, E, in, out]


def initializer_of(cfg):
    """A parameter's name → its initializer: the convolution uniform in
    ``+- 1 / sqrt(conv_L_cache)`` (a depth-wise ``Conv1d``'s own), the
    selection bias not zero. **The expert stacks are drawn a layer at a
    time**: one of the cut's is 8 x 64 x 2048 x 1536 = 1.6 G values, whose
    random bits alone, drawn at once, are 6 GiB beside the 9.8 GiB of
    parameters a 16 GB chip then holds (the engine's one set-up program
    would not load); a layer at a time it holds 0.75 GiB of them."""
    bound = 1.0 / math.sqrt(cfg.conv_L_cache)
    table = {"scale": nn.initializers.ones, "conv_kernel": _uniform(-bound, bound),
             # the checkpoint's is trained and non-zero; zeros would hide a router that
             # weights by the biased score
             "expert_bias": nn.initializers.normal(0.1)}

    def of(name):
        draw = table.get(name, nn.initializers.normal(0.02))
        if name not in EXPERT_STACKS:
            return draw
        return lambda key, shape: jax.lax.map(lambda k: draw(k, shape[1:]),
                                              jax.random.split(key, shape[0]))

    return of


class Lfm2MoeForCausalLM(nn.Module):
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        init = initializer_of(self.config)
        params = {name: _Tree(value, init, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_lfm2(preset_or_config="lfm2-debug", **overrides) -> Lfm2MoeForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, Lfm2MoeConfig) \
        else LFM2_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return Lfm2MoeForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rope_halves(x, theta):
    """x [B, S, H, d] rotated by halves at positions 0 .. S - 1."""
    S, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def reference_conv(p, x, cfg, tail=None):
    """One ``conv`` operator on whole sequences: x [B, S, D] (the normalised
    stream) → (y [B, S, D], the tail it leaves [B, K - 1, D]: the last rows
    of the gated stream ``g``). The convolution as ``K`` shifted products;
    ``tail``: what the sequences carried in (None: a sequence's start,
    zero)."""
    p = _f32(p)
    B, S, D = x.shape
    K = cfg.conv_L_cache
    bcx = x @ p["in_proj"]["kernel"]
    b, c, xx = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    before = jnp.zeros((B, K - 1, D), jnp.float32) if tail is None else tail.astype(jnp.float32)
    padded = jnp.concatenate([before, b * xx], axis=1)
    v = sum(p["conv_kernel"][j] * padded[:, j:j + S] for j in range(K))
    return (c * v) @ p["out_proj"]["kernel"], padded[:, S:]


def reference_attention(p, x, cfg):
    """One ``full_attention`` operator: x [B, S, D] → y; causal."""
    p = _f32(p)
    B, S, _ = x.shape
    Hq, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _rms_norm((x @ p["q_proj"]["kernel"]).reshape(B, S, Hq, d), p["q_layernorm"]["scale"],
                  cfg.norm_eps)
    k = _rms_norm((x @ p["k_proj"]["kernel"]).reshape(B, S, Hkv, d), p["k_layernorm"]["scale"],
                  cfg.norm_eps)
    v = (x @ p["v_proj"]["kernel"]).reshape(B, S, Hkv, d)
    q = _rope_halves(q, cfg.rope_theta).reshape(B, S, Hkv, Hq // Hkv, d)
    k = _rope_halves(k, cfg.rope_theta)
    scores = jnp.einsum("bpkgd,bukd->bkgpu", q, k) / math.sqrt(d)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgpu,bukd->bpkgd", probs, v).reshape(B, S, Hq * d)
    return out @ p["out_proj"]["kernel"]


def reference_router(p, x, cfg):
    """→ (weights [..., E]: ``routed_scaling_factor * s_j / (sum of the
    picks' s + 1e-6)`` at the picks, zero elsewhere; margin [...]: the last
    pick's lead over the first column left out, of ``s + expert_bias``)."""
    gate = _f32(p["gate"])
    s = jax.nn.sigmoid(x @ gate["weight"])
    biased = s + gate["expert_bias"]
    k = cfg.num_experts_per_tok
    ranked, chosen = jax.lax.top_k(biased, min(k + 1, biased.shape[-1]))
    picked = jnp.sum(jax.nn.one_hot(chosen[..., :k], s.shape[-1], dtype=jnp.float32), axis=-2) > 0
    weights = jnp.where(picked, s, 0.0)
    weights = cfg.routed_scaling_factor * weights / (weights.sum(-1, keepdims=True) + TOPK_EPS)
    margin = ranked[..., k - 1] - ranked[..., k] if ranked.shape[-1] > k \
        else jnp.full(s.shape[:-1], jnp.inf)
    return weights, margin


def reference_swiglu(p, x):
    p = _f32(p)
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])) \
        @ p["down_proj"]["kernel"]


def reference_experts(p, x, cfg):
    """One expert feed-forward: x [..., D] → y. Every expert applied to
    every token, weighted (zero where the router did not pick it)."""
    weights, _ = reference_router(p, x, cfg)
    experts = _f32(p["experts"])

    def one(acc, e):
        out = (jax.nn.silu(x @ experts["gate_proj"][e]) * (x @ experts["up_proj"][e])) \
            @ experts["down_proj"][e]
        return acc + out * jnp.take(weights, e, axis=-1)[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(cfg.num_experts))
    return y


def layer_params(params, cfg, position):
    """→ (the operator's parameters, the feed-forward's) of the layer at
    ``position`` of the stack, each cut out of its kind's stack."""
    kind = cfg.layer_types[position]
    i = cfg.layer_types[:position].count(kind)
    model = params["model"]
    op = jax.tree.map(lambda w: w[i], model["conv_layers" if kind == CONV else "attn_layers"])
    if position < cfg.num_dense_layers:
        ffn = jax.tree.map(lambda w: w[position], model["dense_ffn"])
    else:
        ffn = jax.tree.map(lambda w: w[position - cfg.num_dense_layers], model["moe_ffn"])
    return op, ffn


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no chunks, no slots, no kernels: the
    convolution as shifted products from a zero start, attention by a mask
    over all rows, an explicit top-k and every expert on every token.

    Departures from the source's modeling file: weights ``[in, out]``, the
    operators and feed-forwards of a kind stacked; the convolution as
    ``[K, D]`` taps; float32 throughout; no attention-mask argument, no
    dropout."""
    eps = cfg.norm_eps
    with jax.default_matmul_precision("highest"):
        embed = params["model"]["embed_tokens"].astype(jnp.float32)
        h = embed[input_ids]
        for position, kind in enumerate(cfg.layer_types):
            op, ffn = layer_params(params, cfg, position)
            x = _rms_norm(h, op["operator_norm"]["scale"].astype(jnp.float32), eps)
            h = h + (reference_conv(op, x, cfg)[0] if kind == CONV
                     else reference_attention(op, x, cfg))
            x = _rms_norm(h, ffn["ffn_norm"]["scale"].astype(jnp.float32), eps)
            h = h + (reference_swiglu(ffn, x) if position < cfg.num_dense_layers
                     else reference_experts(ffn, x, cfg))
        h = _rms_norm(h, params["model"]["embedding_norm"]["scale"].astype(jnp.float32), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ embed.T
