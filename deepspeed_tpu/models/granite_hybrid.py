"""Granite 4.0-H (``model_type: granitemoehybrid``; e.g.
``ibm-granite/granite-4.0-h-small``, "32B-A9B"): a decoder in which **every
layer is a mixer and a routed feed-forward**, each under a residual
multiplier - the mixer a Mamba-2 state-space layer or, once in ten layers,
a grouped-query attention with **no positional term** (``layer_types``);
the feed-forward ``num_local_experts`` small SwiGLU experts behind a
softmax-over-the-picks router beside one shared SwiGLU on every token -
over a tied vocabulary that is scaled on the way in and divided on the
way out.

The equations (``D`` hidden, ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``,
``eps`` = ``rms_norm_eps``; ``e`` = ``embedding_multiplier`` 12, ``r`` =
``residual_multiplier`` 0.22, ``a`` = ``attention_multiplier`` 1/128, ``s``
= ``logits_scaling`` 16)::

    h0 = e * E[ids]
    h <- h + r * Mixer_l(rms(h; w_l))          for every layer l, by layer_types[l]
    h <- h + r * (MoE_l(u) + Shared_l(u)),     u = rms(h; w'_l)
    logits = (rms(h_L; w_f) E^T) / s           (the one tied matrix E)

    mamba:  [z | xBC | dt] = x W_in            widths I | I + 2 G N | H   (I = H P = expand D)
        xBC_t <- silu(b_c + sum_{j<K} w_c[j] * xBC_{t-K+1+j})             (rows before the start: 0)
        x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t);  head h reads group h // (H / G)
            (granite-4.0-h-small: G = 1, one B and one C row shared by all 128 heads)
        Delta_t = softplus(dt_t + dt_bias) [H];  a_t = exp(-Delta_t exp(A_log))
        S_t = a_t S_{t-1} + Delta_t x_t (x) B_t   [H, P, N] float32, S_{-1} = 0
        y_t = S_t C_t + D x_t
        out = (w_n * rms_{groups of I / G}(y * silu(z))) W_out
            (the gate before the norm; at G = 1 the norm runs over all of I)

    attention:  q [Hq, d], k, v [Hkv, d] = x W_q, x W_k, x W_v;  causal softmax(a q k) v;  W_o
        (scores scaled by attention_multiplier, not 1 / sqrt(d); no rotary embedding:
        position_embedding_type "nope"; no bias)

    MoE:  l = u W_r [E] float32;  the k picks: the largest of l;
        w = softmax over those k of l;   expert j: (silu(u W1_j) * (u W3_j)) W2_j
        MoE(u) = sum_j w_j expert_j(u);   Shared(u) = (silu(u Ws1) * (u Ws3)) Ws2

The state a sequence carries through a ``mamba`` layer is ``S`` and **the
convolution's tail**: the last ``K - 1`` rows of ``xBC`` before the
activation. ``mamba_chunk_size`` is the published kernels' blocking and
changes no result.

**Departures from the source's modeling file**, none of the mathematics:
weights ``[in, out]``, the layers of a kind stacked; the published fused
``input_linear`` ``[2 F, D]`` of an expert (and of the shared expert) is held
as its two halves, ``gate_proj`` (the half under ``silu``: rows ``0..F``) and
``up_proj`` (rows ``F..2 F``), each ``[D, F]``; the convolution as ``[K, C]``
taps; the router's softmax is taken over all ``E`` columns and the picks'
weights divided by their sum, which is the softmax over the picks (``exp(l_j)
/ Z`` over ``sum_picks exp(l_i) / Z``); float32 throughout in the reference;
no attention-mask argument, no dropout. Refused: projection biases, a
convolution without bias, untied embeddings, a positional term, ``rope``
position embeddings.

The state-space parameters' initial draws (``A_log``, ``dt_bias``, ``D``,
the convolution) and the step's clamp are **assumed**: the catalog's config
does not give them, and they are drawn as ``models/nemotron_h.py`` draws
its own (``time_step_min`` 0.001, ``time_step_max`` 0.1, ``time_step_floor``
1e-4: the family's Mamba-2 defaults).

An **expert share** (``experts_held`` of ``num_local_experts`` from
``first_expert_held``): the router keeps every column and every pick; the
held picks alone are computed, and what experts held elsewhere would add
is left out (``ops/grouped_gemm.ExpertShare``). The router and the shared
expert are whole on every share.

Parameter tree: the layers of a kind are stacked (``Lm`` mamba, ``La``
attention mixers in stack order; ``L`` feed-forwards), matrices ``[in, out]``::

    model/embed_tokens [V, D]     model/norm/scale [D]          (no lm_head: tied)
    model/mamba_layers/norm/scale [Lm, D]      .../in_proj/kernel [Lm, D, 2 I + 2 G N + H]
    model/mamba_layers/conv_kernel [Lm, K, I + 2 G N]     .../conv_bias [Lm, I + 2 G N]
    model/mamba_layers/{dt_bias, A_log, D} [Lm, H]        .../gate_norm/scale [Lm, I]
    model/mamba_layers/out_proj/kernel [Lm, I, D]
    model/attn_layers/norm/scale [La, D]       .../{q,k,v,o}_proj/kernel [La, in, out]
    model/moe_layers/norm/scale [L, D]         .../router/weight [L, D, E]
    model/moe_layers/experts/{gate,up}_proj [L, held, D, F]   .../down_proj [L, held, F, D]
    model/moe_layers/shared_experts/{gate,up}_proj/kernel [L, D, Fs]  .../down_proj/kernel [L, Fs, D]

Serving only: ``inference/v2`` runs this model through
``model_runner.GraniteHybridKind`` (paged keys and values of the attention
layers, a slot a sequence of Mamba states and convolution tails, and a
snapshot of a slot at a block boundary for the prefix cache);
:func:`reference_logits` is the plain float32 forward over whole sequences,
the recurrence a token at a time.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import nemotron_h
from deepspeed_tpu.models.moonlight import _Tree, _rms_norm
from deepspeed_tpu.models.nemotron_h import _f32, reference_mamba, segments_of

MAMBA, ATTENTION = "mamba", "attention"

# ibm-granite/granite-4.0-h-small config.json, layer_types: an attention layer at 5, 15, 25, 35
PUBLISHED_LAYER_TYPES = tuple(ATTENTION if i % 10 == 5 else MAMBA for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_hidden_layers: int = 40
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    # the four multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_bias: bool = False
    position_embedding_type: str = "nope"
    # Mamba-2
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # assumed (the module docstring): the family's Mamba-2 defaults
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the feed-forward
    num_local_experts: int = 72             # the router's columns, whatever is held
    num_experts_per_tok: int = 10
    intermediate_size: int = 768            # one expert's width
    shared_intermediate_size: int = 1536
    hidden_act: str = "silu"
    normalization_function: str = "rmsnorm"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072
    # the share of an expert-parallel deployment held here (None: every expert)
    experts_held: Optional[int] = None
    first_expert_held: int = 0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        types = self.layer_types
        unsupported = {
            "layer_types": not types or any(t not in (MAMBA, ATTENTION) for t in types),
            "num_hidden_layers": self.num_hidden_layers != len(types),
            "attention_bias": self.attention_bias,
            "mamba_proj_bias": self.mamba_proj_bias,
            "mamba_conv_bias": not self.mamba_conv_bias,
            "tie_word_embeddings": not self.tie_word_embeddings,
            "position_embedding_type": self.position_embedding_type != "nope",
            "hidden_act": self.hidden_act != "silu",
            "normalization_function": self.normalization_function != "rmsnorm",
            "mamba_expand": self.mamba_n_heads * self.mamba_d_head
            != self.mamba_expand * self.hidden_size,
            "mamba_n_groups": self.mamba_n_groups < 1
            or self.mamba_n_heads % max(self.mamba_n_groups, 1) != 0,
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0
            or self.hidden_size % self.num_attention_heads != 0,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"GraniteHybridConfig: unsupported setting of {bad}")
        if not 0 < self.num_experts_per_tok <= self.num_local_experts:
            raise ValueError("GraniteHybridConfig: num_experts_per_tok exceeds the router's columns")
        if not (0 <= self.first_expert_held
                and 0 < self.held and self.first_expert_held + self.held <= self.num_local_experts):
            raise ValueError(
                f"GraniteHybridConfig: experts {self.first_expert_held}..+{self.held} are not "
                f"among the {self.num_local_experts} routed")

    @property
    def held(self):
        return self.num_local_experts if self.experts_held is None else self.experts_held

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    # what the Mamba-2 mixer written for Nemotron-H (``model_runner._mamba_mixer``,
    # ``nemotron_h.reference_mamba``) reads of a config, under that family's names
    mamba_num_heads = property(lambda self: self.mamba_n_heads)
    mamba_head_dim = property(lambda self: self.mamba_d_head)
    n_groups = property(lambda self: self.mamba_n_groups)
    ssm_state_size = property(lambda self: self.mamba_d_state)
    conv_kernel = property(lambda self: self.mamba_d_conv)
    layer_norm_epsilon = property(lambda self: self.rms_norm_eps)

    @property
    def mamba_inner(self):
        """``I``: the Mamba mixer's inner width, heads x head size."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        """The convolution's channels: ``x``, ``B`` and ``C`` side by side."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def count(self, kind):
        return self.layer_types.count(kind)

    @property
    def letters(self):
        """A letter a layer, by its mixer: ``m`` | ``a``."""
        return "".join(t[0] for t in self.layer_types)

    @property
    def segments(self):
        """The stack as ``[(unit, repeats), ...]`` of :attr:`letters`: the
        serving stack scans a unit that repeats. ``nemotron_h.segments_of``
        finds the periods of two layers or more; a layer here is whole (a
        mixer and its feed-forward), so a run of one letter is a scan of that
        one layer too: ``mmmmmammmm`` is ``[("m", 5), ("a", 1), ("m", 4)]``,
        and the published forty layers are ``[("mmmmmammmm", 4)]``."""
        out = []
        for unit, repeats in segments_of(self.letters):
            if len(set(unit)) == 1:
                unit, repeats = unit[0], len(unit) * repeats
            if out and out[-1][0] == unit and len(unit) == 1:
                out[-1] = (unit, out[-1][1] + repeats)
            else:
                out.append((unit, repeats))
        return tuple(out)


GRANITE_HYBRID_CONFIGS = {
    # rank 0 of 4-way expert parallelism, pipeline stage 1 of 4 (benchmark/configs/
    # granite4-h-small-ep4-10l.json): every width as published, the published layers 10-19
    # (one whole period: five mamba, the attention layer, four mamba), experts 0-17 of 72,
    # a quarter of the tied vocabulary
    "granite4-h-small-ep4-10l": GraniteHybridConfig(
        num_hidden_layers=10, layer_types=PUBLISHED_LAYER_TYPES[10:20], vocab_size=25088,
        experts_held=18),
    # every mechanism at a size the CPU tests run: one group of 4 heads, a convolution of
    # 4, 8 experts of which 3 are picked, and a pattern that scans a period, then changes it
    "granite-hybrid-debug": GraniteHybridConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=6,
        layer_types=(MAMBA, ATTENTION, MAMBA, ATTENTION, MAMBA, MAMBA),
        num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.125,
        mamba_n_heads=4, mamba_d_head=32, mamba_n_groups=1, mamba_d_state=16,
        num_local_experts=8, num_experts_per_tok=3, intermediate_size=48,
        shared_intermediate_size=96, max_position_embeddings=512),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, H, I, C = cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_inner, cfg.conv_dim
    Lm, La, L = cfg.count(MAMBA), cfg.count(ATTENTION), cfg.num_hidden_layers
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    F, Fs = cfg.intermediate_size, cfg.shared_intermediate_size
    model = {"embed_tokens": (cfg.vocab_size, D), "norm": {"scale": (D,)}}
    if Lm:
        model["mamba_layers"] = {
            "norm": {"scale": (Lm, D)}, "in_proj": {"kernel": (Lm, D, I + C + H)},
            "conv_kernel": (Lm, cfg.mamba_d_conv, C), "conv_bias": (Lm, C),
            "dt_bias": (Lm, H), "A_log": (Lm, H), "D": (Lm, H),
            "gate_norm": {"scale": (Lm, I)}, "out_proj": {"kernel": (Lm, I, D)}}
    if La:
        model["attn_layers"] = {
            "norm": {"scale": (La, D)}, "q_proj": {"kernel": (La, D, q)},
            "k_proj": {"kernel": (La, D, kv)}, "v_proj": {"kernel": (La, D, kv)},
            "o_proj": {"kernel": (La, q, D)}}
    model["moe_layers"] = {
        "norm": {"scale": (L, D)}, "router": {"weight": (L, D, cfg.num_local_experts)},
        "experts": {"gate_proj": (L, cfg.held, D, F), "up_proj": (L, cfg.held, D, F),
                    "down_proj": (L, cfg.held, F, D)},
        "shared_experts": {"gate_proj": {"kernel": (L, D, Fs)}, "up_proj": {"kernel": (L, D, Fs)},
                           "down_proj": {"kernel": (L, Fs, D)}}}
    return {"model": model}


class GraniteHybridForCausalLM(nn.Module):
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        # the state-space parameters as nemotron_h draws its own: decays where a trained model's lie
        init = nemotron_h.initializer_of(self.config)
        params = {name: _Tree(value, init, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_granite_hybrid(preset_or_config="granite-hybrid-debug",
                         **overrides) -> GraniteHybridForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, GraniteHybridConfig) \
        else GRANITE_HYBRID_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return GraniteHybridForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference (the Mamba-2 mixer is nemotron_h.reference_mamba: the same layer)
# ----------------------------------------------------------------------------


def reference_attention(p, x, cfg, scale=None):
    """One attention mixer: x [B, S, D] → y; causal, no positional term,
    scores times ``attention_multiplier`` (``scale``: a control's)."""
    p = _f32(p)
    B, S, _ = x.shape
    Hq, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = (x @ p["q_proj"]["kernel"]).reshape(B, S, Hkv, Hq // Hkv, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(B, S, Hkv, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(B, S, Hkv, d)
    scores = jnp.einsum("bpkgd,bukd->bkgpu", q, k) * (
        cfg.attention_multiplier if scale is None else scale)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgpu,bukd->bpkgd", probs, v).reshape(B, S, Hq * d)
    return out @ p["o_proj"]["kernel"]


def reference_router(p, x, cfg):
    """→ (weights [..., E]: the softmax over the ``k`` picked logits at the
    picks, zero elsewhere; margin [...]: the last pick's lead over the first
    column left out, of the logits)."""
    logits = x @ p["router"]["weight"]
    k = cfg.num_experts_per_tok
    ranked, chosen = jax.lax.top_k(logits, min(k + 1, logits.shape[-1]))
    picked = jnp.sum(jax.nn.one_hot(chosen[..., :k], logits.shape[-1], dtype=jnp.float32),
                     axis=-2) > 0
    weights = jax.nn.softmax(jnp.where(picked, logits, -jnp.inf), axis=-1)
    margin = ranked[..., k - 1] - ranked[..., k] if ranked.shape[-1] > k \
        else jnp.full(logits.shape[:-1], jnp.inf)
    return weights, margin


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])) \
        @ p["down_proj"]["kernel"]


def reference_experts(p, x, cfg, share=None, shared=True, weights=None):
    """One layer's feed-forward: x [..., D] → y. Every held expert applied to
    every token, weighted (zero where the router did not pick it). ``share``:
    (first, held) of the router's columns (None: the config's own share),
    the experts ``p`` holds; ``shared``: whether the shared expert is added
    (every share computes it alike: a sum over shares counts it once);
    ``weights``: a control's, in place of the router's."""
    p = _f32(p)
    first, held = (cfg.first_expert_held, cfg.held) if share is None else share
    if weights is None:
        weights, _ = reference_router(p, x, cfg)
    e = p["experts"]

    def one(acc, i):
        out = (jax.nn.silu(x @ e["gate_proj"][i]) * (x @ e["up_proj"][i])) @ e["down_proj"][i]
        return acc + out * jnp.take(weights, first + i, axis=-1)[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if shared:
        y = y + swiglu(p["shared_experts"], x)
    return y


def layer_params(params, cfg, position):
    """→ (the mixer's, the feed-forward's) parameters of the layer at
    ``position``, each cut out of its kind's stack."""
    kind = cfg.layer_types[position]
    i = cfg.layer_types[:position].count(kind)
    stack = "mamba_layers" if kind == MAMBA else "attn_layers"
    model = params["model"]
    return (jax.tree.map(lambda w: w[i], model[stack]),
            jax.tree.map(lambda w: w[position], model["moe_layers"]))


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no chunks: the state-space recurrence a
    token at a time from a zero state, attention by a mask over all rows,
    an explicit top-k and every held expert on every token. Departures
    from the source's modeling file: the module docstring's."""
    eps, r = cfg.rms_norm_eps, cfg.residual_multiplier
    with jax.default_matmul_precision("highest"):
        embed = params["model"]["embed_tokens"].astype(jnp.float32)
        h = cfg.embedding_multiplier * embed[input_ids]
        for position, kind in enumerate(cfg.layer_types):
            mp, fp = layer_params(params, cfg, position)
            x = _rms_norm(h, mp["norm"]["scale"].astype(jnp.float32), eps)
            y = reference_mamba(mp, x, cfg)[0] if kind == MAMBA else reference_attention(mp, x, cfg)
            h = h + r * y
            u = _rms_norm(h, fp["norm"]["scale"].astype(jnp.float32), eps)
            h = h + r * reference_experts(fp, u, cfg)
        h = _rms_norm(h, params["model"]["norm"]["scale"].astype(jnp.float32), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return (h @ embed.T) / cfg.logits_scaling
