"""LongCat-Flash (``meituan-longcat/LongCat-Flash-Omni``'s language model):
**double layers** of two latent attentions and two dense SwiGLUs with a
shortcut expert layer beside the second half, behind a softmax router
whose columns outnumber its experts — ``n_routed_experts`` SwiGLU experts
and ``zero_expert_num`` zero-compute ones (``zero_expert_type:
identity``: the expert returns its input).

The config dataclass holds the published ``config.json``'s keys under
their own names, and the **share** of an expert-parallel deployment this
process holds: ``experts_held`` routed experts from ``first_expert_held``
(None: all of them). The router keeps every column and its ``moe_topk``
picks whatever the share; the expert layer computes ``sum_j w_j E_j(x)``
over the picks whose expert is held and over the zero-compute picks (a
token's identity part is computed where the token lives), and what the
absent experts would add is left out — here and in
:func:`reference_logits` alike.

The double layer ``l`` over the stream ``h`` (``A_i`` latent attention
with state layer ``2l + i``, ``F_i`` dense SwiGLU, ``M`` the experts)::

    h1 = h  + A_0(RMS_a0(h));   x = RMS_p0(h1);   m = M(x)
    h2 = h1 + F_0(x)
    h3 = h2 + A_1(RMS_a1(h2))
    h4 = h3 + F_1(RMS_p1(h3)) + m

Parameter tree, under the checkpoint's module names; what the checkpoint
keeps as a list of layers is stacked (``L`` double layers), and the pair
inside a layer keeps its index ``i`` in {0, 1} as a name, as in the
checkpoint's ``self_attn.0.q_a_proj`` (every weight is then ``[L, in,
out]``, which the serving scan reads in place, a layer at a time; ``Eh``
experts held, ``C`` = routed + zero columns)::

    model/embed_tokens [V, D]     model/norm/scale [D]     lm_head/kernel [D, V]
    model/layers/{input,post_attention}_layernorm/i/scale              [L, D]
    model/layers/self_attn/i/q_a_proj/kernel                           [L, D, q_lora_rank]
    model/layers/self_attn/i/q_a_layernorm/scale                       [L, q_lora_rank]
    model/layers/self_attn/i/q_b_proj/kernel                           [L, q_lora_rank, H*(nope+rope)]
    model/layers/self_attn/i/kv_a_proj_with_mqa/kernel                 [L, D, kv_lora_rank+rope]
    model/layers/self_attn/i/kv_a_layernorm/scale                      [L, kv_lora_rank]
    model/layers/self_attn/i/kv_b_proj/kernel                          [L, kv_lora_rank, H*(nope+v)]
    model/layers/self_attn/i/o_proj/kernel                             [L, H*v, D]
    model/layers/mlps/i/{gate,up,down}_proj/kernel                     [L, in, out]
    model/layers/mlp/router/classifier/weight [L, D, C]
    model/layers/mlp/router/e_score_correction_bias [L, C]
    model/layers/mlp/experts/{gate,up,down}_proj [L, Eh, in, out]

Matrices are stored ``[in, out]`` (``x @ kernel``). Serving only:
``inference/v2`` runs this model through its latent paged cache with
``kv_b_proj`` absorbed (``model_runner.LongcatKind``);
:func:`reference_logits` is the plain float32 forward that expands it.
Training this model is not implemented.
"""

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.moonlight import _Tree, _rms_norm, _rope


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288            # a dense SwiGLU's width
    expert_ffn_hidden_size: int = 2048      # one routed expert's width
    num_layers: int = 28                    # double layers
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512             # the router's routed columns, whatever is held
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    norm_topk_prob: bool = False
    router_bias: bool = False
    rope_theta: float = 1e7
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    attention_method: str = "MLA"
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # the share of an expert-parallel deployment held here (None: every routed expert)
    experts_held: Optional[int] = None
    first_expert_held: int = 0

    def __post_init__(self):
        unsupported = {
            "attention_method": self.attention_method != "MLA",
            "q_lora_rank (a full-rank query)": self.q_lora_rank is None,
            "zero_expert_type": self.zero_expert_type != "identity",
            "norm_topk_prob": self.norm_topk_prob,
            "router_bias": self.router_bias,
            "rope_scaling (YaRN)": self.rope_scaling is not None,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act != "silu",
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"LongcatFlashConfig: unsupported setting of {bad}")
        if not 0 < self.moe_topk <= self.n_routed_experts + self.zero_expert_num:
            raise ValueError("LongcatFlashConfig: moe_topk exceeds the router's columns")
        if not (0 <= self.first_expert_held
                and 0 < self.held and self.first_expert_held + self.held <= self.n_routed_experts):
            raise ValueError(
                f"LongcatFlashConfig: experts {self.first_expert_held}..+{self.held} are not "
                f"among the {self.n_routed_experts} routed")

    @property
    def held(self):
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    @property
    def router_columns(self):
        return self.n_routed_experts + self.zero_expert_num

    @property
    def query_scale(self):
        """``mla_scale_q_lora``: sqrt(hidden_size / q_lora_rank) on the query."""
        return math.sqrt(self.hidden_size / self.q_lora_rank) if self.mla_scale_q_lora else 1.0

    @property
    def latent_scale(self):
        """``mla_scale_kv_lora``: sqrt(hidden_size / kv_lora_rank) on the
        normalised compressed row (not on the rotated key)."""
        return math.sqrt(self.hidden_size / self.kv_lora_rank) if self.mla_scale_kv_lora else 1.0

    # the names the latent-state machinery reads off any config it serves
    @property
    def num_hidden_layers(self):
        return self.num_layers

    @property
    def num_key_value_heads(self):
        return self.num_attention_heads


LONGCAT_CONFIGS = {
    # one chip's share of 32-way expert parallelism (benchmark/configs/
    # longcat-flash-omni-ep32.json): every width as published, 4 of the 28 double
    # layers, experts 0-15 of 512, an eighth of the vocabulary
    "longcat-flash-omni-ep32": LongcatFlashConfig(num_layers=4, vocab_size=16384,
                                                  experts_held=16),
    # every mechanism at a size the CPU tests run: two double layers, 8 routed
    # experts + 4 zero, top 3, a low-rank query, nope / rope / v head sizes all different
    "longcat-flash-debug": LongcatFlashConfig(
        vocab_size=256, hidden_size=64, ffn_hidden_size=160, expert_ffn_hidden_size=48,
        num_layers=2, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=24,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
        zero_expert_num=4, moe_topk=3, max_position_embeddings=512),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, H, L = cfg.hidden_size, cfg.num_attention_heads, cfg.num_layers
    r, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, I, C, Eh = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size, cfg.router_columns, cfg.held
    attention = {"q_a_proj": {"kernel": (L, D, qr)}, "q_a_layernorm": {"scale": (L, qr)},
                 "q_b_proj": {"kernel": (L, qr, H * (dn + dr))},
                 "kv_a_proj_with_mqa": {"kernel": (L, D, r + dr)},
                 "kv_a_layernorm": {"scale": (L, r)},
                 "kv_b_proj": {"kernel": (L, r, H * (dn + dv))},
                 "o_proj": {"kernel": (L, H * dv, D)}}
    dense = {"gate_proj": {"kernel": (L, D, F)}, "up_proj": {"kernel": (L, D, F)},
             "down_proj": {"kernel": (L, F, D)}}
    pair = lambda half: {"0": half, "1": half}  # noqa: E731
    layers = {
        "input_layernorm": pair({"scale": (L, D)}),
        "post_attention_layernorm": pair({"scale": (L, D)}),
        "self_attn": pair(attention),
        "mlps": pair(dense),
        "mlp": {"router": {"classifier": {"weight": (L, D, C)},
                           "e_score_correction_bias": (L, C)},
                "experts": {"gate_proj": (L, Eh, D, I), "up_proj": (L, Eh, D, I),
                            "down_proj": (L, Eh, I, D)}},
    }
    return {"model": {"embed_tokens": (cfg.vocab_size, D), "norm": {"scale": (D,)},
                      "layers": layers},
            "lm_head": {"kernel": (D, cfg.vocab_size)}}


def _initializer(name):
    if name == "scale":
        return nn.initializers.ones
    if name == "e_score_correction_bias":
        # the checkpoint's is trained and non-zero; zeros would hide a router that
        # weights by the biased score. Half the mean softmax score (1 / columns):
        # it moves picks without choosing them alone
        return lambda key, shape, dtype=jnp.float32: \
            nn.initializers.normal(0.5 / shape[-1])(key, shape, dtype)
    return nn.initializers.normal(0.02)


class LongcatFlashForCausalLM(nn.Module):
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        params = {name: _Tree(value, _initializer, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_longcat(preset_or_config="longcat-flash-debug", **overrides) -> LongcatFlashForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, LongcatFlashConfig) \
        else LONGCAT_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return LongcatFlashForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _f32(x):
    return x.astype(jnp.float32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def reference_router(mlp, x, cfg):
    """x [..., D] → (weights [..., C], margin [...]): softmax over every
    column in float32; the ``moe_topk`` columns with the largest score +
    bias; each weighted by its *unbiased* score, not normalised, times
    ``routed_scaling_factor``; zero elsewhere. ``margin``: by how much the
    last column chosen leads the first one left out, in score + bias."""
    scores = jax.nn.softmax(x @ _f32(mlp["router"]["classifier"]["weight"]), axis=-1)
    ranked, chosen = jax.lax.top_k(scores + _f32(mlp["router"]["e_score_correction_bias"]),
                                   cfg.moe_topk + 1)
    chosen = chosen[..., :cfg.moe_topk]
    picked = jnp.take_along_axis(scores, chosen, axis=-1) * cfg.routed_scaling_factor
    weights = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
                      * picked[..., None], axis=-2)
    return weights, ranked[..., cfg.moe_topk - 1] - ranked[..., cfg.moe_topk]


def reference_experts(mlp, x, cfg, zero=True):
    """``M(x)`` as this share gives it: every held expert applied to every
    token, one at a time, weighted (zero where the router did not choose
    it), plus — ``zero`` — the zero-compute picks' ``(sum of their
    weights) * x``. ``mlp``: one layer's router and its ``cfg.held``
    experts ``[Eh, in, out]``."""
    weights, _ = reference_router(mlp, x, cfg)
    out = jnp.zeros_like(x)
    for e in range(cfg.held):
        ex = mlp["experts"]
        out = out + weights[..., cfg.first_expert_held + e, None] * _swiglu(
            x, ex["gate_proj"][e], ex["up_proj"][e], ex["down_proj"][e])
    if zero:
        out = out + jnp.sum(weights[..., cfg.n_routed_experts:], axis=-1, keepdims=True) * x
    return out


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``, given ``cfg``'s share.

    No cache and no absorption: ``kv_b_proj`` is **expanded** into
    per-head keys and values and ordinary causal attention runs over
    (nope+rope)-wide heads; every held expert is applied to every token.
    The serving path absorbs ``kv_b_proj`` and runs a grouped matmul over
    the held picks; the two share no line.

    Departures from the source's modeling file: weights are ``[in, out]``
    and stacked over layers; float32 throughout; no attention
    mask argument, no dropout, no YaRN (the config has no
    ``rope_scaling``)."""
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.rms_norm_eps

    def attention(a, x):
        B, S, _ = x.shape
        c_q = _rms_norm(x @ _f32(a["q_a_proj"]["kernel"]), _f32(a["q_a_layernorm"]["scale"]), eps)
        q = (c_q @ _f32(a["q_b_proj"]["kernel"])).reshape(B, S, H, dn + dr) * cfg.query_scale
        kv_a = x @ _f32(a["kv_a_proj_with_mqa"]["kernel"])
        c_kv = _rms_norm(kv_a[..., :r], _f32(a["kv_a_layernorm"]["scale"]), eps) * cfg.latent_scale
        kv = (c_kv @ _f32(a["kv_b_proj"]["kernel"])).reshape(B, S, H, dn + dv)    # expanded
        q_rope = _rope(q[..., dn:], cfg.rope_theta)
        k_rope = _rope(kv_a[:, :, None, r:], cfg.rope_theta)                      # one head, shared
        qf = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        kf = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(dn + dr)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:]).reshape(B, S, H * dv)
        return out @ _f32(a["o_proj"]["kernel"])

    def half(lp, i):
        return {k: lp[k][str(i)] for k in (
            "input_layernorm", "post_attention_layernorm", "self_attn", "mlps")}

    def dense(p, x):
        return _swiglu(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                       p["down_proj"]["kernel"])

    with jax.default_matmul_precision("highest"):
        model = params["model"]
        h = _f32(model["embed_tokens"][input_ids])
        for l in range(cfg.num_layers):
            lp = jax.tree.map(lambda x: x[l], model["layers"])
            a, b = half(lp, 0), half(lp, 1)
            h = h + attention(a["self_attn"], _rms_norm(h, _f32(a["input_layernorm"]["scale"]), eps))
            x = _rms_norm(h, _f32(a["post_attention_layernorm"]["scale"]), eps)
            m = reference_experts(lp["mlp"], x, cfg)          # the shortcut: joins at the end
            h = h + dense(a["mlps"], x)
            h = h + attention(b["self_attn"], _rms_norm(h, _f32(b["input_layernorm"]["scale"]), eps))
            h = h + dense(b["mlps"], _rms_norm(h, _f32(b["post_attention_layernorm"]["scale"]),
                                               eps)) + m
        h = _rms_norm(h, _f32(model["norm"]["scale"]), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ _f32(params["lm_head"]["kernel"])
