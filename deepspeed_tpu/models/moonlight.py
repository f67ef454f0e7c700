"""Moonlight-16B-A3B (``model_type: deepseek_v3``): multi-head latent
attention, a leading dense layer, then layers of many small routed
experts beside shared ones behind a sigmoid ``noaux_tc`` router.

The config dataclass holds the published ``config.json``'s keys under
their own names (https://huggingface.co/moonshotai/Moonlight-16B-A3B).
The parameter tree uses the checkpoint's module names; what the
checkpoint keeps as a list of layers is stacked here, the leading dense
layers under ``model/dense_layers`` and the expert layers under
``model/layers`` (the serving scan's ``xs``)::

    model/embed_tokens [V, D]     model/norm/scale [D]     lm_head/kernel [D, V]
    model/{dense_layers,layers}/{input,post_attention}_layernorm/scale [L, D]
    model/{dense_layers,layers}/self_attn/q_proj/kernel               [L, D, H*(nope+rope)]
    model/{dense_layers,layers}/self_attn/kv_a_proj_with_mqa/kernel   [L, D, kv_lora_rank+rope]
    model/{dense_layers,layers}/self_attn/kv_a_layernorm/scale        [L, kv_lora_rank]
    model/{dense_layers,layers}/self_attn/kv_b_proj/kernel            [L, kv_lora_rank, H*(nope+v)]
    model/{dense_layers,layers}/self_attn/o_proj/kernel               [L, H*v, D]
    model/dense_layers/mlp/{gate,up,down}_proj/kernel                 [L, in, out]
    model/layers/mlp/gate/weight [L, D, E]    model/layers/mlp/gate/e_score_correction_bias [L, E]
    model/layers/mlp/experts/{gate,up,down}_proj [L, E, in, out]
    model/layers/mlp/shared_experts/{gate,up,down}_proj/kernel        [L, in, out]

Matrices are stored ``[in, out]`` (``x @ kernel``), the transpose of the
checkpoint's ``nn.Linear.weight``; ``mlp/gate/weight`` likewise.

Serving only: ``inference/v2`` runs this model through its latent paged
cache with absorbed ``kv_b_proj`` (``model_runner.MoonlightKind``).
:func:`reference_logits` below is the plain float32 forward that
expands ``kv_b_proj`` instead; the flax module's ``__call__`` is that
forward, so the module exists to build and initialise the tree.
Training this model is not implemented.
"""

import dataclasses
import math
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoonlightConfig:
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264          # the leading dense layers' width
    moe_intermediate_size: int = 1408       # one routed expert's width
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    rope_theta: float = 50000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False

    def __post_init__(self):
        # what the source's other checkpoints switch on and this one leaves off is
        # refused by name, not implemented half
        unsupported = {
            "q_lora_rank": self.q_lora_rank is not None,
            "n_group/topk_group (group-limited routing)": (self.n_group, self.topk_group) != (1, 1),
            "scoring_func": self.scoring_func != "sigmoid",
            "topk_method": self.topk_method != "noaux_tc",
            "moe_layer_freq": self.moe_layer_freq != 1,
            "rope_scaling (YaRN)": self.rope_scaling is not None,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act != "silu",
            "num_key_value_heads": self.num_key_value_heads != self.num_attention_heads,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"MoonlightConfig: unsupported setting of {bad}")
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("MoonlightConfig: needs at least one dense and one expert layer")

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_moe_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace


MOONLIGHT_CONFIGS = {
    "moonlight-16b-a3b": MoonlightConfig(),
    # every mechanism at a size the CPU tests run: 1 dense + 2 expert layers, 8
    # experts top-3 beside 1 shared, nope / rope / v head sizes all different
    "moonlight-debug": MoonlightConfig(
        vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
        num_experts_per_tok=3, n_shared_experts=1, max_position_embeddings=512),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, H, E = cfg.hidden_size, cfg.num_attention_heads, cfg.n_routed_experts
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)

    def swiglu(L, width):
        return {"gate_proj": {"kernel": (L, D, width)}, "up_proj": {"kernel": (L, D, width)},
                "down_proj": {"kernel": (L, width, D)}}

    def layers(L, mlp):
        return {"input_layernorm": {"scale": (L, D)},
                "post_attention_layernorm": {"scale": (L, D)},
                "self_attn": {"q_proj": {"kernel": (L, D, H * (dn + dr))},
                              "kv_a_proj_with_mqa": {"kernel": (L, D, r + dr)},
                              "kv_a_layernorm": {"scale": (L, r)},
                              "kv_b_proj": {"kernel": (L, r, H * (dn + dv))},
                              "o_proj": {"kernel": (L, H * dv, D)}},
                "mlp": mlp}

    Ld, Lm, I = cfg.first_k_dense_replace, cfg.num_moe_layers, cfg.moe_intermediate_size
    moe = {"gate": {"weight": (Lm, D, E), "e_score_correction_bias": (Lm, E)},
           "experts": {"gate_proj": (Lm, E, D, I), "up_proj": (Lm, E, D, I),
                       "down_proj": (Lm, E, I, D)},
           "shared_experts": swiglu(Lm, I * cfg.n_shared_experts)}
    return {"model": {"embed_tokens": (cfg.vocab_size, D), "norm": {"scale": (D,)},
                      "dense_layers": layers(Ld, swiglu(Ld, cfg.intermediate_size)),
                      "layers": layers(Lm, moe)},
            "lm_head": {"kernel": (D, cfg.vocab_size)}}


def _initializer(name):
    if name == "scale":
        return nn.initializers.ones
    if name == "e_score_correction_bias":
        # the checkpoint's is trained and non-zero; zeros would hide a router that
        # weights by the biased score
        return nn.initializers.normal(0.1)
    return nn.initializers.normal(0.02)


class _Tree(nn.Module):
    """Declares the parameters of one level of :func:`param_shapes`;
    ``initializer``: a parameter's name → its initializer."""
    shapes: dict
    initializer: Callable = _initializer

    @nn.compact
    def __call__(self):
        return {name: _Tree(value, self.initializer, name=name)() if hasattr(value, "items")
                else self.param(name, self.initializer(name), tuple(value))
                for name, value in self.shapes.items()}


class MoonlightForCausalLM(nn.Module):
    config: MoonlightConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        params = {name: _Tree(value, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_moonlight(preset_or_config="moonlight-debug", **overrides) -> MoonlightForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, MoonlightConfig) \
        else MOONLIGHT_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return MoonlightForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, H, d]: the pairs (2i, 2i+1) are de-interleaved to (i, i + d/2)
    and then rotated by halves, as ``apply_rotary_pos_emb`` does."""
    B, S, H, d = x.shape
    x = x.reshape(B, S, H, d // 2, 2).swapaxes(-1, -2).reshape(B, S, H, d)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]     # [S, d/2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    No cache and no absorption: ``kv_b_proj`` is **expanded** into
    per-head keys and values, and ordinary causal attention runs over
    (nope+rope)-wide query/key heads and v-wide value heads; every expert
    is applied to every token, one at a time, and weighted (zero where it
    was not chosen). The serving path absorbs ``kv_b_proj`` into the
    query and the output instead; the two share no line.

    Departures from ``modeling_deepseek.py``: weights are ``[in, out]``
    and stacked over layers; float32 throughout (the source computes in
    the checkpoint's dtype with a float32 softmax); no attention mask
    argument, no dropout, no YaRN ``mscale`` (``rope_scaling`` is null);
    ``n_group = topk_group = 1``, so the group-limited step of
    ``noaux_tc`` selects every group and is left out."""
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps, k = cfg.rms_norm_eps, cfg.num_experts_per_tok

    def w(x):
        return x.astype(jnp.float32)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ w(gate)) * (x @ w(up))) @ w(down)

    def attention(lp, h):
        B, S, _ = h.shape
        x = _rms_norm(h, w(lp["input_layernorm"]["scale"]), eps)
        a = lp["self_attn"]
        q = (x @ w(a["q_proj"]["kernel"])).reshape(B, S, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        kv_a = x @ w(a["kv_a_proj_with_mqa"]["kernel"])                        # [B, S, r + dr]
        c_kv = _rms_norm(kv_a[..., :r], w(a["kv_a_layernorm"]["scale"]), eps)
        k_rope = kv_a[..., r:]
        kv = (c_kv @ w(a["kv_b_proj"]["kernel"])).reshape(B, S, H, dn + dv)    # expanded
        k_nope, v = kv[..., :dn], kv[..., dn:]
        q_rope = _rope(q_rope, cfg.rope_theta)
        k_rope = _rope(k_rope[:, :, None, :], cfg.rope_theta)                  # one head, shared
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(dn + dr)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * dv)
        return h + out @ w(a["o_proj"]["kernel"])

    def experts(mlp, x):
        scores = jax.nn.sigmoid(x @ w(mlp["gate"]["weight"]))
        biased = scores + w(mlp["gate"]["e_score_correction_bias"])
        _, chosen = jax.lax.top_k(biased, k)                                    # [B, S, k]
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        picked = picked * cfg.routed_scaling_factor
        weights = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=jnp.float32)
                          * picked[..., None], axis=-2)                         # [B, S, E]
        out = jnp.zeros_like(x)
        for e in range(cfg.n_routed_experts):
            ex = mlp["experts"]
            out = out + weights[..., e, None] * swiglu(
                x, ex["gate_proj"][e], ex["up_proj"][e], ex["down_proj"][e])
        sh = mlp["shared_experts"]
        return out + swiglu(x, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                            sh["down_proj"]["kernel"])

    with jax.default_matmul_precision("highest"):
        model = params["model"]
        h = w(model["embed_tokens"][input_ids])
        for stack, moe in ((model["dense_layers"], False), (model["layers"], True)):
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                lp = jax.tree.map(lambda x: x[i], stack)
                h = attention(lp, h)
                x = _rms_norm(h, w(lp["post_attention_layernorm"]["scale"]), eps)
                if moe:
                    h = h + experts(lp["mlp"], x)
                else:
                    m = lp["mlp"]
                    h = h + swiglu(x, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                                   m["down_proj"]["kernel"])
        h = _rms_norm(h, w(model["norm"]["scale"]), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ w(params["lm_head"]["kernel"])
