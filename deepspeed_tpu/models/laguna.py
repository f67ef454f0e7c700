"""Laguna (``model_type: laguna``; e.g. ``poolside/Laguna-XS.2``): a decoder
whose layers alternate **window and full attention** 3 : 1 - the two kinds
differ in shape (64 and 48 query heads over the same 8 key-value heads of
128) and in rotation - each followed by a routed feed-forward (a dense SwiGLU
in the leading layer), with a sigmoid **gate a head** on the attention's
output.

The equations (``D`` hidden, ``d`` = ``head_dim``, ``Hkv`` key-value heads,
``W`` = ``sliding_window``, ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``,
``eps`` = ``rms_norm_eps``; layer ``l`` has ``H_l =
num_attention_heads_per_layer[l]`` query heads and is a window layer where
``layer_types[l]`` is ``sliding_attention``; no bias anywhere)::

    a = rms(h; w_in[l]);   h <- h + Attn_l(a)
    f = rms(h; w_ff[l]);   h <- h + (mlp_layer_types[l] == "dense" ? SwiGLU(f) : MoE_l(f))
    logits = rms(h; w_f) W_head                                           (untied)

    Attn_l:  q [H_l, d], k, v [Hkv, d] = a W_q, a W_k, a W_v
             q, k <- rope_l(q, k, pos)
               full:    the first ``partial_rotary_factor * d`` columns of a head rotated
                        (rotate-half over those columns), YaRN frequencies over that many
                        columns (:func:`yarn_inv_freq`), cos and sin times ``attention_factor``;
                        the other columns untouched
               sliding: all d columns, theta as given, no scaling
             s_ij = q_i . k_j / sqrt(d)  for j <= i, and in a window layer also i - j < W
             o_i = softmax_j(s_ij) v_j   (query head n reads key-value head n // (H_l / Hkv))
             g = sigmoid(a W_g)  [H_l]   (gating: a head)
             Attn = concat_n(g_n o_n) W_o

    MoE_l:   s = sigmoid(f W_r) [E] (float32);  picks = the k largest of s + b_l
             w = s[picks] / sum s[picks] * moe_routed_scaling_factor      (on the output)
             MoE = sum_i w_i E_picks[i](f) + E_shared(f);   E(x) = W_down(silu(W_gate x) * (W_up x))

Three **forms** follow from no key of the published config: the gate is read
a head (``gating: true``; the sibling ``Laguna-S-2.1`` spells the key
``"per-head"``), which the parameter count bears out - 33.44 B against the
published "33.4B", where an element-wise gate would read 34.07 B
(``tests/unit/inference/v2/test_laguna.py`` counts :func:`param_shapes`); the
router's score is the sigmoid with a selection bias that joins the scores for
the choice and not the weights, the picks' weights over their sum times the
scaling factor (the DeepSeek-V3 form that ``moe_routed_scaling_factor`` 2.5,
256 experts, 8 picks and one shared expert are to the digit); there is no
query / key norm (no key names one). Refused by name
(:class:`LagunaConfig`): ``gating`` other than true / ``"per-head"``, a
router soft cap, router weights on the input, attention bias, tied
embeddings, a layer pattern that is not one period repeated from a full
layer, heads that differ within a kind of layer.

Parameter tree: the attention layers of a kind are stacked (``Lf`` full,
``Lw`` window, each in stack order: their ``q_proj`` / ``g_proj`` /
``o_proj`` differ in shape), the dense feed-forwards in one stack and the
routed ones in another, matrices ``[in, out]``::

    model/embed_tokens [V, D]     model/norm/scale [D]     lm_head/kernel [D, V]
    model/full_layers/input_layernorm/scale [Lf, D]   .../{q,k,v,g,o}_proj/kernel [Lf, in, out]
    model/window_layers/input_layernorm/scale [Lw, D] .../{q,k,v,g,o}_proj/kernel [Lw, in, out]
    model/dense_ffn/post_attention_layernorm/scale [Ld, D]  .../{gate,up,down}_proj/kernel [Ld, in, out]
    model/moe/post_attention_layernorm/scale [Ls, D]
    model/moe/gate/{weight [Ls, D, E], e_score_correction_bias [Ls, E]}
    model/moe/experts/{gate,up,down}_proj [Ls, held, in, out]
    model/moe/shared_experts/{gate,up,down}_proj/kernel [Ls, in, out]

Serving only: ``inference/v2`` runs this model through
``model_runner.LagunaKind``, whose window layers keep their keys and values
in a pool of their own that holds a window a sequence;
:func:`reference_logits` is the plain float32 forward over whole sequences.
"""

import dataclasses
import itertools
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.moonlight import _Tree, _rms_norm
from deepspeed_tpu.models.nemotron_h import segments_of

FULL, WINDOW = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
STACKS = {FULL: "full_layers", WINDOW: "window_layers"}

PUBLISHED_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
           "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
    WINDOW: {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096,
}


def _frozen(value):
    """A published nested group (a dict of dicts) as sorted pairs, so the config hashes."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    return value


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192                   # the dense layer's
    num_hidden_layers: int = 40
    num_attention_heads: int = 48                   # a full layer's; the list below says each layer's
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256                          # the router's columns, whatever is held
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: object = True                           # true / "per-head": a gate a head
    sliding_window: int = 512
    rope_parameters: tuple = _frozen(PUBLISHED_ROPE)
    layer_types: tuple = (FULL, WINDOW, WINDOW, WINDOW) * 10
    mlp_layer_types: tuple = (DENSE,) + (SPARSE,) * 39
    num_attention_heads_per_layer: tuple = (48, 64, 64, 64) * 10
    moe_apply_router_weight_on_input: bool = False
    moe_router_logit_softcapping: float = 0.0
    partial_rotary_factor: float = 0.5              # the full layers' (rope_parameters says it again)
    moe_routed_scaling_factor: float = 2.5
    # the share of an expert-parallel deployment held here (None: every routed expert)
    experts_held: Optional[int] = None
    first_expert_held: int = 0

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        object.__setattr__(self, "rope_parameters", _frozen(self.rope_parameters))
        L, kinds = self.num_hidden_layers, self.layer_types
        lengths = {len(kinds), len(self.mlp_layer_types), len(self.num_attention_heads_per_layer)}
        if lengths != {L}:
            raise ValueError(f"LagunaConfig: layer_types, mlp_layer_types and "
                             f"num_attention_heads_per_layer must each name {L} layers")
        period = (kinds[1:] + (FULL,)).index(FULL) + 1
        heads = {kind: {h for k, h in zip(kinds, self.num_attention_heads_per_layer) if k == kind}
                 for kind in (FULL, WINDOW)}
        n_dense = self.mlp_layer_types.count(DENSE)
        rope = {kind: dict(dict(self.rope_parameters).get(kind, ())) for kind in (FULL, WINDOW)}
        unsupported = {
            "gating": self.gating not in (True, "per-head"),
            "moe_router_logit_softcapping": bool(self.moe_router_logit_softcapping),
            "moe_apply_router_weight_on_input": self.moe_apply_router_weight_on_input,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "layer_types (one period repeated, from a full_attention layer)":
                set(kinds) - {FULL, WINDOW} != set()
                or any(kinds[i] != kinds[i % period] for i in range(L)) or kinds[0] != FULL,
            "mlp_layer_types (dense layers first, then sparse)":
                self.mlp_layer_types != (DENSE,) * n_dense + (SPARSE,) * (L - n_dense),
            "num_attention_heads_per_layer (one number a kind of layer)":
                any(len(h) > 1 for h in heads.values())
                or any(h % self.num_key_value_heads for hs in heads.values() for h in hs),
            "rope_parameters.full_attention.rope_type":
                rope[FULL].get("rope_type") not in ("yarn", "default"),
            "rope_parameters.sliding_attention.rope_type":
                WINDOW in kinds and rope[WINDOW].get("rope_type") != "default",
            "sliding_window": WINDOW in kinds and self.sliding_window < 1,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"LagunaConfig: unsupported setting of {bad}")
        if not 0 < self.num_experts_per_tok <= self.num_experts:
            raise ValueError("LagunaConfig: num_experts_per_tok exceeds the router's columns")
        if not (0 <= self.first_expert_held
                and 0 < self.held and self.first_expert_held + self.held <= self.num_experts):
            raise ValueError(f"LagunaConfig: experts {self.first_expert_held}..+{self.held} are "
                             f"not among the {self.num_experts} routed")

    @property
    def held(self):
        return self.num_experts if self.experts_held is None else self.experts_held

    def count(self, kind):
        """The layers of an attention kind (``FULL`` / ``WINDOW``) or a feed-forward's."""
        return (self.layer_types + self.mlp_layer_types).count(kind)

    def heads(self, kind):
        """The query heads of a ``kind`` layer (every such layer's; 0 where there is none)."""
        return next((h for k, h in zip(self.layer_types, self.num_attention_heads_per_layer)
                     if k == kind), 0)

    def rope(self, kind):
        """→ (``inv_freq`` [r / 2] float32 numpy, r the rotated columns of a
        head; what cos and sin are multiplied by) of a ``kind`` layer."""
        p = dict(dict(self.rope_parameters)[kind])
        r = int(self.head_dim * p.get("partial_rotary_factor", 1))
        if p.get("rope_type") == "yarn":
            top = dict(self.rope_parameters)
            original = p.get("original_max_position_embeddings",
                             top.get("original_max_position_embeddings"))
            factor = p.get("attention_factor")
            if factor is None:
                factor = 0.1 * math.log(p["factor"]) + 1.0
            return yarn_inv_freq(r, p["rope_theta"], p["factor"], original,
                                 p.get("beta_fast", 32), p.get("beta_slow", 1)), float(factor)
        return (1.0 / (p["rope_theta"] ** (np.arange(0, r, 2, dtype=np.float32) / r))
                ).astype(np.float32), 1.0

    @property
    def letters(self):
        """A letter a layer: ``f`` / ``w`` its attention's kind, upper case
        where its feed-forward is dense. Published: ``Fwww`` + ``fwww`` x 9."""
        return "".join((l.upper() if m == DENSE else l) for l, m in zip(
            ("f" if k == FULL else "w" for k in self.layer_types), self.mlp_layer_types))

    @property
    def segments(self):
        """The stack as ``[(unit, repeats), ...]`` for
        ``model_runner._run_segments``: one scan over a period where the
        letters repeat (``nemotron_h.segments_of``), and a run of single
        layers of one letter one scan too - ``Fwww`` + ``fwww`` x 4 is ``F``,
        ``wwwf`` x 4, ``w`` x 3: six layer bodies in a program, not eight."""
        out = []
        for single, run in itertools.groupby(segments_of(self.letters),
                                             key=lambda s: s if s[1] == 1 else None):
            run = list(run)
            out += run if single is None else [(single[0], len(run))]
        return tuple(out)


def yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies over ``dim`` rotated columns (the transformers
    library's ``_compute_yarn_parameters``, ``truncate`` on): a column's
    frequency is the plain one where it turns more than ``beta_fast`` times
    in the original context, the plain one over ``factor`` where fewer than
    ``beta_slow``, and a linear ramp between. → [dim / 2] float32."""
    def correction(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (plain / factor * ramp + plain * (1 - ramp)).astype(np.float32)


LAGUNA_CONFIGS = {
    "laguna-xs2": LagunaConfig(),
    # expert rank 0 of 8, pipeline stage 0 of 2 (benchmark/configs/laguna-xs2-ep8-20l.json):
    # every width and the whole vocabulary as published, the published layers 0-19 (the
    # leading dense layer and five whole periods), experts 0-31 of 256
    "laguna-xs2-ep8-20l": LagunaConfig(
        num_hidden_layers=20, layer_types=(FULL, WINDOW, WINDOW, WINDOW) * 5,
        mlp_layer_types=(DENSE,) + (SPARSE,) * 19,
        num_attention_heads_per_layer=(48, 64, 64, 64) * 5, experts_held=32),
    # every mechanism at a size the CPU tests run: three whole periods (a leading dense layer,
    # a period scanned twice, a tail), query groups of 3 (full) and 4 (window) over 2 key-value
    # heads of 16, a window of 8, half-rotary YaRN, 16 experts top-4 beside a shared one
    "laguna-debug": LagunaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=12,
        num_attention_heads=6, num_key_value_heads=2, head_dim=16, max_position_embeddings=512,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=8,
        rope_parameters={FULL: dict(PUBLISHED_ROPE[FULL], factor=4,
                                    original_max_position_embeddings=32,
                                    attention_factor=1.1386294361119891, beta_fast=4),
                         WINDOW: PUBLISHED_ROPE[WINDOW], "original_max_position_embeddings": 32},
        layer_types=(FULL, WINDOW, WINDOW, WINDOW) * 3, mlp_layer_types=(DENSE,) + (SPARSE,) * 11,
        num_attention_heads_per_layer=(6, 8, 8, 8) * 3),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, d, F = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    kv = cfg.num_key_value_heads * d
    model = {"embed_tokens": (cfg.vocab_size, D), "norm": {"scale": (D,)}}
    for kind, stack in STACKS.items():
        n, q = cfg.count(kind), cfg.heads(kind) * d
        if n:
            model[stack] = {
                "input_layernorm": {"scale": (n, D)}, "q_proj": {"kernel": (n, D, q)},
                "k_proj": {"kernel": (n, D, kv)}, "v_proj": {"kernel": (n, D, kv)},
                "g_proj": {"kernel": (n, D, cfg.heads(kind))}, "o_proj": {"kernel": (n, q, D)}}
    Ld, Ls, I, S = (cfg.count(DENSE), cfg.count(SPARSE), cfg.intermediate_size,
                    cfg.shared_expert_intermediate_size)
    if Ld:
        model["dense_ffn"] = {
            "post_attention_layernorm": {"scale": (Ld, D)}, "gate_proj": {"kernel": (Ld, D, I)},
            "up_proj": {"kernel": (Ld, D, I)}, "down_proj": {"kernel": (Ld, I, D)}}
    if Ls:
        model["moe"] = {
            "post_attention_layernorm": {"scale": (Ls, D)},
            "gate": {"weight": (Ls, D, cfg.num_experts),
                     "e_score_correction_bias": (Ls, cfg.num_experts)},
            "experts": {"gate_proj": (Ls, cfg.held, D, F), "up_proj": (Ls, cfg.held, D, F),
                        "down_proj": (Ls, cfg.held, F, D)},
            "shared_experts": {"gate_proj": {"kernel": (Ls, D, S)}, "up_proj": {"kernel": (Ls, D, S)},
                               "down_proj": {"kernel": (Ls, S, D)}}}
    return {"model": model, "lm_head": {"kernel": (D, cfg.vocab_size)}}


def initializer_of(cfg):
    """A parameter's name → its initializer: norms one, the router's
    selection bias **zero** (as the configuration's file says), every matrix
    normal(0.02)."""
    table = {"scale": nn.initializers.ones, "e_score_correction_bias": nn.initializers.zeros}
    return lambda name: table.get(name, nn.initializers.normal(0.02))


class LagunaForCausalLM(nn.Module):
    config: LagunaConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        init = initializer_of(self.config)
        params = {name: _Tree(value, init, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_laguna(preset_or_config="laguna-debug", **overrides) -> LagunaForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, LagunaConfig) \
        else LAGUNA_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return LagunaForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def reference_rope(x, cfg, kind):
    """x [B, S, H, d] at positions 0..S-1 → rotated: the first ``r`` columns
    of a head by halves (``rotate_half`` over those ``r``), the rest as they
    are; cos and sin times the kind's attention factor."""
    inv_freq, factor = cfg.rope(kind)
    r = 2 * inv_freq.shape[0]
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = (jnp.cos(angle) * factor)[None, :, None, :], (jnp.sin(angle) * factor)[None, :, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], axis=-1)


def reference_attention(p, x, cfg, kind):
    """One attention layer's mixer on whole sequences: x [B, S, D] (the
    normalised stream) → y. Grouped-query softmax attention by a ``[S, S]``
    mask - causal, and in a window layer the last ``sliding_window`` keys
    alone, self included - the output gated a head before ``W_o``."""
    p = _f32(p)
    B, S, _ = x.shape
    Hq, Hkv, d = cfg.heads(kind), cfg.num_key_value_heads, cfg.head_dim
    q = reference_rope((x @ p["q_proj"]["kernel"]).reshape(B, S, Hq, d), cfg, kind)
    k = reference_rope((x @ p["k_proj"]["kernel"]).reshape(B, S, Hkv, d), cfg, kind)
    v = (x @ p["v_proj"]["kernel"]).reshape(B, S, Hkv, d)
    q = q.reshape(B, S, Hkv, Hq // Hkv, d)
    scores = jnp.einsum("bpkgd,bukd->bkgpu", q, k) / math.sqrt(d)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if kind == WINDOW:
        seen &= i - j < cfg.sliding_window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgpu,bukd->bpkgd", probs, v).reshape(B, S, Hq, d)
    out = out * jax.nn.sigmoid(x @ p["g_proj"]["kernel"])[..., None]
    return out.reshape(B, S, Hq * d) @ p["o_proj"]["kernel"]


def reference_router(p, x, cfg):
    """→ (weights [..., E]: ``moe_routed_scaling_factor * s_j / sum of the
    picks' s`` at the picks, zero elsewhere; margin [...]: the last pick's
    lead over the first column left out, of ``s + bias``)."""
    s = jax.nn.sigmoid(x @ p["gate"]["weight"])
    biased = s + p["gate"]["e_score_correction_bias"]
    k = cfg.num_experts_per_tok
    ranked, chosen = jax.lax.top_k(biased, min(k + 1, biased.shape[-1]))
    picked = jnp.sum(jax.nn.one_hot(chosen[..., :k], s.shape[-1], dtype=jnp.float32), axis=-2) > 0
    weights = jnp.where(picked, s, 0.0)
    weights = cfg.moe_routed_scaling_factor * weights / (weights.sum(-1, keepdims=True) + 1e-20)
    margin = ranked[..., k - 1] - ranked[..., k] if ranked.shape[-1] > k \
        else jnp.full(s.shape[:-1], jnp.inf)
    return weights, margin


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def reference_moe(p, x, cfg, share=None, shared=True):
    """One routed feed-forward: x [..., D] → y. Every held expert applied to
    every token, weighted (zero where the router did not pick it).
    ``share``: (first, held) of the router's columns (None: the config's own
    share), the experts ``p`` holds; ``shared``: whether the shared expert is
    added (every share computes it alike: a sum over shares counts it once)."""
    p = _f32(p)
    first, held = (cfg.first_expert_held, cfg.held) if share is None else share
    weights, _ = reference_router(p, x, cfg)
    e = p["experts"]

    def one(acc, i):
        out = _swiglu(x, e["gate_proj"][i], e["up_proj"][i], e["down_proj"][i])
        return acc + out * jnp.take(weights, first + i, axis=-1)[..., None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    if shared:
        s = p["shared_experts"]
        y = y + _swiglu(x, s["gate_proj"]["kernel"], s["up_proj"]["kernel"],
                        s["down_proj"]["kernel"])
    return y


def layer_params(params, cfg, position):
    """→ (the attention's parameters, the feed-forward's) of the layer at
    ``position`` of the stack, each cut out of its kind's stack."""
    kind, ffn = cfg.layer_types[position], cfg.mlp_layer_types[position]
    model = params["model"]
    return (jax.tree.map(lambda w: w[cfg.layer_types[:position].count(kind)], model[STACKS[kind]]),
            jax.tree.map(lambda w: w[cfg.mlp_layer_types[:position].count(ffn)],
                         model["dense_ffn" if ffn == DENSE else "moe"]))


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no chunks, no pools, no kernels: attention by
    a mask over all rows (a window layer's with its lower edge), an explicit
    top-k and every held expert on every token, given the config's share.

    Departures from the published description, none of the mathematics:
    weights ``[in, out]``, the attention layers of a kind stacked; float32
    throughout; no attention-mask argument, no dropout; the three forms the
    module's docstring names as assumed."""
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        h = params["model"]["embed_tokens"][input_ids].astype(jnp.float32)
        for position, kind in enumerate(cfg.layer_types):
            attn, ffn = layer_params(params, cfg, position)
            x = _rms_norm(h, attn["input_layernorm"]["scale"].astype(jnp.float32), eps)
            h = h + reference_attention(attn, x, cfg, kind)
            x = _rms_norm(h, ffn["post_attention_layernorm"]["scale"].astype(jnp.float32), eps)
            if cfg.mlp_layer_types[position] == DENSE:
                f = _f32(ffn)
                h = h + _swiglu(x, f["gate_proj"]["kernel"], f["up_proj"]["kernel"],
                                f["down_proj"]["kernel"])
            else:
                h = h + reference_moe(ffn, x, cfg)
        h = _rms_norm(h, params["model"]["norm"]["scale"].astype(jnp.float32), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ params["lm_head"]["kernel"].astype(jnp.float32)
