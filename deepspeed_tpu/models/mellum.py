"""Mellum 2 (``model_type: mellum``; ``JetBrains/Mellum2-12B-A2.5B-Instruct``):
a Llama-family decoder whose layers come in periods of three
``sliding_attention`` and one ``full_attention``, every MLP a mixture of 64
experts of which a token takes 8. **Trained**, not served: the model is
the Llama family's training block (``models/llama.py``) under this
configuration - :meth:`MellumConfig.to_llama` says which of its lines each
published key sets - and this file holds the published keys, the presets and
the plain float32 reference the tests and the benchmark hold the trainer to.

The equations, from the published ``config.json`` (``D`` hidden, ``H`` query
heads over ``G`` key-value heads of ``d``, ``E`` experts of width ``I``, ``k``
picks; ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``; no bias anywhere)::

    h = Emb[ids]
    for l in 0 .. L-1, t = layer_types[l]:
        a = rms(h; n1_l);  q = a W_q [H, d],  k = a W_k [G, d],  v = a W_v [G, d]
        q, k rotated by halves over all d columns, by position, with the table of kind t:
            sliding_attention: theta^(-2i/d)                                    (plain)
            full_attention:    YaRN (factor, original length, beta_fast, beta_slow of
                               rope_parameters; transformers' _compute_yarn_parameters),
                               cos and sin both times attention_factor
        P = softmax(q k^T / sqrt(d)) over the keys j <= i (full) or i - W < j <= i (sliding,
            W = sliding_window: a query's W newest keys, itself among them)
        h <- h + (P v) W_o
        m = rms(h; n2_l);  p = softmax(m W_r) over E, float32
        the k largest p, their weights divided by their sum (norm_topk_prob)
        h <- h + sum_j w_j (silu(m W1_j) * (m W3_j)) W2_j
    logits = rms(h; n_f) W_head                                                (untied)
    loss = mean_i -log softmax(logits_i)[ids_{i+1}]  +  c / L  sum_l aux_l
    aux_l = E sum_e mean_i(p_ie) mean_i[e is token i's first pick]    (the job's
            load-balancing term, ``moe/sharded_moe.gshard_aux_loss`` over the step's tokens;
            ``c`` = ``moe_aux_loss_coef``: a trainer's setting, not the model's)

Not in ``config.json`` and set by the family's convention: the pre-norm
residual block above, no norm on queries and keys, a float32 router. The
catalog's description mentions a multi-token-prediction head; the config
has no key for one and none is built. ``intermediate_size`` (7168) is read by
nothing: every layer is ``sparse``. Refused by name (:class:`MellumConfig`):
a ``mlp_layer_types`` entry other than ``sparse``, a bias, tied embeddings,
``norm_topk_prob`` false, an activation other than ``silu``, a sliding layer
with ``use_sliding_window`` false, a rope type other than ``default`` / ``yarn``.

Parameter tree (the Llama family's: the layers stacked, whatever their kind)::

    model/embed_tokens [V, D]   model/norm/scale [D]   lm_head/kernel [D, V]
    model/layers/{input_layernorm,post_attention_layernorm}/scale [L, D]
    model/layers/self_attn/{q,k,v,o}_proj/kernel [L, in, out]
    model/layers/moe_mlp/deepspeed_moe/gate/wg/kernel [L, D, E]
    model/layers/moe_mlp/deepspeed_moe/experts_w{1,3} [L, E, D, I]   experts_w2 [L, E, I, D]
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.llama import FULL, SLIDING, LlamaConfig, LlamaForCausalLM

SPARSE = "sparse"
PUBLISHED_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
           "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The published ``config.json``'s keys, under their names."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168                       # not read: no layer is dense
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    layer_types: Optional[Tuple[str, ...]] = None       # None: (sliding x 3, full) repeated
    mlp_layer_types: Optional[Tuple[str, ...]] = None   # None: sparse, one a layer
    max_position_embeddings: int = 131072
    max_window_layers: int = 0                          # not read
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_parameters: Optional[dict] = None              # None: PUBLISHED_ROPE
    sliding_window: int = 1024
    use_sliding_window: bool = True
    tie_word_embeddings: bool = False

    def __post_init__(self):
        L = self.num_hidden_layers
        types = ((SLIDING,) * 3 + (FULL,)) * (L // 4) if self.layer_types is None \
            else tuple(self.layer_types)
        mlps = (SPARSE,) * L if self.mlp_layer_types is None else tuple(self.mlp_layer_types)
        rope = PUBLISHED_ROPE if self.rope_parameters is None else self.rope_parameters
        for name, value in (("layer_types", types), ("mlp_layer_types", mlps),
                            ("rope_parameters", rope)):
            object.__setattr__(self, name, value)
        kinds = {rope.get(t, {}).get("rope_type") for t in set(types)}
        unsupported = {
            "layer_types (a kind a layer, full_attention | sliding_attention)":
                len(types) != L or bool(set(types) - {FULL, SLIDING}),
            "mlp_layer_types (other than sparse, one a layer)":
                len(mlps) != L or any(m != SPARSE for m in mlps),
            "use_sliding_window (false beside sliding_attention layers)":
                SLIDING in types and not self.use_sliding_window,
            "rope_parameters (a rope_type other than default / yarn, or a kind left out)":
                bool(kinds - {"default", "yarn"}),
            "rope_parameters (one rope_theta for every kind)":
                len({rope.get(t, {}).get("rope_theta") for t in set(types)}) > 1,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "norm_topk_prob": not self.norm_topk_prob,
            "hidden_act": self.hidden_act != "silu",
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"MellumConfig: unsupported setting of {bad}")

    def to_llama(self, **trainer) -> LlamaConfig:
        """The Llama family's configuration that builds this model; ``trainer``
        sets what is the job's and not the model's (``remat``,
        ``remat_policy``, ``attention_impl``, ``moe_aux_loss_coef``, ``loss_chunk``)."""
        yarn = self.rope_parameters.get(FULL, {}) if FULL in self.layer_types else {}
        scaled = yarn.get("rope_type") == "yarn"
        theta = next(self.rope_parameters[t]["rope_theta"] for t in self.layer_types)
        settings = dict(moe_aux_loss_coef=0.001)
        settings.update(trainer)
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads, head_dim_override=self.head_dim,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=float(theta),
            rope_scaling_type="yarn" if scaled else "none",
            rope_scaling_factor=float(yarn.get("factor", 1.0)),
            rope_original_max_position=int(yarn.get("original_max_position_embeddings", 8192)),
            rope_yarn_beta_fast=float(yarn.get("beta_fast", 32)),
            rope_yarn_beta_slow=float(yarn.get("beta_slow", 1)),
            rope_attention_factor=float(yarn.get("attention_factor", 0.0)),
            rope_scaling_kinds=(FULL,) if scaled else (),
            layer_types=self.layer_types, sliding_window=self.sliding_window,
            tie_word_embeddings=False, attention_bias=False,
            moe_num_experts=self.num_experts, moe_intermediate_size=self.moe_intermediate_size,
            moe_top_k=self.num_experts_per_tok, moe_drop_tokens=False, **settings)


MELLUM_CONFIGS = {
    "mellum2-12b": MellumConfig(),
    # every mechanism at a size the CPU tests run: two whole periods, 4 heads of 32 over 2, a
    # window of 8 at 32 positions, YaRN over an original length of 16, 8 experts top-2 of 64
    "mellum2-debug": MellumConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        max_position_embeddings=64, num_experts=8, num_experts_per_tok=2, sliding_window=8,
        rope_parameters={FULL: dict(PUBLISHED_ROPE[FULL], factor=4,
                                    original_max_position_embeddings=16, beta_fast=4,
                                    attention_factor=0.1 * math.log(4) + 1),
                         SLIDING: PUBLISHED_ROPE[SLIDING]}),
}


def build_mellum(preset_or_config="mellum2-debug", **trainer) -> LlamaForCausalLM:
    """The trainable model of a preset (or a :class:`MellumConfig`)."""
    cfg = preset_or_config if isinstance(preset_or_config, MellumConfig) \
        else MELLUM_CONFIGS[preset_or_config]
    return LlamaForCausalLM(cfg.to_llama(**trainer))


def seeded_params(cfg, seed=0, dtype=jnp.float32, **trainer):
    """The model's parameters from ``seed`` (flax's initialisers: ``lecun_normal``
    matrices, an embedding of deviation 0.02, norms of one), in ``dtype``."""
    model = build_mellum(cfg, **trainer)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda key: model.init(key, ids)["params"])(jax.random.PRNGKey(seed))
    return jax.tree.map(lambda x: x.astype(dtype), params)


# --------------------------------------------------------------------------- the reference

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_tables(cfg, kind, length):
    """cos, sin ``[length, d / 2]`` of a layer of ``kind``, float32."""
    from deepspeed_tpu.models.laguna import yarn_inv_freq
    p, d = cfg.rope_parameters[kind], cfg.head_dim
    gain = 1.0
    if p["rope_type"] == "yarn":
        inv = yarn_inv_freq(d, p["rope_theta"], p["factor"], p["original_max_position_embeddings"],
                            p["beta_fast"], p["beta_slow"])
        gain = p.get("attention_factor") or 0.1 * math.log(p["factor"]) + 1.0
    else:
        inv = 1.0 / (p["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float32) / d))
    f = np.outer(np.arange(length, dtype=np.float32), inv)
    return (np.cos(f) * gain).astype(np.float32), (np.sin(f) * gain).astype(np.float32)


def _rotate(x, cos, sin):
    """x [B, S, heads, d] rotated by halves."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_params(params, cfg, l):
    """Layer ``l``'s own parameters, cut out of the stack."""
    return jax.tree.map(lambda x: x[l], params["model"]["layers"])


def reference_attention(p, h, cfg, kind):
    """The attention half of a layer of ``kind``: ``h [B, S, D]`` float32 →
    ``(P v) W_o`` (the residual not added). The window is a mask."""
    B, S, _ = h.shape
    H, G, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    a = _rms(h, p["input_layernorm"]["scale"], cfg.rms_norm_eps)
    at = p["self_attn"]
    q = (a @ at["q_proj"]["kernel"]).reshape(B, S, H, d)
    k = (a @ at["k_proj"]["kernel"]).reshape(B, S, G, d)
    v = (a @ at["v_proj"]["kernel"]).reshape(B, S, G, d)
    cos, sin = rope_tables(cfg, kind, S)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if kind == SLIDING:
        seen = seen & (j > i - cfg.sliding_window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * d)
    return out @ at["o_proj"]["kernel"]


def reference_route(p, m, cfg):
    """→ (the router's probabilities ``[T, E]``, the picks ``[T, k]``, their
    normalised weights ``[T, k]``) of the rows ``m [T, D]``."""
    probs = jax.nn.softmax(m @ p["moe_mlp"]["deepspeed_moe"]["gate"]["wg"]["kernel"], axis=-1)
    vals, picks = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    return probs, picks, vals / jnp.sum(vals, axis=-1, keepdims=True)


def reference_experts(p, h, cfg, experts=None):
    """The expert half of a layer: ``h [B, S, D]`` → (``sum_j w_j E_j(m)``, the
    layer's load-balancing term). Every expert a loop over all the rows, its
    picks' weights selecting. ``experts`` (a range): the part those experts
    alone add - a rank's share of the layer."""
    B, S, D = h.shape
    moe = p["moe_mlp"]["deepspeed_moe"]
    m = _rms(h, p["post_attention_layernorm"]["scale"], cfg.rms_norm_eps).reshape(B * S, D)
    probs, picks, weights = reference_route(p, m, cfg)
    out = jnp.zeros_like(m)
    for e in (range(cfg.num_experts) if experts is None else experts):
        w_e = jnp.sum(jnp.where(picks == e, weights, 0.0), axis=-1, keepdims=True)
        y = (jax.nn.silu(m @ moe["experts_w1"][e]) * (m @ moe["experts_w3"][e])) @ moe["experts_w2"][e]
        out = out + w_e * y
    first = jax.nn.one_hot(picks[:, 0], cfg.num_experts, dtype=jnp.float32)
    aux = jnp.sum(probs.mean(axis=0) * first.mean(axis=0)) * cfg.num_experts
    return out.reshape(B, S, D), aux


def reference_layer(p, h, cfg, kind):
    """One whole layer → (the stream after it, its load-balancing term)."""
    h = h + reference_attention(p, h, cfg, kind)
    y, aux = reference_experts(p, h, cfg)
    return h + y, aux


def reference_nll(params, ids, cfg, moe_aux_loss_coef=0.001):
    """→ (each position's next-token negative log-likelihood ``[B, S - 1]``,
    the loss's load-balancing part ``c / L sum_l aux_l``)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
        h = params["model"]["embed_tokens"][ids]
        aux = jnp.zeros((), jnp.float32)
        for l, kind in enumerate(cfg.layer_types):
            h, a = reference_layer(layer_params(params, cfg, l), h, cfg, kind)
            aux = aux + a
        h = _rms(h, params["model"]["norm"]["scale"], cfg.rms_norm_eps)
        logp = jax.nn.log_softmax(h[:, :-1] @ params["lm_head"]["kernel"], axis=-1)
        nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
        return nll, moe_aux_loss_coef * aux / cfg.num_hidden_layers


def reference_loss(params, ids, cfg, moe_aux_loss_coef=0.001):
    """The training loss of ``ids [B, S]`` (inputs and, shifted, labels) in
    plain float32 ``jax.numpy`` at the highest matmul precision: no kernel, no
    scan, no mesh, every expert a loop, the window a mask. ``jax.grad`` of it
    is the reference gradient.

    Departures from the published description: none in the model. The
    load-balancing term is the trainer's (``moe_aux_loss_coef``; 0 leaves the
    model's own loss), taken over **all** the step's tokens as
    ``moe/sharded_moe.TopKGate`` takes it under any mesh, from the first pick."""
    nll, aux = reference_nll(params, ids, cfg, moe_aux_loss_coef)
    return jnp.mean(nll) + aux
