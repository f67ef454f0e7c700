"""Solar Open 2 (``model_type: solar_open2``; e.g.
``upstage/Solar-Open2-250B``): a decoder whose every layer is **a mixer and
a routed feed-forward**, the mixer a Kimi-delta-attention (KDA) layer or -
at the layers ``gqa_layers`` names, one in four - a grouped-query softmax
attention with **no positional term** and a gate on its output.

The equations (``D`` hidden, ``H`` KDA heads of ``d`` = ``linear_attn_config.
head_dim`` key and value columns, ``I = H d``, ``K`` =
``short_conv_kernel_size``, ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``,
``eps`` = ``rms_norm_eps``)::

    a = rms(h; w_in);  h <- h + Mixer(a)
    f = rms(h; w_ff);  h <- h + MoE(f)
    logits = rms(h; w_f) @ W_head

    KDA (a token t, a head):
        [q' | k' | v]_t = silu(sum_{j<K} w_c[j] * ([W_q | W_k | W_v] a)_{t-K+1+j})   (rows before the start: 0)
        q = q' / sqrt(|q'|^2 + 1e-6) / sqrt(d);   k = k' / sqrt(|k'|^2 + 1e-6)
        log alpha_t = -exp(A_log[head]) * softplus(W_f2 (W_f1 a_t) + dt_bias)   [d], <= 0
        beta_t = 2 sigmoid(w_beta . a_t)                      (kda_allow_neg_eigval: in (0, 2))
        S' = Diag(alpha_t) S_{t-1};   S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     [d, d], float32
        o_t = S_t^T q_t
        Mixer = W_o (rms(o_t; w_o) * sigmoid(W_g2 (W_g1 a_t) + b_g))     (the norm a head, over d)

    attention (layer i in gqa_layers):
        q [Hq, d], k, v [Hkv, d] = a W_q, a W_k, a W_v;  o = causal softmax(q k / sqrt(d)) v
        Mixer = W_o (o * sigmoid(a W_gate))                   (use_gqa_gate; no rotary, bias, window)

    MoE:  s = sigmoid(f W_r);  picks = the k largest of s + b;  w = s[picks] / sum s[picks] * scale
          MoE = sum_i w_i E_picks[i](f) + E_shared(f);   E(x) = W_down(silu(W_gate x) * (W_up x))

**The transition is not diagonal**: ``(I - beta k k^T) Diag(alpha)`` - a
token decays every key column by a factor of its own and then *rotates* the
state toward its key before it writes (an eigenvalue ``1 - beta`` in (-1, 1)
along ``k``), so neither a decay mask over a chunk's rows nor an
element-wise scan expresses a chunk of a sequence
(``ops/pallas/kda.py``: the rows in order through the slot's state).

Three **forms** follow from no key of the published config and are the
named families' published ones: KDA's inner forms (the two-factor
projections of width ``head_dim``, ``A_log`` a head and ``dt_bias`` a
channel, the L2 norms, the sigmoid-gated head norm) are Kimi Linear's
(arXiv:2510.26692); ``use_gqa_gate`` is read as the element-wise sigmoid
gate on the attention's output before ``W_o``; the router's score as the
sigmoid with a selection bias. Not read: ``intermediate_size`` (every
layer's feed-forward is routed: ``first_k_dense_replace`` 0), ``rope_theta``
and ``partial_rotary_factor`` (``use_rope`` false).

The state a sequence carries through a KDA layer is ``S`` ``[H, d, d]``
(float32, key rows, value columns) and the convolutions' tail: the last ``K
- 1`` rows of ``[W_q | W_k | W_v] a`` before the activation, ``[K - 1, 3 I]``.

Parameter tree: the mixers of a kind are stacked (``Lk`` KDA, ``Lg``
attention, each in stack order), the feed-forwards of all ``L`` layers in one
stack, matrices ``[in, out]``; KDA's three projections side by side in one
``qkv_proj`` and its three depth-wise convolutions in one ``conv_kernel``::

    model/embed_tokens [V, D]     model/norm/scale [D]     lm_head/kernel [D, V]
    model/kda_layers/input_layernorm/scale [Lk, D]    .../qkv_proj/kernel [Lk, D, 3 I]
    model/kda_layers/conv_kernel [Lk, K, 3 I]         .../b_proj/kernel [Lk, D, H]
    model/kda_layers/f_a_proj/kernel [Lk, D, d]       .../f_b_proj/kernel [Lk, d, I]
    model/kda_layers/{A_log [Lk, H], dt_bias [Lk, I]}
    model/kda_layers/g_a_proj/kernel [Lk, D, d]       .../g_b_proj/{kernel [Lk, d, I], bias [Lk, I]}
    model/kda_layers/o_norm/scale [Lk, d]             .../o_proj/kernel [Lk, I, D]
    model/gqa_layers/input_layernorm/scale [Lg, D]    .../{q,k,v,gate,o}_proj/kernel [Lg, in, out]
    model/moe/post_attention_layernorm/scale [L, D]
    model/moe/gate/{weight [L, D, E], e_score_correction_bias [L, E]}
    model/moe/experts/{gate,up,down}_proj [L, held, in, out]
    model/moe/shared_experts/{gate,up,down}_proj/kernel [L, in, out]

Serving only: ``inference/v2`` runs this model through
``model_runner.SolarOpen2Kind``; :func:`reference_logits` is the plain
float32 forward over whole sequences, the recurrence a token at a time.
"""

import dataclasses
import itertools
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.moonlight import _Tree, _rms_norm
from deepspeed_tpu.models import nemotron_h
from deepspeed_tpu.models.nemotron_h import _uniform, segments_of

KDA, GQA = "k", "g"
L2_EPS = 1e-6       # under the root of q's and k's norm a head (Kimi Linear's)

PUBLISHED_LINEAR_ATTN = (("head_dim", 128), ("num_heads", 64), ("num_kv_heads", None),
                         ("short_conv_kernel_size", 4))


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240          # not read: every layer's feed-forward is routed
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    # the published group, a dict there; kept as sorted (key, value) pairs so the config hashes
    linear_attn_config: tuple = PUBLISHED_LINEAR_ATTN
    gqa_interval: int = 3                   # the KDA layers after each attention layer
    gqa_layers: tuple = tuple(range(0, 48, 4))
    use_rope: bool = False
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    rope_theta: float = 10000.0             # not read: use_rope is false
    partial_rotary_factor: float = 1.0      # not read
    # the routed feed-forward
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320             # the router's columns, whatever is held
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1280
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    # the share of an expert-parallel deployment held here (None: every routed expert)
    experts_held: Optional[int] = None
    first_expert_held: int = 0

    def __post_init__(self):
        if isinstance(self.linear_attn_config, dict):
            object.__setattr__(self, "linear_attn_config",
                               tuple(sorted(self.linear_attn_config.items())))
        object.__setattr__(self, "gqa_layers", tuple(self.gqa_layers))
        linear = dict(self.linear_attn_config)
        unsupported = {
            "kda_use_full_proj": self.kda_use_full_proj,
            "kda_allow_neg_eigval": not self.kda_allow_neg_eigval,
            "use_rope": self.use_rope,
            "use_gqa_gate": not self.use_gqa_gate,
            "linear_attn_config.num_kv_heads": linear.get("num_kv_heads") is not None,
            "linear_attn_config.short_conv_kernel_size":
                linear.get("short_conv_kernel_size", 0) < 2,
            "first_k_dense_replace (leading dense layers)": self.first_k_dense_replace != 0,
            "n_shared_experts": self.n_shared_experts != 1,
            "norm_topk_prob": not self.norm_topk_prob,
            "tie_word_embeddings": self.tie_word_embeddings,
            "gqa_layers": any(not 0 <= i < self.num_hidden_layers for i in self.gqa_layers),
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"SolarOpen2Config: unsupported setting of {bad}")
        if not 0 < self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError("SolarOpen2Config: num_experts_per_tok exceeds the router's columns")
        if not (0 <= self.first_expert_held
                and 0 < self.held and self.first_expert_held + self.held <= self.n_routed_experts):
            raise ValueError(
                f"SolarOpen2Config: experts {self.first_expert_held}..+{self.held} are not "
                f"among the {self.n_routed_experts} routed")

    @property
    def held(self):
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    @property
    def kda_heads(self):
        return dict(self.linear_attn_config)["num_heads"]

    @property
    def kda_head_dim(self):
        return dict(self.linear_attn_config)["head_dim"]

    @property
    def kda_conv(self):
        return dict(self.linear_attn_config)["short_conv_kernel_size"]

    @property
    def kda_inner(self):
        """``I``: a KDA projection's width, heads x head size."""
        return self.kda_heads * self.kda_head_dim

    @property
    def letters(self):
        """A letter a layer, its mixer's kind: the published stack is ``gkkk`` twelve times."""
        return "".join(GQA if i in self.gqa_layers else KDA
                       for i in range(self.num_hidden_layers))

    def count(self, letter):
        return self.letters.count(letter)

    @property
    def segments(self):
        """The stack as ``[(unit, repeats), ...]`` for
        ``model_runner._run_segments``: one scan over a period where the
        pattern repeats (``nemotron_h.segments_of``: ``("gkkk", 12)``), and
        where it does not - one period alone, a pipeline stage - a run of
        layers of one kind is one scan (``("g", 1), ("k", 3)``: two layer
        bodies in a program, not four)."""
        found = segments_of(self.letters)
        if any(repeats > 1 for _, repeats in found):
            return found
        return tuple((letter, len(list(run))) for letter, run in itertools.groupby(self.letters))


SOLAR_OPEN2_CONFIGS = {
    "solar-open2-250b": SolarOpen2Config(),
    # rank 0 of 8-way expert parallelism, pipeline stage 1 of 12 (benchmark/configs/
    # solar-open2-ep8-4l.json): every width as published, the published layers 4-7 (one whole
    # period: GQA, KDA, KDA, KDA), experts 0-39 of 320, an eighth of the vocabulary
    "solar-open2-ep8-4l": SolarOpen2Config(
        num_hidden_layers=4, gqa_layers=(0,), vocab_size=24576, experts_held=40),
    # every mechanism at a size the CPU tests run: two whole periods, the two-factor
    # projections, beta in (0, 2), three convolutions of 4, a query group of 2 over 2
    # key-value heads, 16 experts top-4 beside a shared one
    "solar-open2-debug": SolarOpen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_attn_config=(("head_dim", 16), ("num_heads", 4), ("num_kv_heads", None),
                            ("short_conv_kernel_size", 4)),
        gqa_layers=(0, 4), n_routed_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        max_position_embeddings=512),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, H, d, I, K = (cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner,
                     cfg.kda_conv)
    L, Lk, Lg, F = (cfg.num_hidden_layers, cfg.count(KDA), cfg.count(GQA),
                    cfg.moe_intermediate_size)
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    model = {"embed_tokens": (cfg.vocab_size, D), "norm": {"scale": (D,)}}
    if Lk:
        model["kda_layers"] = {
            "input_layernorm": {"scale": (Lk, D)}, "qkv_proj": {"kernel": (Lk, D, 3 * I)},
            "conv_kernel": (Lk, K, 3 * I), "b_proj": {"kernel": (Lk, D, H)},
            "f_a_proj": {"kernel": (Lk, D, d)}, "f_b_proj": {"kernel": (Lk, d, I)},
            "A_log": (Lk, H), "dt_bias": (Lk, I),
            "g_a_proj": {"kernel": (Lk, D, d)},
            "g_b_proj": {"kernel": (Lk, d, I), "bias": (Lk, I)},
            "o_norm": {"scale": (Lk, d)}, "o_proj": {"kernel": (Lk, I, D)}}
    if Lg:
        model["gqa_layers"] = {
            "input_layernorm": {"scale": (Lg, D)}, "q_proj": {"kernel": (Lg, D, q)},
            "k_proj": {"kernel": (Lg, D, kv)}, "v_proj": {"kernel": (Lg, D, kv)},
            "gate_proj": {"kernel": (Lg, D, q)}, "o_proj": {"kernel": (Lg, q, D)}}
    model["moe"] = {
        "post_attention_layernorm": {"scale": (L, D)},
        "gate": {"weight": (L, D, cfg.n_routed_experts),
                 "e_score_correction_bias": (L, cfg.n_routed_experts)},
        "experts": {"gate_proj": (L, cfg.held, D, F), "up_proj": (L, cfg.held, D, F),
                    "down_proj": (L, cfg.held, F, D)},
        "shared_experts": {"gate_proj": {"kernel": (L, D, F)}, "up_proj": {"kernel": (L, D, F)},
                           "down_proj": {"kernel": (L, F, D)}}}
    return {"model": model, "lm_head": {"kernel": (D, cfg.vocab_size)}}


# the seeded step of a KDA channel (the gated-delta family's defaults; no key of the config)
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 1e-3, 1e-1, 1e-4


def initializer_of(cfg):
    """A parameter's name → its initializer. The decay's parameters as the
    gated-delta family's code draws them (``nemotron_h.initializer_of`` /
    ``jamba`` do the same), so that decays lie where a trained model's do:
    ``A_log = log(U(1, 16))`` a head; ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly in ``[TIME_STEP_MIN, TIME_STEP_MAX]`` a channel
    - a key column forgets in 0.6 to 1000 tokens; the convolutions uniform
    in ``+- 1 / sqrt(K)``; the router's selection bias **zero**, as the
    configuration's file says."""
    bound = 1.0 / math.sqrt(cfg.kda_conv)

    def dt_bias(key, shape, dtype=jnp.float32):
        lo, hi = math.log(TIME_STEP_MIN), math.log(TIME_STEP_MAX)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)),
                         TIME_STEP_FLOOR)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)

    table = {"scale": nn.initializers.ones, "dt_bias": dt_bias, "A_log": a_log,
             "conv_kernel": _uniform(-bound, bound),
             "e_score_correction_bias": nn.initializers.zeros}
    return lambda name: table.get(name, nn.initializers.normal(0.02))


class SolarOpen2ForCausalLM(nn.Module):
    config: SolarOpen2Config

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        init = initializer_of(self.config)
        params = {name: _Tree(value, init, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_solar_open2(preset_or_config="solar-open2-debug", **overrides) -> SolarOpen2ForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, SolarOpen2Config) \
        else SOLAR_OPEN2_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return SolarOpen2ForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def delta_rule(q, k, v, log_alpha, beta, state):
    """The recurrence a token at a time: q, k, v, log_alpha [B, S, H, d],
    beta [B, S, H], state [B, H, d, d] (key rows, value columns) → (o
    [B, S, H, d], the state the sequences leave)."""
    def one(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[..., None] * s
        seen = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + (b_t[..., None, None] * k_t[..., None]) * (v_t - seen)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    last, o = jax.lax.scan(one, state,
                           tuple(jnp.moveaxis(r, 1, 0) for r in (q, k, v, log_alpha, beta)))
    return jnp.moveaxis(o, 0, 1), last


def reference_kda(p, x, cfg, state=None, tail=None):
    """One KDA mixer on whole sequences, the recurrence a token at a time: x
    [B, S, D] (the normalised stream) → (y [B, S, D], the state it leaves
    [B, H, d, d] - key rows, value columns -, the convolutions' tail it
    leaves [B, K - 1, 3 I]: the last rows of ``[W_q | W_k | W_v] a`` before
    the activation). ``state`` / ``tail``: what the sequences carried in
    (None: a sequence's start, both zero)."""
    p = _f32(p)
    B, S, _ = x.shape
    H, d, I, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner, cfg.kda_conv
    qkv = x @ p["qkv_proj"]["kernel"]
    before = jnp.zeros((B, K - 1, 3 * I), jnp.float32) if tail is None \
        else tail.astype(jnp.float32)
    padded = jnp.concatenate([before, qkv], axis=1)
    act = jax.nn.silu(sum(p["conv_kernel"][j] * padded[:, j:j + S] for j in range(K)))
    q, k, v = (act[..., i * I:(i + 1) * I].reshape(B, S, H, d) for i in range(3))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(d)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    step = jax.nn.softplus((x @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"] + p["dt_bias"])
    log_alpha = -jnp.exp(p["A_log"])[:, None] * step.reshape(B, S, H, d)
    beta = 2.0 * jax.nn.sigmoid(x @ p["b_proj"]["kernel"])                      # [B, S, H]
    start = jnp.zeros((B, H, d, d), jnp.float32) if state is None else state.astype(jnp.float32)
    o, last = delta_rule(q, k, v, log_alpha, beta, start)
    gate = jax.nn.sigmoid((x @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"]
                          + p["g_b_proj"]["bias"])
    o = _rms_norm(o, p["o_norm"]["scale"], cfg.rms_norm_eps).reshape(B, S, I) * gate
    return o @ p["o_proj"]["kernel"], last, padded[:, S:]


def reference_attention(p, x, cfg, gated=True):
    """The attention mixer: x [B, S, D] → y. Causal grouped-query softmax
    attention by a mask over all rows, queries and keys as projected (no
    positional term), the output gated element-wise by ``sigmoid(a
    W_gate)`` before ``W_o``. ``gated`` false: a control's."""
    p = _f32(p)
    B, S, _ = x.shape
    Hq, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = (x @ p["q_proj"]["kernel"]).reshape(B, S, Hkv, Hq // Hkv, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(B, S, Hkv, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(B, S, Hkv, d)
    scores = jnp.einsum("bpkgd,bukd->bkgpu", q, k) / math.sqrt(d)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgpu,bukd->bpkgd", probs, v).reshape(B, S, Hq * d)
    if gated:
        out = out * jax.nn.sigmoid(x @ p["gate_proj"]["kernel"])
    return out @ p["o_proj"]["kernel"]


def reference_router(p, x, cfg):
    """→ (weights [..., E], margin [...]): ``nemotron_h.reference_router``'s
    biased sigmoid top-k - the picks' unbiased scores over their sum, times
    ``routed_scaling_factor`` - read through this layer's ``gate``."""
    return nemotron_h.reference_router({"router": p["gate"]}, x, cfg)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def reference_moe(p, x, cfg, share=None, shared=True):
    """One routed feed-forward: x [..., D] → y. Every held expert applied
    to every token, weighted (zero where the router did not pick it).
    ``share``: (first, held) of the router's columns (None: the config's own
    share), the experts ``p`` holds; ``shared``: whether the shared expert
    is added (every share computes it alike: a sum over shares counts it
    once)."""
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


STACKS = {KDA: "kda_layers", GQA: "gqa_layers"}


def layer_params(params, cfg, position):
    """→ (the mixer's parameters, the feed-forward's) of the layer at
    ``position`` of the stack, each cut out of its kind's stack."""
    letter = cfg.letters[position]
    i = cfg.letters[:position].count(letter)
    model = params["model"]
    return (jax.tree.map(lambda w: w[i], model[STACKS[letter]]),
            jax.tree.map(lambda w: w[position], model["moe"]))


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no chunks, no slots, no kernels: the
    convolutions as shifted products and the delta rule a token at a time,
    both from a zero start; attention by a mask over all rows; an explicit
    top-k and every held expert on every token, given the config's share.

    Departures from the published description, none of the mathematics:
    weights ``[in, out]``, the mixers of a kind stacked; KDA's three
    projections in one ``qkv_proj`` and its three convolutions in one
    ``[K, 3 I]`` set of taps; float32 throughout; no attention-mask
    argument, no dropout; the three forms the module's docstring names as
    assumed."""
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        h = params["model"]["embed_tokens"][input_ids].astype(jnp.float32)
        for position, letter in enumerate(cfg.letters):
            mixer, moe = layer_params(params, cfg, position)
            x = _rms_norm(h, mixer["input_layernorm"]["scale"].astype(jnp.float32), eps)
            h = h + (reference_kda(mixer, x, cfg)[0] if letter == KDA
                     else reference_attention(mixer, x, cfg))
            x = _rms_norm(h, moe["post_attention_layernorm"]["scale"].astype(jnp.float32), eps)
            h = h + reference_moe(moe, x, cfg)
        h = _rms_norm(h, params["model"]["norm"]["scale"].astype(jnp.float32), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ params["lm_head"]["kernel"].astype(jnp.float32)
