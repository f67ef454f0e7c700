"""Nemotron-H (``model_type: nemotron_h``; e.g.
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``): a decoder in which
**each layer is one sublayer alone**, of the kind its letter in
``hybrid_override_pattern`` names —

- ``M``: a Mamba-2 mixer: a depthwise causal convolution, then a
  state-space recurrence whose decay is **data-dependent a token**, whose
  ``B`` and ``C`` are shared by a group of heads, with a skip ``D x`` and a
  gated group norm;
- ``*``: grouped-query softmax attention with **no positional term**;
- ``E``: a LatentMoE expert layer: a sigmoid router with a selection bias
  over ``n_routed_experts`` columns, routed experts of **two matrices and
  ``relu(.)^2``** that work in a ``moe_latent_size``-wide latent which one
  shared projection enters and another leaves, beside a shared expert on
  the full width.

The equations (``D`` hidden, ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``,
``eps`` = ``layer_norm_epsilon``)::

    h <- h + F_t(rms(h; w_t))  for every layer t;   logits = rms(h; w_f) @ W_head

    M:  [z | xBC | dt] = x W_in          widths I | I + 2 G N | H      (I = H P = expand D)
        xBC_t <- silu(b_c + sum_{j<K} w_c[j] * xBC_{t-K+1+j})          (rows before the start: 0)
        x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t);  head h reads group h // (H / G)
        Delta_t = softplus(dt_t + dt_bias) [H];  a_t = exp(Delta_t A),  A = -exp(A_log)
        S_t = a_t S_{t-1} + Delta_t x_t (x) B_t   [H, P, N], S_{-1} = 0
        y_t = S_t C_t + D x_t
        out = (w_n * rms_{groups of I / G}(y * silu(z))) W_out

    *:  q [Hq, d], k, v [Hkv, d] = x W_q, x W_k, x W_v;  causal softmax(q k / sqrt(d)) v;  W_o
        (no rotary embedding: the family's attention applies none)

    E:  s = sigmoid(x W_r) [E];  the k picks: the largest of s + bias;
        w_j = routed_scaling_factor * s_j / sum of the picks' s        (norm_topk_prob)
        u = x W_down [Z];  v = sum_j w_j relu(u W1_j)^2 W2_j;  routed = v W_up
        out = routed + relu(x Ws1)^2 Ws2

The state a sequence carries through an ``M`` layer is ``S`` and **the
convolution's tail**: the last ``K - 1`` rows of ``xBC`` before the
activation. ``chunk_size`` is the published kernel's blocking and changes
no result; ``time_step_min`` / ``_max`` / ``_floor`` only initialise
``dt_bias``.

**Left out**: the multi-token-prediction head (``num_nextn_predict_layers``,
``mtp_hybrid_override_pattern``): no weights are held for it and nothing
speculates with it. A ``-`` layer (the family's dense MLP) is refused, as
are group-limited routing, projection biases and tied embeddings.

An **expert share** (``experts_held`` of ``n_routed_experts`` from
``first_expert_held``): the router keeps every column and every pick; the
held picks alone are computed, and what experts held elsewhere would add
is left out (``ops/grouped_gemm.ExpertShare``). The latent projections,
the router and the shared expert are whole on every share.

Parameter tree: the layers of a kind are stacked (``Lm`` Mamba, ``La``
attention, ``Le`` expert layers, each in stack order), matrices
``[in, out]``::

    model/embed_tokens [V, D]     model/norm/scale [D]     lm_head/kernel [D, V]
    model/mamba_layers/norm/scale [Lm, D]      .../in_proj/kernel [Lm, D, 2 I + 2 G N + H]
    model/mamba_layers/conv_kernel [Lm, K, I + 2 G N]     .../conv_bias [Lm, I + 2 G N]
    model/mamba_layers/{dt_bias, A_log, D} [Lm, H]        .../gate_norm/scale [Lm, I]
    model/mamba_layers/out_proj/kernel [Lm, I, D]
    model/attn_layers/norm/scale [La, D]       .../{q,k,v,o}_proj/kernel [La, in, out]
    model/moe_layers/norm/scale [Le, D]        .../router/weight [Le, D, E]
    model/moe_layers/router/e_score_correction_bias [Le, E]
    model/moe_layers/latent_{down,up}/kernel [Le, D, Z] / [Le, Z, D]
    model/moe_layers/experts/{up,down}_proj [Le, held, Z, F] / [Le, held, F, Z]
    model/moe_layers/shared_experts/{up,down}_proj/kernel [Le, D, Fs] / [Le, Fs, D]

Serving only: ``inference/v2`` runs this model through
``model_runner.NemotronHKind`` (paged keys and values of the ``*`` layers,
a slot a sequence of Mamba states and convolution tails);
:func:`reference_logits` is the plain float32 forward over whole
sequences, the recurrence a token at a time.
"""

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.moonlight import _Tree, _rms_norm

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"

# nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json, hybrid_override_pattern
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88             # the layers that run: len(hybrid_override_pattern)
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    expand: int = 2
    chunk_size: int = 128
    mamba_hidden_act: str = "silu"
    mamba_proj_bias: bool = False
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the expert layer
    n_routed_experts: int = 512             # the router's columns, whatever is held
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    use_bias: bool = False
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    # the share of an expert-parallel deployment held here (None: every routed expert)
    experts_held: Optional[int] = None
    first_expert_held: int = 0

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        unsupported = {
            "hybrid_override_pattern": not pattern
            or any(t not in (MAMBA, EXPERTS, ATTENTION) for t in pattern),
            "num_hidden_layers": self.num_hidden_layers != len(pattern),
            "n_group / topk_group (group-limited routing)": self.n_group != 1
            or self.topk_group != 1,
            "norm_topk_prob": not self.norm_topk_prob,
            "n_shared_experts": self.n_shared_experts != 1,
            "attention_bias": self.attention_bias,
            "mamba_proj_bias": self.mamba_proj_bias,
            "mlp_bias": self.mlp_bias,
            "use_bias": self.use_bias,
            "use_conv_bias": not self.use_conv_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
            "mamba_hidden_act": self.mamba_hidden_act != "silu",
            "mlp_hidden_act": self.mlp_hidden_act != "relu2",
            "expand": self.mamba_num_heads * self.mamba_head_dim != self.expand * self.hidden_size,
            "n_groups": self.mamba_num_heads % self.n_groups != 0,
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"NemotronHConfig: unsupported setting of {bad}")
        if not 0 < self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError("NemotronHConfig: num_experts_per_tok exceeds the router's columns")
        if not (0 <= self.first_expert_held
                and 0 < self.held and self.first_expert_held + self.held <= self.n_routed_experts):
            raise ValueError(
                f"NemotronHConfig: experts {self.first_expert_held}..+{self.held} are not "
                f"among the {self.n_routed_experts} routed")

    @property
    def held(self):
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    @property
    def mamba_inner(self):
        """``I``: the Mamba mixer's inner width, heads x head size."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        """The convolution's channels: ``x``, ``B`` and ``C`` side by side."""
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def count(self, letter):
        return self.hybrid_override_pattern.count(letter)

    @property
    def segments(self):
        """The stack as ``[(unit, repeats), ...]`` (:func:`segments_of` the
        pattern): ``EMEMEMEMEM*`` is ``[("EM", 5), ("*", 1)]``: the serving
        stack scans the first and runs the second."""
        return segments_of(self.hybrid_override_pattern)


def segments_of(pattern):
    """A stack's letters → ``[(unit, repeats), ...]``: the longest stretch
    of a repeating unit of two layers or more wherever the pattern repeats,
    single layers (``repeats`` 1) elsewhere."""
    out, i = [], 0
    while i < len(pattern):
        best = (pattern[i], 1)
        for p in range(2, (len(pattern) - i) // 2 + 1):
            unit, r = pattern[i:i + p], 1
            while pattern[i + r * p:i + (r + 1) * p] == unit:
                r += 1
            if r > 1 and r * p > len(best[0]) * best[1]:
                best = (unit, r)
        out.append(best)
        i += len(best[0]) * best[1]
    return tuple(out)


NEMOTRON_H_CONFIGS = {
    # rank 0 of 4-way expert parallelism, pipeline stage 3 of 8 (benchmark/configs/
    # nemotron3-super-ep4-11l.json): every width as published, the published layers 26-36
    # (one whole period, 5 : 5 : 1), experts 0-127 of 512, a quarter of the vocabulary
    "nemotron3-super-ep4-11l": NemotronHConfig(
        num_hidden_layers=11, hybrid_override_pattern=PUBLISHED_PATTERN[26:37],
        vocab_size=32768, experts_held=128),
    # every mechanism at a size the CPU tests run: 2 groups of 2 heads, a convolution
    # of 4, 8 routed experts of which 3 are picked, and a pattern that scans a period,
    # then changes it
    "nemotron-h-debug": NemotronHConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=8, hybrid_override_pattern="EMEM*MEM",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
        mamba_head_dim=32, n_groups=2, ssm_state_size=16, n_routed_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=48, moe_latent_size=32,
        moe_shared_expert_intermediate_size=96, max_position_embeddings=512),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, H, I, C = cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_inner, cfg.conv_dim
    Lm, La, Le = cfg.count(MAMBA), cfg.count(ATTENTION), cfg.count(EXPERTS)
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    Z, F, Fs = cfg.moe_latent_size, cfg.moe_intermediate_size, \
        cfg.moe_shared_expert_intermediate_size
    model = {"embed_tokens": (cfg.vocab_size, D), "norm": {"scale": (D,)}}
    if Lm:
        model["mamba_layers"] = {
            "norm": {"scale": (Lm, D)}, "in_proj": {"kernel": (Lm, D, I + C + H)},
            "conv_kernel": (Lm, cfg.conv_kernel, C), "conv_bias": (Lm, C),
            "dt_bias": (Lm, H), "A_log": (Lm, H), "D": (Lm, H),
            "gate_norm": {"scale": (Lm, I)}, "out_proj": {"kernel": (Lm, I, D)}}
    if La:
        model["attn_layers"] = {
            "norm": {"scale": (La, D)}, "q_proj": {"kernel": (La, D, q)},
            "k_proj": {"kernel": (La, D, kv)}, "v_proj": {"kernel": (La, D, kv)},
            "o_proj": {"kernel": (La, q, D)}}
    if Le:
        model["moe_layers"] = {
            "norm": {"scale": (Le, D)},
            "router": {"weight": (Le, D, cfg.n_routed_experts),
                       "e_score_correction_bias": (Le, cfg.n_routed_experts)},
            "latent_down": {"kernel": (Le, D, Z)}, "latent_up": {"kernel": (Le, Z, D)},
            "experts": {"up_proj": (Le, cfg.held, Z, F), "down_proj": (Le, cfg.held, F, Z)},
            "shared_experts": {"up_proj": {"kernel": (Le, D, Fs)},
                               "down_proj": {"kernel": (Le, Fs, D)}}}
    return {"model": model, "lm_head": {"kernel": (D, cfg.vocab_size)}}


def _uniform(lo, hi):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, lo, hi)
    return init


def initializer_of(cfg):
    """A parameter's name → its initializer. The state-space parameters
    as the family's code draws them, so that decays lie where a trained
    model's do: ``A_log = log(U(1, 16))``; ``dt_bias`` the inverse softplus
    of a step drawn log-uniformly in ``[time_step_min, time_step_max]`` (not
    under ``time_step_floor``); ``D`` ones; the convolution uniform in
    ``+- 1 / sqrt(conv_kernel)``."""
    bound = 1.0 / math.sqrt(cfg.conv_kernel)

    def dt_bias(key, shape, dtype=jnp.float32):
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)),
                         cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)

    table = {"scale": nn.initializers.ones, "D": nn.initializers.ones, "dt_bias": dt_bias,
             "A_log": a_log, "conv_kernel": _uniform(-bound, bound),
             "conv_bias": _uniform(-bound, bound),
             # the checkpoint's is trained and non-zero; zeros would hide a router that
             # weights by the biased score
             "e_score_correction_bias": nn.initializers.normal(0.1)}
    return lambda name: table.get(name, nn.initializers.normal(0.02))


class NemotronHForCausalLM(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        init = initializer_of(self.config)
        params = {name: _Tree(value, init, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_nemotron_h(preset_or_config="nemotron-h-debug", **overrides) -> NemotronHForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, NemotronHConfig) \
        else NEMOTRON_H_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return NemotronHForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def reference_mamba(p, x, cfg, state=None, tail=None):
    """One ``M`` layer's mixer on whole sequences, the recurrence a token
    at a time: x [B, S, D] (the normalised stream) → (y [B, S, D], the
    state it leaves [B, H, P, N], the convolution's tail it leaves
    [B, K - 1, C]: the last rows of ``xBC`` before the activation).
    ``state`` / ``tail``: what the sequences carried in (None: a
    sequence's start, both zero)."""
    p = _f32(p)
    B, S, _ = x.shape
    H, P, G, N, K = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size,
                     cfg.conv_kernel)
    I, C = cfg.mamba_inner, cfg.conv_dim
    zxbcdt = x @ p["in_proj"]["kernel"]
    z, xbc, dt = zxbcdt[..., :I], zxbcdt[..., I:I + C], zxbcdt[..., I + C:]
    before = jnp.zeros((B, K - 1, C), jnp.float32) if tail is None else tail.astype(jnp.float32)
    padded = jnp.concatenate([before, xbc], axis=1)
    conv = p["conv_bias"] + sum(p["conv_kernel"][j] * padded[:, j:j + S] for j in range(K))
    act = jax.nn.silu(conv)
    xs = act[..., :I].reshape(B, S, H, P)
    per = H // G
    b_heads = jnp.repeat(act[..., I:I + G * N].reshape(B, S, G, N), per, axis=2)
    c_heads = jnp.repeat(act[..., I + G * N:].reshape(B, S, G, N), per, axis=2)
    delta = jax.nn.softplus(dt + p["dt_bias"])                          # [B, S, H]
    decay = jnp.exp(delta * -jnp.exp(p["A_log"]))

    def one(s, row):
        a_t, d_t, x_t, b_t, c_t = row
        s = a_t[..., None, None] * s + (d_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    start = jnp.zeros((B, H, P, N), jnp.float32) if state is None else state.astype(jnp.float32)
    rows = tuple(jnp.moveaxis(r, 1, 0) for r in (decay, delta, xs, b_heads, c_heads))
    last, y = jax.lax.scan(one, start, rows)
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs
    y = (y.reshape(B, S, I) * jax.nn.silu(z)).reshape(B, S, G, I // G)
    y = _rms_norm(y, 1.0, cfg.layer_norm_epsilon).reshape(B, S, I) * p["gate_norm"]["scale"]
    return y @ p["out_proj"]["kernel"], last, padded[:, S:]


def reference_attention(p, x, cfg):
    """One ``*`` layer's mixer: x [B, S, D] → y; causal, no positional term."""
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
    return out @ p["o_proj"]["kernel"]


def reference_router(p, x, cfg):
    """→ (weights [..., E]: ``routed_scaling_factor * s_j / sum of the
    picks' s`` at the picks, zero elsewhere; margin [...]: the last pick's
    lead over the first column left out, of ``s + bias``)."""
    s = jax.nn.sigmoid(x @ p["router"]["weight"])
    biased = s + p["router"]["e_score_correction_bias"]
    k = cfg.num_experts_per_tok
    ranked, chosen = jax.lax.top_k(biased, min(k + 1, biased.shape[-1]))
    picked = jnp.sum(jax.nn.one_hot(chosen[..., :k], s.shape[-1], dtype=jnp.float32), axis=-2) > 0
    weights = jnp.where(picked, s, 0.0)
    weights = cfg.routed_scaling_factor * weights / (weights.sum(-1, keepdims=True) + 1e-20)
    margin = ranked[..., k - 1] - ranked[..., k] if ranked.shape[-1] > k \
        else jnp.full(s.shape[:-1], jnp.inf)
    return weights, margin


def reference_experts(p, x, cfg, share=None, shared=True):
    """One ``E`` layer: x [..., D] → y. Every held expert applied to every
    token, weighted (zero where the router did not pick it). ``share``:
    (first, held) of the router's columns (None: the config's own share),
    the experts ``p`` holds; ``shared``: whether the shared expert is
    added (every share computes it alike: a sum over shares counts it
    once)."""
    p = _f32(p)
    first, held = (cfg.first_expert_held, cfg.held) if share is None else share
    weights, _ = reference_router(p, x, cfg)
    u = x @ p["latent_down"]["kernel"]

    def one(acc, e):
        out = relu2(u @ p["experts"]["up_proj"][e]) @ p["experts"]["down_proj"][e]
        return acc + out * jnp.take(weights, first + e, axis=-1)[..., None], None

    v, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(held))
    y = v @ p["latent_up"]["kernel"]
    if shared:
        s = p["shared_experts"]
        y = y + relu2(x @ s["up_proj"]["kernel"]) @ s["down_proj"]["kernel"]
    return y


STACKS = {MAMBA: "mamba_layers", ATTENTION: "attn_layers", EXPERTS: "moe_layers"}


def layer_params(params, cfg, position):
    """The parameters of the layer at ``position`` of the stack, cut out
    of its kind's stack."""
    letter = cfg.hybrid_override_pattern[position]
    i = cfg.hybrid_override_pattern[:position].count(letter)
    return jax.tree.map(lambda w: w[i], params["model"][STACKS[letter]])


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no chunks: the state-space recurrence a
    token at a time from a zero state, attention by a mask over all rows,
    an explicit top-k and every held expert on every token.

    Departures from the source's modeling file: weights ``[in, out]``, the
    layers of a kind stacked; the convolution as ``[K, C]`` taps; float32
    throughout; no multi-token-prediction head; no attention-mask
    argument, no dropout."""
    eps = cfg.layer_norm_epsilon
    with jax.default_matmul_precision("highest"):
        h = params["model"]["embed_tokens"][input_ids].astype(jnp.float32)
        for position, letter in enumerate(cfg.hybrid_override_pattern):
            lp = layer_params(params, cfg, position)
            x = _rms_norm(h, lp["norm"]["scale"].astype(jnp.float32), eps)
            if letter == MAMBA:
                y = reference_mamba(lp, x, cfg)[0]
            elif letter == ATTENTION:
                y = reference_attention(lp, x, cfg)
            else:
                y = reference_experts(lp, x, cfg)
            h = h + y
        h = _rms_norm(h, params["model"]["norm"]["scale"].astype(jnp.float32), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ params["lm_head"]["kernel"].astype(jnp.float32)
