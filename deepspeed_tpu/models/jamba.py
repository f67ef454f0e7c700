"""Jamba (``model_type: jamba``; e.g. ``ai21labs/AI21-Jamba2-3B``): a decoder
whose every layer is **a mixer and a feed-forward**, the mixer a Mamba-1
state-space layer or - once a period - a grouped-query softmax attention
with **no positional term**, the feed-forward a dense SwiGLU
(``num_experts`` 1: ``expert_layer_period`` / ``_offset`` select nothing).

The equations (``D`` hidden, ``I = mamba_expand D`` channels, ``N =
mamba_d_state`` state columns, ``R = mamba_dt_rank``, ``rms(x; w) = x /
sqrt(mean(x^2) + eps) * w``, ``eps`` = ``rms_norm_eps``)::

    a = rms(h; w_in);  h <- h + Mixer(a)
    f = rms(h; w_ff);  h <- h + W_down(silu(W_gate f) * (W_up f))
    logits = rms(h; w_f) @ E^T                             (E the embedding: tied)

    attention (layer i with i % attn_layer_period == attn_layer_offset):
        q [Hq, d], k, v [Hkv, d] = a W_q, a W_k, a W_v;  causal softmax(q k / sqrt(d)) v;  W_o
        (no rotary embedding, no bias, no window: the Mamba layers carry the order)

    Mamba-1 (every other layer):
        [x | z] = a W_in                                   widths I | I
        x_t <- silu(b_c + sum_{j<K} w_c[j] * x_{t-K+1+j})  (rows before the start: 0)
        [dt | B | C] = x W_x                               widths R | N | N, no bias
        dt = rms(dt; w_dt);  B = rms(B; w_B);  C = rms(C; w_C)   (the family's three inner norms)
        Delta_t = softplus(dt W_dt + b_dt) [I];   A = -exp(A_log) [I, N]
        S_t = exp(Delta_t[:, None] * A) * S_{t-1} + (Delta_t * x_t)[:, None] * B_t[None, :]   [I, N]
        y_t = S_t C_t + D * x_t
        out = (y_t * silu(z_t)) W_out

**The decay is an element's own**: ``exp(Delta_t[c] A[c, n])`` differs for
every channel, state column and token (Mamba-2 has one scalar a head a
token), so a chunk of a sequence cannot be taken by a decay mask over its
rows and is **scanned** (``ops/pallas/selective_scan.py``).

The state a sequence carries through a Mamba layer is ``S`` (float32) and
the convolution's tail: the last ``K - 1`` rows of ``x`` before the
activation. Not read: ``use_mamba_kernels`` (which of the source's code paths
runs), ``num_logits_to_keep`` (the serving engine keeps a sequence's last
row itself), ``expert_layer_period`` / ``_offset`` (with one expert every
feed-forward is the dense one); a model with routed experts is refused.

Parameter tree: the mixers of a kind are stacked (``Lm`` Mamba, ``La``
attention, each in stack order), the feed-forwards of all ``L`` layers in
one stack, matrices ``[in, out]``; ``A_log`` **a state column a row**, ``[N,
I]``, the transpose of the source's, so that the channels lie along a
vector's lanes::

    model/embed_tokens [V, D]     model/final_layernorm/scale [D]
    model/mamba_layers/input_layernorm/scale [Lm, D]   .../in_proj/kernel [Lm, D, 2 I]
    model/mamba_layers/conv_kernel [Lm, K, I]          .../conv_bias [Lm, I]
    model/mamba_layers/x_proj/kernel [Lm, I, R + 2 N]  .../{dt,b,c}_layernorm/scale [Lm, R | N | N]
    model/mamba_layers/dt_proj/kernel [Lm, R, I]       .../dt_bias [Lm, I]
    model/mamba_layers/A_log [Lm, N, I]   .../D [Lm, I]   .../out_proj/kernel [Lm, I, D]
    model/attn_layers/input_layernorm/scale [La, D]    .../{q,k,v,o}_proj/kernel [La, in, out]
    model/ffn/pre_ff_layernorm/scale [L, D]            .../{gate,up,down}_proj/kernel [L, in, out]

Serving only: ``inference/v2`` runs this model through
``model_runner.JambaKind``; :func:`reference_logits` is the plain float32
forward over whole sequences, the recurrence a token at a time.
"""

import dataclasses
import itertools
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.moonlight import _Tree, _rms_norm
from deepspeed_tpu.models.nemotron_h import _uniform, reference_attention  # noqa: F401

MAMBA, ATTENTION = "m", "a"


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    hidden_act: str = "silu"
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2            # not read: num_experts is 1
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    use_mamba_kernels: bool = True          # not read
    num_logits_to_keep: int = 1             # not read
    sliding_window: Optional[int] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 262144

    def __post_init__(self):
        unsupported = {
            "num_experts (routed experts)": self.num_experts != 1 or self.num_experts_per_tok != 1,
            "hidden_act": self.hidden_act != "silu",
            "mamba_conv_bias": not self.mamba_conv_bias,
            "mamba_proj_bias": self.mamba_proj_bias,
            "mamba_d_conv": self.mamba_d_conv < 2,
            "sliding_window": self.sliding_window is not None,
            "tie_word_embeddings": not self.tie_word_embeddings,
            "attn_layer_offset": not 0 <= self.attn_layer_offset < self.attn_layer_period,
            "num_attention_heads": self.hidden_size % self.num_attention_heads != 0,
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"JambaConfig: unsupported setting of {bad}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self):
        """``I``: the Mamba mixer's channels."""
        return self.mamba_expand * self.hidden_size

    @property
    def letters(self):
        """A letter a layer, its mixer's kind: the published stack is
        ``mmmmmmmammmmmm`` twice."""
        return "".join(ATTENTION if i % self.attn_layer_period == self.attn_layer_offset
                       else MAMBA for i in range(self.num_hidden_layers))

    def count(self, letter):
        return self.letters.count(letter)

    @property
    def segments(self):
        """The stack as ``[(unit, repeats), ...]`` for
        ``model_runner._run_segments``: a run of layers of one kind is one
        scan over one layer's body (``("m", 7), ("a", 1), ("m", 13), ("a",
        1), ("m", 6)``: five bodies in a program, not a period's fourteen)."""
        return tuple((letter, len(list(run))) for letter, run in itertools.groupby(self.letters))


JAMBA_CONFIGS = {
    "jamba2-3b": JambaConfig(),
    # every mechanism at a size the CPU tests run: two periods of five with the attention
    # layer in the middle (two Mamba layers either side of it), dt_rank, the three inner
    # norms, a convolution of 4, one key-value head under a query group of 3 (heads of 64),
    # 384 channels (three 128-lane pieces of the scan)
    "jamba-debug": JambaConfig(
        vocab_size=256, hidden_size=192, intermediate_size=256, num_hidden_layers=10,
        num_attention_heads=3, num_key_value_heads=1, attn_layer_period=5, attn_layer_offset=2,
        mamba_d_state=16, mamba_dt_rank=8, max_position_embeddings=512),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, I, N, R, K = (cfg.hidden_size, cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                     cfg.mamba_d_conv)
    L, Lm, La, F = (cfg.num_hidden_layers, cfg.count(MAMBA), cfg.count(ATTENTION),
                    cfg.intermediate_size)
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    model = {"embed_tokens": (cfg.vocab_size, D), "final_layernorm": {"scale": (D,)}}
    if Lm:
        model["mamba_layers"] = {
            "input_layernorm": {"scale": (Lm, D)}, "in_proj": {"kernel": (Lm, D, 2 * I)},
            "conv_kernel": (Lm, K, I), "conv_bias": (Lm, I),
            "x_proj": {"kernel": (Lm, I, R + 2 * N)}, "dt_layernorm": {"scale": (Lm, R)},
            "b_layernorm": {"scale": (Lm, N)}, "c_layernorm": {"scale": (Lm, N)},
            "dt_proj": {"kernel": (Lm, R, I)}, "dt_bias": (Lm, I), "A_log": (Lm, N, I),
            "D": (Lm, I), "out_proj": {"kernel": (Lm, I, D)}}
    if La:
        model["attn_layers"] = {
            "input_layernorm": {"scale": (La, D)}, "q_proj": {"kernel": (La, D, q)},
            "k_proj": {"kernel": (La, D, kv)}, "v_proj": {"kernel": (La, D, kv)},
            "o_proj": {"kernel": (La, q, D)}}
    model["ffn"] = {
        "pre_ff_layernorm": {"scale": (L, D)}, "gate_proj": {"kernel": (L, D, F)},
        "up_proj": {"kernel": (L, D, F)}, "down_proj": {"kernel": (L, F, D)}}
    return {"model": model}


# the seeded step of a Mamba layer (Mamba's own defaults; no key of the published config)
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 1e-3, 1e-1, 1e-4


def initializer_of(cfg):
    """A parameter's name → its initializer. The state-space parameters as
    Mamba's own code draws them, so that random weights give a recurrence
    that neither dies nor explodes: ``A_log = log(1 .. N)`` a channel (state
    column ``n`` decays at ``n + 1`` times the step); ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly in ``[TIME_STEP_MIN,
    TIME_STEP_MAX]`` (not under ``TIME_STEP_FLOOR``), so a state remembers
    0.6 to 1000 tokens; ``D`` ones; the convolution uniform in ``+- 1 /
    sqrt(mamba_d_conv)`` (a depth-wise ``Conv1d``'s own)."""
    bound = 1.0 / math.sqrt(cfg.mamba_d_conv)

    def dt_bias(key, shape, dtype=jnp.float32):
        lo, hi = math.log(TIME_STEP_MIN), math.log(TIME_STEP_MAX)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi)),
                         TIME_STEP_FLOOR)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    def a_log(key, shape, dtype=jnp.float32):
        columns = jnp.log(jnp.arange(1, shape[-2] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(columns[:, None], shape).astype(dtype)

    table = {"scale": nn.initializers.ones, "D": nn.initializers.ones, "dt_bias": dt_bias,
             "A_log": a_log, "conv_kernel": _uniform(-bound, bound),
             "conv_bias": _uniform(-bound, bound)}
    return lambda name: table.get(name, nn.initializers.normal(0.02))


class JambaForCausalLM(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        init = initializer_of(self.config)
        params = {name: _Tree(value, init, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_jamba(preset_or_config="jamba-debug", **overrides) -> JambaForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, JambaConfig) \
        else JAMBA_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return JambaForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def reference_mamba(p, x, cfg, state=None, tail=None):
    """One Mamba-1 mixer on whole sequences, the recurrence a token at a
    time: x [B, S, D] (the normalised stream) → (y [B, S, D], the state it
    leaves [B, N, I] - a state column a row, as the parameters' ``A_log`` -,
    the convolution's tail it leaves [B, K - 1, I]: the last rows of the
    mixer's ``x`` before the activation). ``state`` / ``tail``: what the
    sequences carried in (None: a sequence's start, both zero)."""
    p = _f32(p)
    B, S, _ = x.shape
    I, N, R, K = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    eps = cfg.rms_norm_eps
    xz = x @ p["in_proj"]["kernel"]
    xs, z = xz[..., :I], xz[..., I:]
    before = jnp.zeros((B, K - 1, I), jnp.float32) if tail is None else tail.astype(jnp.float32)
    padded = jnp.concatenate([before, xs], axis=1)
    xs = jax.nn.silu(p["conv_bias"] + sum(p["conv_kernel"][j] * padded[:, j:j + S]
                                          for j in range(K)))
    dbc = xs @ p["x_proj"]["kernel"]
    dt = _rms_norm(dbc[..., :R], p["dt_layernorm"]["scale"], eps)
    b = _rms_norm(dbc[..., R:R + N], p["b_layernorm"]["scale"], eps)
    c = _rms_norm(dbc[..., R + N:], p["c_layernorm"]["scale"], eps)
    delta = jax.nn.softplus(dt @ p["dt_proj"]["kernel"] + p["dt_bias"])      # [B, S, I]
    a = -jnp.exp(p["A_log"])                                                # [N, I]

    def one(s, row):
        d_t, x_t, b_t, c_t = row
        s = jnp.exp(d_t[:, None, :] * a) * s + (d_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.einsum("bni,bn->bi", s, c_t)

    start = jnp.zeros((B, N, I), jnp.float32) if state is None else state.astype(jnp.float32)
    last, y = jax.lax.scan(one, start, tuple(jnp.moveaxis(r, 1, 0) for r in (delta, xs, b, c)))
    y = jnp.moveaxis(y, 0, 1) + p["D"] * xs
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"], last, padded[:, S:]


def reference_swiglu(p, x):
    p = _f32(p)
    return (jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])) \
        @ p["down_proj"]["kernel"]


def layer_params(params, cfg, position):
    """→ (the mixer's parameters, the feed-forward's) of the layer at
    ``position`` of the stack, each cut out of its kind's stack."""
    letter = cfg.letters[position]
    i = cfg.letters[:position].count(letter)
    model = params["model"]
    mixer = jax.tree.map(lambda w: w[i],
                         model["mamba_layers" if letter == MAMBA else "attn_layers"])
    return mixer, jax.tree.map(lambda w: w[position], model["ffn"])


def reference_logits(params, input_ids, cfg, positions=None):
    """The plain reference: ids [B, S] → logits [B, S, V] (or, with
    ``positions`` [B, n], the logits at those positions only), float32
    under ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no chunks, no slots, no kernels: the
    convolution as shifted products and the recurrence a token at a time,
    both from a zero start; attention by a mask over all rows
    (``nemotron_h.reference_attention``: the same position-free
    grouped-query attention, read through this config's sizes).

    Departures from the source's modeling file, none of the mathematics:
    weights ``[in, out]``, the mixers of a kind stacked; ``A_log`` ``[N,
    I]``; the convolution as ``[K, I]`` taps; float32 throughout; no
    attention-mask argument, no dropout, no router (one expert)."""
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        embed = params["model"]["embed_tokens"].astype(jnp.float32)
        h = embed[input_ids]
        for position, letter in enumerate(cfg.letters):
            mixer, ffn = layer_params(params, cfg, position)
            x = _rms_norm(h, mixer["input_layernorm"]["scale"].astype(jnp.float32), eps)
            h = h + (reference_mamba(mixer, x, cfg)[0] if letter == MAMBA
                     else reference_attention(mixer, x, cfg))
            x = _rms_norm(h, ffn["pre_ff_layernorm"]["scale"].astype(jnp.float32), eps)
            h = h + reference_swiglu(ffn, x)
        h = _rms_norm(h, params["model"]["final_layernorm"]["scale"].astype(jnp.float32), eps)
        if positions is not None:
            h = jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)
        return h @ embed.T
