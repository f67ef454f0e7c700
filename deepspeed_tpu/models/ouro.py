"""Ouro (``model_type: ouro``; e.g. ``ByteDance/Ouro-2.6B``): a **looped**
("universal") decoder - one stack of ``L`` layers applied ``R =
total_ut_steps`` times **with the same weights**, every pass writing keys
and values of its own, the model's norm after every pass, and a gate that
says after which pass a token's hidden state goes to the head (*Scaling
Latent Reasoning via Looped Language Models*; the equations are the
published ``modeling_ouro.py``'s).

The equations (``D`` hidden, ``H = Hkv`` heads of ``d``, ``F`` intermediate,
``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, ``eps`` = ``rms_norm_eps``;
no bias in a projection)::

    h = E[ids]
    for u in 0 .. R-1:                                   (the same L layers' weights every u)
        for l in 0 .. L-1:
            a = Attn_l(rms(h; n1_l); cache layer u L + l)
            h <- h + rms(a; n2_l)                        (input_layernorm, input_layernorm_2)
            f = rms(h; n3_l);  m = W_down_l(silu(W_gate_l f) * (W_up_l f))
            h <- h + rms(m; n4_l)                        (post_attention_layernorm, .._2)
        h <- rms(h; n_f)                                 (the model's norm after EVERY pass)
        x_u = h;   g_u = sigmoid(x_u w_g + b_g)          (early_exit_gate: Linear(D, 1))
    p_u = g_u prod_{j<u} (1 - g_j)  (u < R-1);   p_{R-1} = prod_{j<R-1} (1 - g_j)
    exit step of a token = the first u with sum_{j<=u} p_j >= early_exit_threshold, else R-1
    logits = x_exit W_head                               (untied)

    Attn_l:  q, k, v [H, d] = x W_q, x W_k, x W_v;  q, k rotated by halves over all d columns
             (theta = rope_theta);  causal softmax(q k / sqrt(d)) v over the keys **that pass
             u wrote** for this layer;  W_o

Every pass writes its own keys and values: a token holds ``R x L`` layers
of cache (the published cache index ``current_ut * num_hidden_layers +
layer_idx``), and pass ``u`` of a later token attends to what pass ``u`` of
the earlier tokens wrote - never to another pass's. All ``R`` passes run for
every token (the threshold selects which ``x_u`` the head reads; it skips no
compute). The paper's cache-sharing variants (one cache for all passes while
decoding) are approximations the published code does not make: not built.

Read from the modelling code and not from ``config.json`` (whose keys name
neither): the block's four norms, the model's norm after every pass, the
gate's bias. Not read: ``max_window_layers``, ``sliding_window`` (no layer
has a window), ``hidden_act`` other than ``silu`` is refused, as is whatever
this file does not run: a ``layer_types`` entry other than
``full_attention``, ``use_sliding_window``, a ``rope_scaling``, tied
embeddings, ``total_ut_steps`` < 1.

Parameter tree (the layers stacked **once**: ``L`` deep, not ``R L``;
matrices ``[in, out]``; the names of the Llama family's tree plus the two
second norms and the gate)::

    model/embed_tokens [V, D]    model/norm/scale [D]    lm_head/kernel [D, V]
    model/early_exit_gate/kernel [D, 1]    model/early_exit_gate/bias [1]
    model/layers/{input_layernorm,input_layernorm_2}/scale [L, D]
    model/layers/{post_attention_layernorm,post_attention_layernorm_2}/scale [L, D]
    model/layers/self_attn/{q,k,v,o}_proj/kernel [L, in, out]
    model/layers/mlp/{gate,up,down}_proj/kernel [L, in, out]

Serving only: ``inference/v2`` runs this model through
``model_runner.OuroKind``; :func:`reference_forward` is the plain float32
forward over whole sequences.
"""

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.moonlight import _Tree, _rms_norm

FULL = "full_attention"


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    hidden_act: str = "silu"
    layer_types: Optional[Tuple[str, ...]] = None       # None: full_attention, one a layer
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    max_position_embeddings: int = 65536
    max_window_layers: int = 48                          # not read
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    sliding_window: Optional[int] = None                 # not read: no layer has a window
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False

    def __post_init__(self):
        types = (FULL,) * self.num_hidden_layers if self.layer_types is None \
            else tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        unsupported = {
            "layer_types (other than full_attention, one a layer)":
                len(types) != self.num_hidden_layers or any(t != FULL for t in types),
            "use_sliding_window": self.use_sliding_window,
            "rope_scaling": self.rope_scaling is not None,
            "tie_word_embeddings": self.tie_word_embeddings,
            "total_ut_steps": self.total_ut_steps < 1,
            "hidden_act": self.hidden_act != "silu",
            "num_key_value_heads": self.num_attention_heads % self.num_key_value_heads != 0,
            "head_dim": self.head_dim % 2 != 0,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"OuroConfig: unsupported setting of {bad}")

    @property
    def state_layers(self):
        """``R x L``: the layers of keys and values a token holds."""
        return self.total_ut_steps * self.num_hidden_layers


OURO_CONFIGS = {
    "ouro-2.6b": OuroConfig(),
    # the published pattern at a size the CPU tests run: four passes over three layers,
    # as many key-value heads as query heads (a query group of one)
    "ouro-debug": OuroConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, head_dim=32, total_ut_steps=4,
        max_position_embeddings=512, max_window_layers=3),
}


def param_shapes(cfg):
    """→ the nested dict of parameter shapes described in the module's docstring."""
    D, F, L, V = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers, cfg.vocab_size
    q, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    layers = {
        **{n: {"scale": (L, D)} for n in ("input_layernorm", "input_layernorm_2",
                                          "post_attention_layernorm",
                                          "post_attention_layernorm_2")},
        "self_attn": {"q_proj": {"kernel": (L, D, q)}, "k_proj": {"kernel": (L, D, kv)},
                      "v_proj": {"kernel": (L, D, kv)}, "o_proj": {"kernel": (L, q, D)}},
        "mlp": {"gate_proj": {"kernel": (L, D, F)}, "up_proj": {"kernel": (L, D, F)},
                "down_proj": {"kernel": (L, F, D)}}}
    return {"model": {"embed_tokens": (V, D), "norm": {"scale": (D,)}, "layers": layers,
                      "early_exit_gate": {"kernel": (D, 1), "bias": (1,)}},
            "lm_head": {"kernel": (D, V)}}


def _initializer(name):
    """Seeded parameters: normal(0.02), the norms 1, the gate's bias 0."""
    return {"scale": nn.initializers.ones, "bias": nn.initializers.zeros}.get(
        name, nn.initializers.normal(0.02))


class OuroForCausalLM(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, input_ids):
        """ids [B, S] → logits [B, S, V], float32: the plain forward."""
        shapes = param_shapes(self.config)
        params = {name: _Tree(value, _initializer, name=name)() for name, value in shapes.items()}
        return reference_logits(params, input_ids, self.config)


def build_ouro(preset_or_config="ouro-debug", **overrides) -> OuroForCausalLM:
    cfg = preset_or_config if isinstance(preset_or_config, OuroConfig) \
        else OURO_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return OuroForCausalLM(cfg)


# ----------------------------------------------------------------------------
# The plain reference
# ----------------------------------------------------------------------------


class Forward(NamedTuple):
    """What :func:`reference_forward` gives, float32: ``logits`` [B, S, V];
    ``passes`` [R, B, S, D], every pass's ``x_u``; ``gates`` [R, B, S], its
    ``g_u``; ``exit_step`` [B, S] int32, the pass whose ``x_u`` the head read."""
    logits: jax.Array
    passes: jax.Array
    gates: jax.Array
    exit_step: jax.Array


def exit_steps(gates, threshold):
    """``gates`` [R, ...] (``g_u``, float32) → the exit step [...] int32: the
    first ``u`` whose cumulative exit probability reaches ``threshold``, else
    ``R - 1``. The last pass takes what the others left (``p_{R-1} = prod (1 -
    g_j)``), so its own gate is not read."""
    R = gates.shape[0]
    if R == 1:
        return jnp.zeros(gates.shape[1:], jnp.int32)
    stay = jnp.cumprod(1.0 - gates[:R - 1], axis=0)                 # prod_{j<=u} (1 - g_j)
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:R - 2]], axis=0)
    reached = jnp.cumsum(gates[:R - 1] * before, axis=0) >= threshold      # [R-1, ...]
    first = jnp.argmax(reached, axis=0)
    return jnp.where(jnp.any(reached, axis=0), first, R - 1).astype(jnp.int32)


def _rope(x, theta):
    """x [B, S, H, d] rotated by halves over all ``d`` columns, position = row."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


LOOP_NORM, SANDWICH_NORMS = "loop_norm", "sandwich_norms"      # reference_forward's leave_out


def reference_layer(lp, h, cfg, sandwich=True):
    """One block on whole sequences: h [B, S, D] → h, with ``lp`` one layer's
    parameters (cut out of the stack), float32. ``sandwich``: False for the
    control that adds a sublayer's output to the residual as computed."""
    lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
    B, S, _ = h.shape
    H, Hkv, d, eps = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                      cfg.rms_norm_eps)
    attn, mlp = lp["self_attn"], lp["mlp"]
    x = _rms_norm(h, lp["input_layernorm"]["scale"], eps)
    q = _rope((x @ attn["q_proj"]["kernel"]).reshape(B, S, H, d), cfg.rope_theta)
    k = _rope((x @ attn["k_proj"]["kernel"]).reshape(B, S, Hkv, d), cfg.rope_theta)
    v = (x @ attn["v_proj"]["kernel"]).reshape(B, S, Hkv, d)
    k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * d) @ attn["o_proj"]["kernel"]
    h = h + (_rms_norm(a, lp["input_layernorm_2"]["scale"], eps) if sandwich else a)
    f = _rms_norm(h, lp["post_attention_layernorm"]["scale"], eps)
    m = (jax.nn.silu(f @ mlp["gate_proj"]["kernel"]) * (f @ mlp["up_proj"]["kernel"])) \
        @ mlp["down_proj"]["kernel"]
    return h + (_rms_norm(m, lp["post_attention_layernorm_2"]["scale"], eps) if sandwich else m)


def reference_forward(params, input_ids, cfg, leave_out=()) -> Forward:
    """The plain reference: ids [B, S] → :class:`Forward`, float32 under
    ``default_matmul_precision("highest")``.

    Whole sequences, no cache, no kernels, no batching tricks: a pass sees
    the keys its own pass computed because it computes them, from the stream
    that entered it. Departures from the source's modeling file, none of the
    mathematics: weights ``[in, out]`` and stacked, float32 throughout, no
    attention-mask argument, no dropout.

    ``leave_out``: the faults of the controls a comparison with this
    reference has to catch (``tests/unit/inference/v2/test_ouro.py``), by
    name: ``LOOP_NORM`` (the model's norm once, after the last pass, as a
    stack run once has it), ``SANDWICH_NORMS`` (no second norm a sublayer)."""
    model = params["model"]
    R, sandwich = cfg.total_ut_steps, SANDWICH_NORMS not in leave_out
    with jax.default_matmul_precision("highest"):
        h = model["embed_tokens"].astype(jnp.float32)[input_ids]
        gate = jax.tree.map(lambda w: w.astype(jnp.float32), model["early_exit_gate"])
        passes, gates = [], []
        for u in range(R):
            for layer in range(cfg.num_hidden_layers):
                h = reference_layer(jax.tree.map(lambda w: w[layer], model["layers"]), h, cfg,
                                    sandwich)
            if LOOP_NORM not in leave_out or u == R - 1:
                h = _rms_norm(h, model["norm"]["scale"].astype(jnp.float32), cfg.rms_norm_eps)
            passes.append(h)
            gates.append(jax.nn.sigmoid((h @ gate["kernel"])[..., 0] + gate["bias"][0]))
        passes, gates = jnp.stack(passes), jnp.stack(gates)
        exit_step = exit_steps(gates, cfg.early_exit_threshold)
        x = jnp.take_along_axis(passes, exit_step[None, ..., None], axis=0)[0]
        return Forward(x @ params["lm_head"]["kernel"].astype(jnp.float32), passes, gates,
                       exit_step)


def reference_logits(params, input_ids, cfg):
    """ids [B, S] → logits [B, S, V] (:func:`reference_forward`'s)."""
    return reference_forward(params, input_ids, cfg).logits
