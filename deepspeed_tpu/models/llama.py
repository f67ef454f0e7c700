"""Llama-family causal decoder, TPU-first.

This is the framework's flagship model: the role the reference fills
with kernel-injected HF models (``deepspeed/module_inject/containers/llama.py``,
``deepspeed/inference/v2/model_implementations/llama_v2/model.py``) is
filled here by a native flax implementation designed for XLA:

- one ``nn.scan`` over identical blocks (single compiled layer body,
  layer-stacked params with a leading L dim — the layout ZeRO-3
  gather-per-layer wants);
- ``nn.remat`` activation checkpointing inside the scan (under ZeRO-3's
  ``overlap_comm`` and full rematerialisation the training scan is
  ``runtime/zero/overlap.py``'s, whose backward gathers a layer once);
- GQA attention with RoPE, RMSNorm, SwiGLU;
- Megatron-style tensor-parallel sharding via :meth:`tp_rule`
  (consumed by ``ZeroShardingPolicy``), Ulysses sequence parallelism
  via sharding re-layouts (``deepspeed_tpu/sequence/layer.py``);
- optional MoE MLP (expert-parallel) per ``moe_num_experts``, with the
  load-balancing aux loss accumulated through the scan carry.

Precision follows the engine: it casts params to the compute dtype
(bf16/fp16/fp32); softmax and the loss always run in fp32.
"""

import dataclasses
from typing import Optional

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.linear.quant_dense import QuantDense
from deepspeed_tpu.moe.sharded_moe import STEP_COUNTS

from deepspeed_tpu.ops.pallas import spec_divides as _spec_divides
from deepspeed_tpu.sequence.layer import (constrain, constrain_hidden, head_to_seq_shard, heads_spec,
                                          hidden_spec, seq_to_head_shard)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # RoPE frequency rescaling (Llama-3.x): "none" | "linear" | "llama3"
    rope_scaling_type: str = "none"
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    # "yarn" (the transformers library's ``_compute_yarn_parameters``) reads the factor and
    # the original length above and these; cos and sin are multiplied by the attention
    # factor (0: ``0.1 ln(factor) + 1``)
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_attention_factor: float = 0.0
    # the kinds of layer (``layer_types``) the rescaling applies to; () = every layer. A
    # layer of another kind rotates by the plain ``rope_theta`` table.
    rope_scaling_kinds: tuple = ()
    # A kind a layer (HF ``layer_types``): "full_attention" | "sliding_attention"; () = every
    # layer alike - windowed if ``sliding_window`` is set (Mistral v0.1), else full. A
    # sliding layer's query attends its ``sliding_window`` newest keys, itself among them.
    # Layers of both kinds are one scanned body that is handed its kind a layer: both
    # attentions are in the program once, and a layer's parameters lie in the one stack.
    layer_types: tuple = ()
    sliding_window: int = 0
    tie_word_embeddings: bool = False
    # Qwen2-style QKV biases (Llama/Mistral/Mixtral: False)
    attention_bias: bool = False
    # InternLM-style o_proj bias (with attention_bias=True: biases on all
    # four attention projections, reference containers/internlm.py)
    attention_out_bias: bool = False
    # Gemma-family knobs: explicit head_dim decoupled from hidden/heads
    # (Gemma-7B: 16 heads x 256 on a 3072 hidden), GeGLU gate activation,
    # and sqrt(hidden) embedding scaling. 0 / "silu" / 1.0 = Llama.
    head_dim_override: int = 0
    mlp_activation: str = "silu"  # "silu" | "gelu_tanh"
    embedding_multiplier: float = 1.0
    attention_impl: str = "auto"  # "auto" | "einsum" | "flash"
    # sequence parallelism: "ulysses" trades seq shards for head shards
    # around local attention (bounded by head count); "ring" keeps the
    # sequence sharded and rotates K/V blocks over the ICI ring
    # (sequence/ring_attention.py) — scales past the head count
    sp_impl: str = "ulysses"  # "ulysses" | "ring"
    remat: bool = True
    # "full" recomputes everything in backward (min memory, ~8N flops);
    # "dots" saves matmul outputs and recomputes elementwise (the usual
    # MFU/memory sweet spot); "moe" saves only the grouped-GEMM
    # residuals so dropless-MoE backward skips re-running the expert
    # GEMMs. Only read when remat=True. (A "save the attention output"
    # variant was measured and removed: the flash kernel is a custom_vjp
    # whose bwd residuals (lse) require re-running the forward anyway,
    # so naming its output saves memory for zero compute —
    # bench-confirmed no-op at MFU 0.538 vs 0.540.)
    remat_policy: str = "full"  # "full" | "dots" | "moe"
    # ZeRO-Infinity param offload: engine sets this when the ds_config
    # has zero_optimization.offload_param — the scanned blocks then
    # stream their layer slice host→HBM (runtime/zero/param_stream.py)
    offload_params: bool = False
    # MoE (0 = dense)
    moe_num_experts: int = 0
    # an expert's width (0 = ``intermediate_size``)
    moe_intermediate_size: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    # False = dropless routing (grouped GEMM; Mixtral-style training)
    moe_drop_tokens: bool = True
    # "" | "Jitter" (multiplicative input noise) | "RSample" (logit noise)
    moe_noisy_gate_policy: str = ""
    # Training CE runs per sequence chunk (remat'd unembed) whenever
    # S > 2*loss_chunk, so the [S, vocab] logits never materialize —
    # the long-context HBM spike. 0 disables chunking.
    loss_chunk: int = 2048

    @property
    def head_dim(self):
        return self.head_dim_override or self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self):
        """``layer_types`` checked, where its layers are not all alike (kinds
        of attention, or a rescaling some kinds rotate by): the stack then
        hands every layer its kind; () where one static kind serves them all."""
        kinds = tuple(self.layer_types)
        if not kinds:
            return ()
        if len(kinds) != self.num_hidden_layers:
            raise ValueError(f"layer_types names {len(kinds)} layers, num_hidden_layers "
                             f"{self.num_hidden_layers}")
        unknown = set(kinds) - {FULL, SLIDING}
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)}: {FULL!r} | {SLIDING!r}")
        if SLIDING in kinds and self.sliding_window < 1:
            raise ValueError("a sliding_attention layer needs sliding_window")
        return kinds if len(set(kinds)) > 1 else ()

    @property
    def uniform_kind(self):
        """The one kind of every layer where ``layer_types`` names one."""
        kinds = tuple(self.layer_types)
        return kinds[0] if kinds and len(set(kinds)) == 1 else None

    def window_of(self, kind):
        """The window of a layer of ``kind`` (None: none)."""
        if kind == SLIDING or (not self.layer_types and self.sliding_window > 0):
            return self.sliding_window
        return None


FULL, SLIDING = "full_attention", "sliding_attention"


# Named presets (tiny ones drive tests/bench; large ones mirror the
# reference's flagship sizes).
LLAMA_CONFIGS = {
    "debug": LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=128),
    "160m": LlamaConfig(vocab_size=32000, hidden_size=768, intermediate_size=2048, num_hidden_layers=12,
                        num_attention_heads=12, num_key_value_heads=12, max_position_embeddings=2048),
    "1b": LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5504, num_hidden_layers=22,
                      num_attention_heads=16, num_key_value_heads=16, max_position_embeddings=4096),
    "7b": LlamaConfig(),
    "13b": LlamaConfig(hidden_size=5120, intermediate_size=13824, num_hidden_layers=40,
                       num_attention_heads=40, num_key_value_heads=40),
    "70b": LlamaConfig(hidden_size=8192, intermediate_size=28672, num_hidden_layers=80,
                       num_attention_heads=64, num_key_value_heads=8),
    # Llama-family presets (the reference's inference-v2 model zoo —
    # mistral/mixtral/qwen2 are Llama-architecture with GQA / MoE; the
    # debug-scale variants exercise the same code paths in tests):
    "mistral-7b": LlamaConfig(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                              num_hidden_layers=32, num_attention_heads=32,
                              num_key_value_heads=8, max_position_embeddings=32768,
                              rope_theta=1e6),
    "mixtral-8x7b": LlamaConfig(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                                num_hidden_layers=32, num_attention_heads=32,
                                num_key_value_heads=8, max_position_embeddings=32768,
                                rope_theta=1e6, moe_num_experts=8, moe_top_k=2),
    "qwen2-7b": LlamaConfig(vocab_size=152064, hidden_size=3584, intermediate_size=18944,
                            num_hidden_layers=28, num_attention_heads=28,
                            num_key_value_heads=4, max_position_embeddings=32768,
                            rope_theta=1e6, attention_bias=True),
    "mixtral-debug": LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                                 num_hidden_layers=2, num_attention_heads=4,
                                 num_key_value_heads=2, max_position_embeddings=128,
                                 moe_num_experts=4, moe_top_k=2),
}


def _remat_policy(name: str):
    cp = jax.checkpoint_policies
    if name == "dots":
        return cp.dots_saveable
    if name == "full":
        return cp.nothing_saveable
    if name == "moe":
        # Dropless-MoE sweet spot: save ONLY the grouped-GEMM residuals
        # (sorted rows + gate/up activations, tagged in
        # ops/grouped_gemm.py) so the backward never re-runs the expert
        # GEMMs — the single biggest recompute under 'full' — while
        # attention and everything elementwise still remat. ~3*T*k rows
        # of extra HBM per layer vs a ~25% cut of expert-GEMM time.
        return cp.save_only_these_names("moe_xs", "moe_gate", "moe_up",
                                        "moe_routing", "moe_tiles")
    raise ValueError(f"unknown remat_policy {name!r}: expected 'full', 'dots' or 'moe'")


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        from deepspeed_tpu.ops.pallas import fused_rms_norm, kernel_dispatch, shard_map_kernel
        from deepspeed_tpu.parallel import groups
        mesh = groups.get_mesh(required=False)
        # Pallas kernel on TPU, identical-math XLA elsewhere. Under a
        # multi-device mesh the kernel must run per-shard (pallas_call
        # has no GSPMD rule), so wrap it in shard_map on the canonical
        # [B, S, D] layout — the norm axis is never sharded.
        if kernel_dispatch(mesh) == "shard_map" and x.ndim == 3 \
                and _spec_divides(mesh, hidden_spec(mesh), x.shape):
            spec = hidden_spec(mesh)
            eps = self.eps
            return shard_map_kernel(lambda xs, sc: fused_rms_norm(xs, sc, eps),
                                    mesh, (spec, P(None)), spec)(x, scale)
        return fused_rms_norm(x, scale, self.eps)


def rope_frequencies(head_dim: int, max_len: int, theta: float, scaling=None):
    """cos/sin tables [T, D/2]. ``scaling``: None, ("linear", factor),
    ("llama3", factor, low_freq_factor, high_freq_factor, orig_max) —
    the Llama-3.x wavelength-dependent inv_freq rescale (long wavelengths
    divided by ``factor``, short kept, smooth ramp between) — or ("yarn",
    factor, orig_max, beta_fast, beta_slow, attention_factor): YaRN's
    frequencies (``models/laguna.yarn_inv_freq``), cos and sin both times
    the attention factor."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    gain = 1.0
    if scaling is not None and scaling[0] != "none":
        kind = scaling[0]
        if kind == "yarn":
            from deepspeed_tpu.models.laguna import yarn_inv_freq
            _, factor, orig_max, beta_fast, beta_slow, gain = scaling
            inv_freq = yarn_inv_freq(head_dim, theta, factor, orig_max, beta_fast, beta_slow)
        elif kind == "linear":
            inv_freq = inv_freq / scaling[1]
        elif kind == "llama3":
            _, factor, low_f, high_f, orig_max = scaling
            wavelen = 2.0 * np.pi / inv_freq
            low_wl = orig_max / low_f
            high_wl = orig_max / high_f
            scaled = np.where(wavelen > low_wl, inv_freq / factor, inv_freq)
            smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
            mid = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
            inv_freq = np.where((wavelen <= low_wl) & (wavelen >= high_wl), mid, scaled)
        else:
            raise ValueError(f"unknown rope scaling {kind!r}")
    t = np.arange(max_len, dtype=np.float32)
    freqs = np.outer(t, inv_freq)  # [T, D/2]
    if gain != 1.0:
        return (np.cos(freqs) * gain).astype(np.float32), (np.sin(freqs) * gain).astype(np.float32)
    return np.cos(freqs), np.sin(freqs)


def rope_scaling_of(cfg, layer_kind=None):
    """Config → the ``scaling`` tuple ``rope_frequencies`` takes, for a
    layer of ``layer_kind`` where the config rescales some kinds only."""
    kind = getattr(cfg, "rope_scaling_type", "none")
    kinds = getattr(cfg, "rope_scaling_kinds", ())
    if kind == "none" or (kinds and layer_kind not in kinds):
        return None
    if kind == "yarn":
        import math
        gain = cfg.rope_attention_factor or 0.1 * math.log(cfg.rope_scaling_factor) + 1.0
        return ("yarn", cfg.rope_scaling_factor, cfg.rope_original_max_position,
                cfg.rope_yarn_beta_fast, cfg.rope_yarn_beta_slow, gain)
    if kind == "linear":
        return ("linear", cfg.rope_scaling_factor)
    if kind == "llama3":
        return ("llama3", cfg.rope_scaling_factor, cfg.rope_low_freq_factor,
                cfg.rope_high_freq_factor, cfg.rope_original_max_position)
    raise ValueError(f"unknown rope_scaling_type {kind!r}: expected 'none', 'linear', "
                     f"'llama3' or 'yarn'")


def apply_rope(x, cos, sin, positions):
    """x: [B, S, H, D]; cos/sin: [T, D/2]; positions: [B or 1, S]."""
    cos = jnp.asarray(cos)[positions][:, :, None, :]  # [B, S, 1, D/2]
    sin = jnp.asarray(sin)[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def repeat_kv(k, v, n_rep: int):
    """GQA head expansion on [.., S, Hkv, D] K/V (shared by every
    attention path; no-op when n_rep == 1)."""
    if n_rep == 1:
        return k, v
    return jnp.repeat(k, n_rep, axis=-2), jnp.repeat(v, n_rep, axis=-2)


def einsum_attention(q, k, v, causal=True, bias=None, mask=None, window=None):
    """Reference attention: [B, S, H, D] → [B, S, H, D]; softmax in fp32.

    ``mask``: optional [.., Sq, Sk] bool (True = attend), e.g. the
    KV-cache validity mask during decode; overrides ``causal``.
    ``window`` (with ``causal``): the newest keys a query attends.
    """
    dtype = q.dtype
    scale = 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        scores = scores + bias
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    elif causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            cmask = cmask & ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        scores = jnp.where(cmask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _local_attention(q, k, v, impl: str, causal=True, window=None):
    """``impl``: "einsum", "flash" (a pin: the Pallas kernels run or this
    raises — never the XLA reference under the kernel's name), or "auto"
    (the kernels where they can run and pay off, else einsum). ``window``:
    the newest keys a query attends (None: all before it)."""
    from deepspeed_tpu.ops.pallas import kernel_dispatch, shard_map_kernel
    from deepspeed_tpu.parallel import groups
    mesh = groups.get_mesh(required=False)
    mode = kernel_dispatch(mesh)
    if mode == "shard_map" and not _spec_divides(mesh, heads_spec(mesh), q.shape):
        mode = "xla"
    if impl == "auto":
        # The Pallas kernel wins once the [S, S] score matrix dominates;
        # tiny test shapes stay on the fused-by-XLA einsum path.
        impl = "flash" if mode != "xla" and q.shape[1] >= 256 else "einsum"
    elif impl == "flash" and mode == "xla":
        raise ValueError(
            f"attention_impl='flash' is pinned but the Pallas kernel cannot run here: "
            f"backend={jax.default_backend()!r}, mesh={mesh and dict(mesh.shape)}, "
            f"q{tuple(q.shape)} (kernels need a TPU backend or DS_PALLAS=1, no enclosing "
            f"manual shard_map, and batch/heads that divide the mesh)")
    if impl == "flash":
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        if window is None:
            attend = lambda a, b, c: flash_attention(a, b, c, causal=causal, force_pallas=True)
        else:
            attend = lambda a, b, c: flash_attention(a, b, c, causal=causal, force_pallas=True,
                                                     window=window)
        if mode == "shard_map":
            # Run the kernel per-shard on the post-Ulysses layout (full
            # sequence, head-sharded) — causal masking is shard-local.
            spec = heads_spec(mesh)
            return shard_map_kernel(attend, mesh, (spec, spec, spec), spec)(q, k, v)
        return attend(q, k, v)
    return einsum_attention(q, k, v, causal=causal, window=window)


class LlamaAttention(nn.Module):
    config: LlamaConfig
    kind: Optional[str] = None      # the layer's kind, where it is known as the module is built

    @nn.compact
    def __call__(self, h, positions, layer_cache=None, sliding=None):
        """Training: ``layer_cache=None`` → causal self-attention with the
        Ulysses seq↔head exchange. Decode: ``layer_cache`` is this
        layer's ``{'k','v'}`` [B, S_max, Hkv, D] KV cache and
        ``positions`` [1 or B, T] the absolute write positions; returns
        ``(out, new_layer_cache)`` (equivalent of the reference's
        softmax_context KV-cache kernels, csrc/transformer/inference).
        ``sliding`` (a traced bool; training only): the layer's kind where the
        stack's layers are of both kinds - its table of positions is picked
        by it and its attention is a ``lax.cond`` of the two."""
        cfg = self.config
        B, S, D = h.shape
        H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

        qkv_bias = cfg.attention_bias
        q = QuantDense(H * Dh, use_bias=qkv_bias, name="q_proj")(h).reshape(B, S, H, Dh)
        k = QuantDense(Hkv * Dh, use_bias=qkv_bias, name="k_proj")(h).reshape(B, S, Hkv, Dh)
        v = QuantDense(Hkv * Dh, use_bias=qkv_bias, name="v_proj")(h).reshape(B, S, Hkv, Dh)

        kind = self.kind or cfg.uniform_kind
        cos, sin = rope_frequencies(Dh, cfg.max_position_embeddings, cfg.rope_theta,
                                    scaling=rope_scaling_of(cfg, FULL if sliding is not None else kind))
        if sliding is not None and rope_scaling_of(cfg, SLIDING) != rope_scaling_of(cfg, FULL):
            other = rope_frequencies(Dh, cfg.max_position_embeddings, cfg.rope_theta,
                                     scaling=rope_scaling_of(cfg, SLIDING))
            cos, sin = (jnp.where(sliding, jnp.asarray(o), jnp.asarray(t))
                        for o, t in zip(other, (cos, sin)))
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        window = cfg.window_of(kind)

        if layer_cache is not None:
            start = positions[0, 0]
            k_full = jax.lax.dynamic_update_slice(layer_cache["k"], k.astype(layer_cache["k"].dtype),
                                                  (0, start, 0, 0))
            v_full = jax.lax.dynamic_update_slice(layer_cache["v"], v.astype(layer_cache["v"].dtype),
                                                  (0, start, 0, 0))
            new_cache = {"k": k_full, "v": v_full}
            kx, vx = repeat_kv(k_full, v_full, H // Hkv)
            # token t may attend to cache positions <= start + t
            s_max = kx.shape[1]
            k_idx = jnp.arange(s_max)[None, :]
            q_pos = (start + jnp.arange(S))[:, None]
            mask = (k_idx <= q_pos)[None, None, :, :]  # [1, 1, T, S_max]
            if window is not None:
                mask = mask & (k_idx > q_pos - window)[None, None, :, :]
            out = einsum_attention(q, kx, vx, mask=mask)
            out = out.reshape(B, S, H * Dh)
            return QuantDense(D, use_bias=cfg.attention_out_bias, name="o_proj")(out), new_cache

        if sliding is not None and (layer_cache is not None or cfg.sp_impl != "ulysses"):
            raise NotImplementedError("layers of both kinds: the training forward under "
                                      "sp_impl='ulysses' only")
        if cfg.sp_impl == "ring" and window is not None:
            raise NotImplementedError("a sliding window under ring attention (sp_impl='ring')")
        if cfg.sp_impl == "ring":
            # Ring context parallelism: stay sequence-sharded; K/V blocks
            # rotate over the 'sequence' axis (no seq↔head exchange).
            # GQA K/V travel the ring unexpanded (H/Hkv less traffic).
            from deepspeed_tpu.sequence.ring_attention import ring_attention
            out = ring_attention(q, k, v, causal=True, impl=cfg.attention_impl)
        elif cfg.sp_impl == "ulysses":
            # GQA: expand kv heads to match q heads
            k, v = repeat_kv(k, v, H // Hkv)
            # Ulysses: trade sequence shard for head shard around local attention
            q = seq_to_head_shard(q)
            k = seq_to_head_shard(k)
            v = seq_to_head_shard(v)
            def attend(window):
                def run(q, k, v):
                    with jax.named_scope("ds.train.attn_full" if window is None
                                         else "ds.train.attn_window"):
                        return _local_attention(q, k, v, cfg.attention_impl, causal=True,
                                                window=window)
                return run
            if sliding is None:
                out = attend(window)(q, k, v)
            else:
                out = jax.lax.cond(sliding, attend(cfg.sliding_window), attend(None), q, k, v)
            out = head_to_seq_shard(out)
        else:
            raise ValueError(f"unknown sp_impl {cfg.sp_impl!r}: expected 'ulysses' or 'ring'")

        out = out.reshape(B, S, H * Dh)
        return QuantDense(D, use_bias=cfg.attention_out_bias, name="o_proj")(out), None


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        gate = QuantDense(cfg.intermediate_size, use_bias=False, name="gate_proj")(h)
        up = QuantDense(cfg.intermediate_size, use_bias=False, name="up_proj")(h)
        if cfg.mlp_activation == "silu":
            inter = nn.silu(gate) * up
        elif cfg.mlp_activation == "gelu_tanh":  # Gemma GeGLU
            inter = nn.gelu(gate, approximate=True) * up
        else:
            raise ValueError(f"mlp_activation {cfg.mlp_activation!r}: silu | gelu_tanh")
        inter = constrain(inter, (("data", "expert"), "sequence", "tensor"))
        return QuantDense(cfg.hidden_size, use_bias=False, name="down_proj")(inter)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    kind: Optional[str] = None

    @nn.compact
    def __call__(self, carry, positions, layer_cache=None, sliding=None):
        h, aux_loss = carry
        cfg = self.config
        decode = layer_cache is not None
        attn_in = RMSNorm(eps=cfg.rms_norm_eps, name="input_layernorm")(h)
        attn_out, new_cache = LlamaAttention(cfg, kind=self.kind, name="self_attn")(
            attn_in, positions, layer_cache, sliding)
        h = h + attn_out
        if not decode:
            h = constrain_hidden(h)
        mlp_in = RMSNorm(eps=cfg.rms_norm_eps, name="post_attention_layernorm")(h)
        if cfg.moe_num_experts > 0:
            from deepspeed_tpu.moe.layer import MoE
            mlp_out, layer_aux = MoE(hidden_size=cfg.hidden_size,
                                     intermediate_size=(cfg.moe_intermediate_size
                                                        or cfg.intermediate_size),
                                     num_experts=cfg.moe_num_experts,
                                     k=cfg.moe_top_k,
                                     capacity_factor=cfg.moe_capacity_factor,
                                     drop_tokens=cfg.moe_drop_tokens,
                                     noisy_gate_policy=cfg.moe_noisy_gate_policy,
                                     name="moe_mlp")(mlp_in)
            h = h + mlp_out
            aux_loss = aux_loss + layer_aux
        else:
            h = h + LlamaMLP(cfg, name="mlp")(mlp_in)
        if not decode:
            h = constrain_hidden(h)
        return (h, aux_loss), new_cache


class LlamaModel(nn.Module):
    """Decoder trunk: embeddings + scanned blocks + final norm."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, start_pos=0):
        cfg = self.config
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size))
        # ZeRO-3 shards the table's D dim over the zero axes; re-gather it
        # before the lookup (the explicit form of ZeRO-3's pre-op
        # all-gather) so the gather's output needs only a cheap
        # dynamic-slice to reach the hidden layout — without this, XLA
        # resorts to an involuntary full rematerialization of the
        # activation on every step.
        embed = constrain(embed, ("tensor", None))
        h = jnp.take(embed, input_ids, axis=0)
        if cfg.embedding_multiplier != 1.0:  # Gemma: sqrt(hidden_size)
            h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
        decode = cache is not None
        if not decode:
            h = constrain_hidden(h)
        positions = (start_pos + jnp.arange(input_ids.shape[1]))[None, :]

        kinds = cfg.layer_kinds
        if kinds and (decode or cfg.offload_params):
            raise NotImplementedError("layer_types of several kinds: the training forward only "
                                      "(no decode cache, no streamed parameters)")
        block = LlamaBlock
        if cfg.offload_params:
            # Training: inside remat, so the host→device copies are
            # recomputed in the backward instead of saved (saving them
            # would pin every layer's device copy until its backward
            # runs). Decode (hybrid-engine generate): same streaming per
            # decode step — ZeRO-Inference semantics.
            from deepspeed_tpu.runtime.zero.param_stream import wrap_streaming_block
            block = wrap_streaming_block(block, llama_tp_rule, self.is_initializing())
        if cfg.remat and not decode:
            policy = _remat_policy(cfg.remat_policy)
            block = nn.remat(block, prevent_cse=False, policy=policy)
        carry0 = (h, jnp.zeros((), jnp.float32))
        overlapped = None if decode or kinds or self.is_initializing() \
            else self._overlapped_layers(carry0, positions)
        if kinds:
            # one body for both kinds, handed its kind a layer: an iteration is a layer, so a
            # layer's recomputation and its residuals live for that layer's backward alone
            ScanBlocks = nn.scan(block,
                                 variable_axes={"params": 0, STEP_COUNTS: 0},
                                 split_rngs={"params": True, "dropout": True},
                                 in_axes=(nn.broadcast, nn.broadcast, 0),
                                 length=cfg.num_hidden_layers,
                                 metadata_params={nn.PARTITION_NAME: "layers"})
            (h, aux_loss), new_cache = ScanBlocks(cfg, name="layers")(
                carry0, positions, None, jnp.asarray([k == SLIDING for k in kinds]))
        elif overlapped is not None:
            (h, aux_loss), new_cache = overlapped, None
        elif decode:
            # cache leaves carry a leading L dim and scan over layers
            # threads each layer's slice through as scanned input/output.
            ScanBlocks = nn.scan(block,
                                 variable_axes={"params": 0},
                                 split_rngs={"params": True, "dropout": True},
                                 in_axes=(nn.broadcast, 0),
                                 out_axes=0,
                                 length=cfg.num_hidden_layers,
                                 metadata_params={nn.PARTITION_NAME: "layers"})
            (h, aux_loss), new_cache = ScanBlocks(cfg, name="layers")(carry0, positions, cache)
        else:
            ScanBlocks = nn.scan(block,
                                 variable_axes={"params": 0, STEP_COUNTS: 0},
                                 split_rngs={"params": True, "dropout": True},
                                 in_axes=nn.broadcast,
                                 length=cfg.num_hidden_layers,
                                 metadata_params={nn.PARTITION_NAME: "layers"})
            (h, aux_loss), new_cache = ScanBlocks(cfg, name="layers")(carry0, positions)
        h = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(h)
        return h, embed, aux_loss, new_cache

    def _overlapped_layers(self, carry0, positions):
        """The training scan under ``zero_optimization.overlap_comm`` at ZeRO
        stage 3 (``runtime/zero/overlap.py``): its backward gathers a layer
        once, whole, for recomputation and differentiation alike. None - and
        the caller runs the plain scan - unless the engine asked for it, the
        stack is sharded over a zero axis, its layers come from HBM and are
        recomputed in full: the overlapped scan saves a layer's input and
        nothing else, which is what ``remat_policy="full"`` states."""
        from deepspeed_tpu.runtime.zero import overlap
        cfg = self.config
        asked = overlap.active()
        if asked is None or cfg.offload_params or not (cfg.remat and cfg.remat_policy == "full"):
            return None
        stacked = self.variables["params"]["layers"]
        path = "/".join(self.scope.path + ("layers",))
        layouts = asked.layouts(path, stacked)
        if layouts is None:
            return None
        block = LlamaBlock(cfg, parent=None)
        keys = None
        if self.has_rng("dropout"):
            keys = jax.random.split(self.make_rng("dropout"), cfg.num_hidden_layers)

        def layer(params, carry, positions, key):
            rngs = None if key is None else {"dropout": key}
            return block.apply({"params": params}, carry, positions, rngs=rngs)[0]

        asked.scans[path] = cfg.num_hidden_layers  # the backward's gathers
        return overlap.overlapped_scan(layer, stacked, carry0, positions, keys, *layouts)


class LlamaForCausalLM(nn.Module):
    """Causal LM with internal next-token shift.

    ``__call__(input_ids, labels)`` → ``(loss, logits)``;
    ``__call__(input_ids)`` → ``logits``; ``__call__(input_ids, labels,
    per_position=True)`` → ``(nll [B, S - 1] float32, None)``: each position's
    next-token term of the loss before its mean (no load-balancing part), by
    the loss's own path. Positions with label -100 are
    ignored (HF convention). For sequences longer than
    ``2 * config.loss_chunk`` the loss is computed chunk-wise and the
    second element is **None** — the full [B, S, vocab] logits are never
    materialized (the long-context HBM spike).
    """
    config: LlamaConfig

    # Subtree the engine may place in pinned_host when offload_param is
    # on (the scanned blocks stream these leaves themselves).
    param_stream_prefix = "model/layers/"

    @nn.compact
    def __call__(self, input_ids, labels=None, cache=None, start_pos=0, per_position=False):
        cfg = self.config
        decode = cache is not None
        h, embed, aux_loss, new_cache = LlamaModel(cfg, name="model")(input_ids, cache=cache,
                                                                      start_pos=start_pos)
        S = input_ids.shape[1]
        chunked = (labels is not None and not decode and cfg.loss_chunk > 0
                   and S > 2 * cfg.loss_chunk)
        if per_position:
            if labels is None or decode or cfg.tie_word_embeddings:
                raise ValueError("per_position: the training loss of an untied head")
            return self._position_nll(cfg, h, labels, cfg.loss_chunk if chunked else S), None
        if not chunked:
            if cfg.tie_word_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", h, embed.astype(h.dtype))
            else:
                logits = QuantDense(cfg.vocab_size, use_bias=False, name="lm_head")(h)
            if decode:
                return logits, new_cache
            logits = constrain(logits, (("data", "expert"), "sequence", "tensor"))
            if labels is None:
                return logits
            loss = causal_lm_loss(logits, labels)
        else:
            # Long-sequence loss: the full [B, S, V] logits (fp32 logp is
            # S·V·4 bytes — 4.2 GB at 32k·32000, THE long-context HBM
            # spike) are never materialized; the unembed + CE run per
            # sequence chunk under remat, so backward recomputes one
            # chunk's logits at a time.
            loss = self._chunked_causal_loss(cfg, h, embed, labels)
            logits = None
        if cfg.moe_num_experts > 0:
            loss = loss + cfg.moe_aux_loss_coef * aux_loss / cfg.num_hidden_layers
        return loss, logits

    def _position_nll(self, cfg, h, labels, C):
        """The loss's terms, a position each, ``C`` positions at a time."""
        hs, ls = h[:, :-1], labels[:, 1:]
        lm_head = QuantDense(cfg.vocab_size, use_bias=False, name="lm_head")
        out = []
        for i in range(0, hs.shape[1], C):
            logits = constrain(lm_head(hs[:, i:i + C]), (("data", "expert"), None, "tensor"))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            tgt = ls[:, i:i + C].astype(jnp.int32)
            nll = -jnp.take_along_axis(logp, jnp.maximum(tgt, 0)[..., None], axis=-1)[..., 0]
            out.append(jnp.where(tgt != -100, nll, 0.0))
        return jnp.concatenate(out, axis=1)

    def _chunked_causal_loss(self, cfg, h, embed, labels):
        C = cfg.loss_chunk
        hs, ls = h[:, :-1], labels[:, 1:]
        pad = (-hs.shape[1]) % C
        if pad:
            hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
            ls = jnp.pad(ls, ((0, 0), (0, pad)), constant_values=-100)
        n = hs.shape[1] // C
        total = jnp.zeros((), jnp.float32)
        count = jnp.zeros((), jnp.int32)
        if cfg.tie_word_embeddings:
            step = jax.checkpoint(lambda hc, lc: _ce_chunk_stats(
                constrain(jnp.einsum("bsd,vd->bsv", hc, embed.astype(hc.dtype)),
                          (("data", "expert"), None, "tensor")), lc))
            for i in range(n):
                s, c = step(hs[:, i * C:(i + 1) * C], ls[:, i * C:(i + 1) * C])
                total, count = total + s, count + c
        else:
            lm_head = QuantDense(cfg.vocab_size, use_bias=False, name="lm_head")
            step = nn.remat(_dense_ce_chunk, prevent_cse=False)
            for i in range(n):
                s, c = step(lm_head, hs[:, i * C:(i + 1) * C], ls[:, i * C:(i + 1) * C])
                total, count = total + s, count + c
        return total / jnp.maximum(count, 1).astype(jnp.float32)

    def step_count_names(self, mesh):
        """What the forward counts on the device for the trainer's step record
        under ``mesh`` (``moe/sharded_moe.py``): the expert exchange's rows."""
        from deepspeed_tpu.moe.sharded_moe import exchange_count_names
        cfg = self.config
        if cfg.moe_num_experts > 0 and not cfg.moe_drop_tokens:
            return exchange_count_names(mesh)
        return ()

    def tp_rule(self, path: str, shape) -> P:
        """Megatron-style tensor sharding (consumed by ZeroShardingPolicy).

        Paths carry the scan dim first for scanned layers, e.g.
        ``model/layers/self_attn/q_proj/kernel`` with shape (L, D, H*Dh).
        """
        return llama_tp_rule(path, shape)


def llama_tp_rule(path: str, shape) -> P:
    lead = [None] * (len(shape) - 2)  # scan L dim (and any extras) unsharded
    # Stacked MoE expert tensors: (L, E, D, I)/(L, E, I, D) — expert dim
    # over the 'expert' axis, features Megatron-style over 'tensor'.
    if "experts_w" in path:
        elead = [None] * (len(shape) - 3)
        if "experts_w2" in path:
            return P(*elead, "expert", "tensor", None)
        return P(*elead, "expert", None, "tensor")
    if any(k in path for k in ("q_proj/kernel", "k_proj/kernel", "v_proj/kernel",
                               "gate_proj/kernel", "up_proj/kernel")):
        return P(*lead, None, "tensor")  # column parallel: shard output features
    if any(k in path for k in ("o_proj/kernel", "down_proj/kernel")):
        return P(*lead, "tensor", None)  # row parallel: shard input features
    if "embed_tokens" in path:
        return P("tensor", None)  # vocab-sharded embedding
    if "lm_head/kernel" in path:
        return P(None, "tensor")
    return P()  # norms, biases, gates replicated


def _ce_chunk_stats(logits, targets):
    """(masked nll sum fp32, valid-token count) for one loss chunk."""
    logits = logits.astype(jnp.float32)
    targets = targets.astype(jnp.int32)
    mask = targets != -100
    safe = jnp.where(mask, targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.where(mask, nll, 0.0).sum(), mask.sum()


def _dense_ce_chunk(lm_head, hc, lc):
    """nn.remat-able chunk step for the untied lm_head path. The chunk
    logits keep the vocab-sharded layout of the full path (the fp32
    log-probs are the buffer the chunking exists to bound)."""
    logits = constrain(lm_head(hc), (("data", "expert"), None, "tensor"))
    return _ce_chunk_stats(logits, lc)


def masked_cross_entropy(logits, targets):
    """Mean token cross entropy in fp32; positions with target -100 are
    ignored (HF convention). Shared by the causal and MLM heads."""
    s, c = _ce_chunk_stats(logits, targets)
    return s / jnp.maximum(c, 1).astype(jnp.float32)


def causal_lm_loss(logits, labels):
    """Next-token cross entropy with -100 ignore mask, fp32."""
    return masked_cross_entropy(logits[:, :-1], labels[:, 1:])


def init_cache(config: LlamaConfig, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    """Allocate the static-shape KV cache: leaves [L, B, S_max, Hkv, D]
    (the TPU analogue of the reference's inference-context workspace,
    csrc/includes/inference_context.h)."""
    shape = (config.num_hidden_layers, batch_size, max_len,
             config.num_key_value_heads, config.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def build_llama(preset_or_config="debug", **overrides) -> LlamaForCausalLM:
    if isinstance(preset_or_config, LlamaConfig):
        cfg = preset_or_config
    else:
        cfg = LLAMA_CONFIGS[preset_or_config]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return LlamaForCausalLM(cfg)
