"""Ragged model execution: flat token batches against a paged KV cache.

Capability match for the reference's v2 model implementations
(``deepspeed/inference/v2/model_implementations/`` — llama_v2, mistral,
mixtral, qwen, falcon, opt, phi — over the ragged kernels in
``deepspeed/inference/v2/kernels/ragged_ops/``: linear_blocked_kv_rotary,
atom-based blocked attention). TPU redesign: one jitted function
consumes the padded flat batch —

- tokens are a flat ``[T]`` buffer with per-token (slot, position);
- the block pool ``[L, NB, bs, Hkv*Dh]`` is the CARRY of the layer scan:
  layer ``l`` scatters its new K/V rows into the whole pool at
  ``(l, block_tables[slot, pos // bs], pos % bs)`` and attends by
  reading the sequence's block table out of ``pool[l]`` (masked to
  ``pos``), which handles mixed prefill chunks + decodes in ONE program
  — the Dynamic SplitFuse execution model. The pool is never sliced,
  stacked or reshaped, so with the callers' donation the write is in
  place from the program's argument to its result;
- the layer stack is ``lax.scan`` over the model's stacked scan params
  (the scan's ``xs``: what is read-only per layer), so any
  ``LlamaForCausalLM`` (Llama/Mistral/Mixtral/Qwen2) or
  ``GPTForCausalLM`` (GPT-2/J/NeoX, OPT, Bloom, Falcon, Phi) checkpoint
  serves directly.
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import LlamaConfig, rope_frequencies, rope_scaling_of


def _c(x, entries, mesh):
    """Sharding constraint with dead-axis/divisibility fallback; no-op
    when serving single-device (mesh None). These pin the Megatron
    layout through the ragged step: replicated token batch, head- and
    feature-sharded projections (reference
    ``inference/v2/model_implementations/sharding/``)."""
    if mesh is None:
        return x
    from deepspeed_tpu.inference.v2.sharding import live_entries
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*live_entries(mesh, entries, x.shape))))


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _layernorm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _proj(x, p):
    """Dense apply from raw params (kernel + optional bias, e.g. Qwen2's
    QKV biases or the GPT family's biased projections). A QuantizedWeight
    kernel routes through the fused dequant-matmul — the bf16 matrix is
    never materialized, not even for this one layer slice."""
    from deepspeed_tpu.inference.quantization import matmul_any
    y = matmul_any(x, p["kernel"], dtype=x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def _rope_flat(x, cos, sin, positions):
    """x: [T, H, D]; cos/sin tables [maxlen, D/2]; positions [T]."""
    c = cos[positions][:, None, :]
    s = sin[positions][:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _rope_flat_interleaved(x, cos, sin, positions):
    """GPT-J layout: adjacent dim pairs rotate together."""
    c = cos[positions][:, None, :]
    s = sin[positions][:, None, :]
    x32 = x.astype(jnp.float32)
    x1 = x32[..., 0::2]
    x2 = x32[..., 1::2]
    out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _c_pool(x, n_kv_heads, mesh):
    """Pin the pool ``[L, NB, bs, Hkv*Dh]`` to the layout it is stored in
    (whole KV heads over 'tensor', ``sharding.kv_pool_spec``)."""
    if mesh is None:
        return x
    from deepspeed_tpu.inference.v2.sharding import kv_pool_spec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, kv_pool_spec(mesh, n_kv_heads)))


def _paged_attend(q, k, v, kc, vc, layer, batch, Dh, alibi=None, mesh=None, impl=None):
    """Scatter layer ``layer``'s new K/V rows into the paged pool (one
    scatter into the whole pool, which comes back as the same buffers)
    and attend over each token's block-tabled context. The attention
    implementation comes from the ``modules/heuristics`` registry
    (Pallas decode kernel single-device or per-TP-shard, XLA gather
    fallback / ALiBi path), optionally pinned by the engine config's
    ``implementation_overrides``; ``impl`` is the engine's
    :class:`AttentionChoice`, which carries the pin in and the selected
    implementation's name out."""
    bs = kc.shape[2]
    T, Hkv = k.shape[:2]
    blk = batch["block_tables"][batch["token_seq"], batch["token_pos"] // bs]  # [T]
    off = batch["token_pos"] % bs
    kc = _c_pool(kc.at[layer, blk, off].set(k.reshape(T, -1).astype(kc.dtype)), Hkv, mesh)
    vc = _c_pool(vc.at[layer, blk, off].set(v.reshape(T, -1).astype(vc.dtype)), Hkv, mesh)

    from deepspeed_tpu.inference.v2.modules.heuristics import instantiate_attn
    tab = batch["block_tables"][batch["token_seq"]]  # [T, MB]
    pos = batch["token_pos"]
    name, attn_fn = instantiate_attn(mesh, Dh, bs, q.shape, kc.shape, alibi,
                                     max_blocks=tab.shape[1],
                                     override=impl.override if impl else None)
    if impl is not None:
        impl.selected[q.shape[0]] = name
    out = attn_fn(q, kc, vc, tab, pos, layer)
    return _c(out, (None, "tensor", None), mesh), kc, vc


def _layer_step(cfg, cos, sin, batch, mesh, attn_impl, lora_ctx, carry, xs):
    h, kc, vc = carry
    if lora_ctx is None:
        layer, lp = xs

        def lproj(x, p, site):
            return _proj(x, p)
    else:
        # Multi-tenant LoRA: the scan sliced this layer's stacked hot
        # slabs alongside the params; each targeted projection adds the
        # segmented per-token adapter delta (slot 0 = base = exact 0.0).
        layer, lp, la, lb = xs
        slots, scales, lora_impl = lora_ctx
        from deepspeed_tpu.ops.pallas.lora_matmul import apply_lora_delta

        def lproj(x, p, site):
            y = _proj(x, p)
            if site in la:
                y = y + apply_lora_delta(x, slots, la[site], lb[site],
                                         scales, impl=lora_impl)
            return y
    # Weight-only quantized serving: the scan sliced this layer's
    # quantized carriers; they stay quantized here and every projection
    # consumes them through the fused dequant-matmul in _proj (norm
    # scales / biases are plain arrays). The MoE expert stacks stay
    # boxed too — _moe_mlp feeds their carriers to the fused grouped
    # GEMM (only the [D, E] router sliver dequantizes per slice).
    T, D = h.shape
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    attn = lp["self_attn"]

    hn = _rms(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
    q = _c(lproj(hn, attn["q_proj"], "q_proj").reshape(T, H, Dh), (None, "tensor", None), mesh)
    k = _c(lproj(hn, attn["k_proj"], "k_proj").reshape(T, Hkv, Dh), (None, "tensor", None), mesh)
    v = _c(lproj(hn, attn["v_proj"], "v_proj").reshape(T, Hkv, Dh), (None, "tensor", None), mesh)
    q = _rope_flat(q, cos, sin, batch["token_pos"])
    k = _rope_flat(k, cos, sin, batch["token_pos"])

    out, kc, vc = _paged_attend(q, k, v, kc, vc, layer, batch, Dh, mesh=mesh,
                                impl=attn_impl)
    h = _c(h + lproj(out.reshape(T, H * Dh), attn["o_proj"], "o_proj"), (None, None), mesh)

    hn2 = _rms(h, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    if "moe_mlp" in lp:
        h = h + _moe_mlp(hn2, lp["moe_mlp"]["deepspeed_moe"], cfg.moe_top_k, mesh)
    else:
        mlp = lp["mlp"]
        gate = _c(_proj(hn2, mlp["gate_proj"]), (None, "tensor"), mesh)
        up = _c(_proj(hn2, mlp["up_proj"]), (None, "tensor"), mesh)
        if getattr(cfg, "mlp_activation", "silu") == "gelu_tanh":  # Gemma GeGLU
            inter = jax.nn.gelu(gate, approximate=True) * up
        else:
            inter = jax.nn.silu(gate) * up
        h = _c(h + _proj(inter, mlp["down_proj"]), (None, None), mesh)
    return (h, kc, vc), None


def _moe_mlp(x, p, k, mesh=None):
    """Dropless top-k MoE over the flat [T, D] batch (Mixtral serving —
    reference inference/v2 cutlass MoE gather/scatter). At serving time
    capacity dropping is undesirable, so every token reaches its full
    top-k: tokens are replicated k× and pushed through the grouped GEMM
    (``ops/grouped_gemm.py`` — ``lax.ragged_dot`` over expert-sorted
    rows), then combined with the renormalized gate weights.

    Under a mesh with expert/tensor parallelism the grouped GEMM runs in
    a manual shard_map: each shard holds ``E/ep`` experts (column/row
    feature shards over 'tensor'), routes every token assignment but
    masks the non-local ones, and a psum over ('expert', 'tensor')
    combines — expert weights never leave their shard, the serving
    analogue of training's expert-axis dispatch.

    Quantized serving: the MoE subtree stays BOXED through the v2 scan
    like every other projection — the expert stacks feed the grouped
    GEMM as grouped-layout carriers and dequantize inside it (fused
    kernel on TPU, gathered/ragged identical-math fallbacks elsewhere);
    only the [D, E] router sliver dequantizes here (its fp32 matmul
    needs the logits exactly as the unboxed path computed them).
    ``DS_FUSED_GMM=0`` restores the old dequantize-at-entry subtree."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    from deepspeed_tpu.ops.grouped_gemm import dropless_moe_ffn, fused_gmm_enabled
    if not fused_gmm_enabled():
        from deepspeed_tpu.inference.quantization import dequantize_tree
        p = dequantize_tree(p, x.dtype)
    gk = p["gate"]["wg"]["kernel"]
    if isinstance(gk, QuantizedWeight):
        gk = gk.dequantized(x.dtype)
    gates = jax.nn.softmax(
        (x.astype(jnp.float32) @ gk.astype(jnp.float32)), axis=-1)
    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # [T, k]
    if k > 1:
        topk_vals = topk_vals / jnp.maximum(topk_vals.sum(-1, keepdims=True), 1e-9)
    return dropless_moe_ffn(x, topk_idx, topk_vals,
                            p["experts_w1"], p["experts_w3"], p["experts_w2"],
                            num_experts=gates.shape[-1], mesh=mesh,
                            widen_boundary=False)  # forward-only: keep the
    # bf16 expert-axis gather (the fp32 boundary exists for the backward
    # transpose psum, which serving never runs)


def _gpt_layer_step(cfg, cos, sin, alibi, batch, mesh, attn_impl, carry, xs):
    """One GPT-family block over the flat ragged batch (sequential or
    parallel wiring, optional partial rotary / ALiBi, biased
    projections, LayerNorm or RMSNorm)."""
    h, kc, vc = carry
    layer, lp = xs
    # Quantized carriers stay boxed; _proj consumes them fused.
    T, D = h.shape
    H, Hkv, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    attn = lp["attn"]

    def norm(p, x):
        p = p["norm"]
        if cfg.norm_type == "rmsnorm":
            return _rms(x, p["scale"], cfg.layer_norm_eps)
        return _layernorm(x, p, cfg.layer_norm_eps)

    x_attn = norm(lp["input_layernorm"], h)
    q = _c(_proj(x_attn, attn["q_proj"]).reshape(T, H, Dh), (None, "tensor", None), mesh)
    k = _c(_proj(x_attn, attn["k_proj"]).reshape(T, Hkv, Dh), (None, "tensor", None), mesh)
    v = _c(_proj(x_attn, attn["v_proj"]).reshape(T, Hkv, Dh), (None, "tensor", None), mesh)
    if cfg.attention_softmax_scale is not None:
        # same pre-scale as models/gpt.py:209 — every attention impl
        # divides by sqrt(Dh); pre-scaling q realises any other softmax
        # scale (GPT-Neo's unscaled attention, MPT's softmax_scale)
        # without touching the paged kernels. Rope is a rotation, so the
        # scalar commutes with it.
        q = q * jnp.asarray(cfg.attention_softmax_scale * math.sqrt(Dh), q.dtype)
    if cfg.position_embedding == "rope" and cfg.rotary_dim > 0:
        rd = cfg.rotary_dim
        rope = _rope_flat_interleaved if cfg.rope_interleaved else _rope_flat
        if rd == Dh:
            q = rope(q, cos, sin, batch["token_pos"])
            k = rope(k, cos, sin, batch["token_pos"])
        else:
            q = jnp.concatenate(
                [rope(q[..., :rd], cos, sin, batch["token_pos"]), q[..., rd:]], -1)
            k = jnp.concatenate(
                [rope(k[..., :rd], cos, sin, batch["token_pos"]), k[..., rd:]], -1)

    out, kc, vc = _paged_attend(q, k, v, kc, vc, layer, batch, Dh, alibi=alibi,
                                mesh=mesh, impl=attn_impl)
    attn_out = _proj(out.reshape(T, H * Dh), attn["o_proj"])

    def mlp(x):
        inter = _c(_proj(x, lp["mlp"]["fc_in"]), (None, "tensor"), mesh)
        if cfg.activation == "relu":
            inter = jax.nn.relu(inter)
        else:
            inter = jax.nn.gelu(inter, approximate=(cfg.activation == "gelu_new"))
        return _proj(inter, lp["mlp"]["fc_out"])

    if cfg.parallel_block:
        x_mlp = norm(lp["mlp_layernorm"], h) if cfg.parallel_two_norms else x_attn
        h = _c(h + attn_out + mlp(x_mlp), (None, None), mesh)
    else:
        h = _c(h + attn_out, (None, None), mesh)
        h = _c(h + mlp(norm(lp["post_attention_layernorm"], h)), (None, None), mesh)
    return (h, kc, vc), None


def ragged_forward(params, kcache, vcache, batch, cfg, dtype=jnp.bfloat16, mesh=None,
                   attn_impl=None, lora=None):
    """→ (last-token logits [max_seqs, vocab] fp32, kcache, vcache).

    ``kcache``/``vcache``: the pool [L, NB, bs, Hkv*Dh], carried through
    the layer scan and written in place (donate them); ``batch``: the
    arrays of ``RaggedBatchWrapper.finalize()``. ``cfg`` is a ``LlamaConfig``
    or ``GPTConfig``; the layer wiring follows it. ``mesh``: an optional
    serving mesh — params/KV arrive sharded per
    ``inference/v2/sharding.py`` and the step pins the Megatron layout
    (replicated tokens, head/feature-sharded projections) so GSPMD
    inserts the TP all-reduces.

    ``lora``: None (the exact pre-LoRA program) or
    ``(a, b, scales, seq_adapters, impl)`` — per-site stacked hot slabs
    ``a[site] [L, S, in, r]`` / ``b[site] [L, S, r, out]``, per-slot
    ``scales [S]``, the batch's per-sequence adapter slots
    ``seq_adapters [max_seqs + 1]`` (pad row = slot 0 = base), and the
    static kernel impl selector. Llama-family layers only.

    ``attn_impl``: the engine's ``heuristics.AttentionChoice`` (None =
    unpinned and unreported)."""
    is_gpt = hasattr(cfg, "position_embedding")
    embed = params["model"]["embed_tokens"]
    h = _c(embed[batch["token_ids"]].astype(dtype), (None, None), mesh)  # [T, D]
    mult = getattr(cfg, "embedding_multiplier", 1.0)
    if mult != 1.0:  # Gemma: sqrt(hidden_size)
        h = h * jnp.asarray(mult, h.dtype)

    layer_ids = jnp.arange(kcache.shape[0], dtype=jnp.int32)
    if lora is not None and is_gpt:
        raise NotImplementedError(
            "multi-tenant LoRA serving targets the Llama-family layer "
            "stack; GPT-family models serve base-only")
    if is_gpt:
        cos = sin = None
        if cfg.position_embedding == "rope" and cfg.rotary_dim > 0:
            cos, sin = rope_frequencies(cfg.rotary_dim, cfg.max_position_embeddings,
                                        cfg.rope_theta)
            cos, sin = jnp.asarray(cos), jnp.asarray(sin)
        alibi = None
        if cfg.position_embedding == "alibi":
            from deepspeed_tpu.models.gpt import alibi_slopes
            alibi = jnp.asarray(alibi_slopes(cfg.num_attention_heads))
        if cfg.position_embedding == "learned":
            pos_table = params["model"]["embed_positions"]
            h = h + pos_table[batch["token_pos"] + cfg.learned_pos_offset].astype(dtype)
        if cfg.embedding_layernorm:
            h = _layernorm(h, params["model"]["embed_layernorm"], cfg.layer_norm_eps)
        step = functools.partial(_gpt_layer_step, cfg, cos, sin, alibi, batch, mesh,
                                 attn_impl)
        xs = (layer_ids, params["model"]["layers"])
    else:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_position_embeddings, cfg.rope_theta,
                                    scaling=rope_scaling_of(cfg))
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
        lora_ctx = None
        xs = (layer_ids, params["model"]["layers"])
        if lora is not None:
            la, lb, scales, seq_adapters, lora_impl = lora
            # per-token adapter slot: pad tokens hit the pad row, which
            # carries slot 0 (base) by construction
            slots = seq_adapters[batch["token_seq"]]
            lora_ctx = (slots, scales, lora_impl)
            xs = (layer_ids, params["model"]["layers"], la, lb)
        step = functools.partial(_layer_step, cfg, cos, sin, batch, mesh, attn_impl,
                                 lora_ctx)

    (h, kc, vc), _ = jax.lax.scan(step, (h, kcache, vcache), xs)

    if is_gpt:
        if cfg.norm_type == "layernorm":
            h = _layernorm(h, params["model"]["final_layernorm"], cfg.layer_norm_eps)
        else:
            h = _rms(h, params["model"]["final_norm"]["scale"], cfg.layer_norm_eps)
    else:
        h = _rms(h, params["model"]["norm"]["scale"], cfg.rms_norm_eps)
    if "lm_head" in params:
        logits = h @ params["lm_head"]["kernel"].astype(h.dtype)
    else:  # tied embeddings
        logits = h @ embed.T.astype(h.dtype)
    logits = _c(logits, (None, "tensor"), mesh)  # vocab-sharded head
    sel = logits[batch["last_index"]]  # [max_seqs, V]
    return sel.astype(jnp.float32), kc, vc
