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
  ``LlamaForCausalLM`` or ``GPTForCausalLM`` checkpoint serves directly;
- what differs between model families sits in one class each, derived
  from :class:`ModelKind` (``KINDS``; :func:`kind_of` picks by the config's
  type), which states only what differs from the base: the state the pool
  holds and how many layers of it, the layer pattern (leading layers, then
  the scan — or, for a stack of several kinds of layer,
  :meth:`SalaKind.stack` and :func:`_run_segments`), what state it keeps
  beyond the two paged pools, what a step counts on the device, the final
  norm. :func:`ragged_forward` is the same for all. To add a kind:
  ``docs/MIGRATING.md``, "Adding a model kind".

Written once for the kinds that share it: the biased top-k router and the
expert layer behind it (:func:`_routed_experts`; a kind gives its ``router``
values), one layer cut out of a stack (:func:`_layer_of`: the check hooks
too), a convolution whose tail is a slot, a packed recurrence's decays, the
latent attention, the position-free grouped-query mixer of the two kinds
whose state-space layers carry the order (:func:`_plain_gqa_attention`).
**Not folded, on purpose**: that mixer and :func:`_lfm2_attention`, a dozen
lines each, differ in a head norm, a rotation and a projection's name (one function of
those three is as long as the two, and its callers still know all three);
:func:`_layer_step`'s carries LoRA sites and sharding constraints. A new
kind takes the nearest; a third wants a reason.
"""

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import (GPTConfig, GraniteHybridConfig, JambaConfig, LagunaConfig,
                                  Lfm2MoeConfig, LlamaConfig, LongcatFlashConfig, MiniCPMSalaConfig, MoonlightConfig,
                                  NemotronHConfig, OuroConfig, SolarOpen2Config)
from deepspeed_tpu.models import laguna, ouro
from deepspeed_tpu.models.lfm2 import TOPK_EPS
from deepspeed_tpu.models.llama import rope_frequencies, rope_scaling_of
from deepspeed_tpu.models.nemotron_h import relu2
from deepspeed_tpu.models.solar_open2 import L2_EPS
from deepspeed_tpu.ops.grouped_gemm import (ExpertShare, dropless_moe_ffn, expert_share_ffn,
                                            fused_gmm_enabled)


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
    return _rotate_halves(x, cos[positions][:, None, :], sin[positions][:, None, :])


def _rotate_halves(x, c, s):
    """x [T, H, D] rotated by halves, in float32: c / s [T, 1, D/2], row t token t's."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _rope_rows(positions, d, theta):
    """→ (cos, sin) [T, d/2]: the rotations of this batch's ``positions`` [T], row t token
    t's, not a table of ``max_position_embeddings`` (131072) rows baked into the program."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angle), jnp.sin(angle)


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


def _live_rows(batch):
    """The rows of the batch before which every row that is not padding
    lies: one past the last row with a sequence (``token_seq < max_seqs``).
    The engine packs live rows first, so it is their count - a ``put``'s
    ``num_tokens``, a burst's sequences, a verify program's ``(d + 1)`` a
    sequence - and a paged kernel told it does no work for the rows from
    there on. Once a step (:func:`ragged_forward` keeps it in the batch)."""
    if "live_rows" in batch:
        return batch["live_rows"]
    seq = batch["token_seq"]
    rows = jnp.arange(1, seq.shape[0] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(seq < batch["block_tables"].shape[0] - 1, rows, 0))


def _paged_attend(q, k, v, kc, vc, layer, batch, Dh, alibi=None, mesh=None, impl=None,
                  window=None):
    """Scatter layer ``layer``'s new K/V rows into the paged pool (one
    scatter into the whole pool, which comes back as the same buffers)
    and attend over each token's block-tabled context. The attention
    implementation comes from the ``modules/heuristics`` registry
    (Pallas decode kernel single-device or per-TP-shard, XLA gather
    fallback / ALiBi path), optionally pinned by the engine config's
    ``implementation_overrides``; ``impl`` is the engine's
    :class:`AttentionChoice`, which carries the pin in and the selected
    implementation's name out. A row's table here is its sequence's whole
    table, so this - and nothing else - gives the kernel the step's query
    tiles (``batch["query_tiles"]``: :func:`ragged_forward`), and notes in
    ``impl.tiled`` that a program of this width did, for the host's count of
    the rows they hold.

    ``window``: None, or the positions a row attends to, its own last (a
    window layer of :class:`LagunaKind`): ``kc`` / ``vc`` are then the
    **window pool's** arrays, and a sequence's table there is its ring, a
    row of ``batch["seq_state"]`` (``ragged_manager``: the block of
    positions ``b * bs ..`` in column ``b % ring``). The new rows go where
    the ring says; the call is given the blocks a row's window touches and
    no other (``paged_attention.window_tables``) and, by name
    (``window=``), the lower edge of the mask."""
    bs = kc.shape[2]
    T, Hkv = k.shape[:2]
    if window is None:
        tables = batch["block_tables"]
        blk = tables[batch["token_seq"], batch["token_pos"] // bs]  # [T]
    else:
        tables = batch["seq_state"]
        blk = tables[batch["token_seq"], (batch["token_pos"] // bs) % tables.shape[1]]
    off = batch["token_pos"] % bs
    kc = _c_pool(kc.at[layer, blk, off].set(k.reshape(T, -1).astype(kc.dtype)), Hkv, mesh)
    vc = _c_pool(vc.at[layer, blk, off].set(v.reshape(T, -1).astype(vc.dtype)), Hkv, mesh)

    from deepspeed_tpu.inference.v2.modules.heuristics import instantiate_attn
    tab = tables[batch["token_seq"]]  # [T, MB]
    pos = batch["token_pos"]
    tiles = batch.get("query_tiles")
    if window is not None:
        from deepspeed_tpu.ops.pallas.paged_attention import QUERY_TILE, window_tables
        tab, pos = window_tables(tab, pos, window, bs, 1 if tiles is None else QUERY_TILE)
    name, attn_fn = instantiate_attn(mesh, Dh, bs, q.shape, kc.shape, alibi,
                                     max_blocks=tab.shape[1],
                                     override=impl.override if impl else None)
    if window is not None:
        attn_fn = functools.partial(attn_fn, window=window)
    if impl is not None:
        impl.selected[q.shape[0]] = name
        if tiles is not None and name != "xla_gather":  # the gather reads every row alone
            impl.tiled.add(q.shape[0])
    out = attn_fn(q, kc, vc, tab, pos, layer, _live_rows(batch), tiles)
    return _c(out, (None, "tensor", None), mesh), kc, vc


def _layer_step(cfg, cos, sin, batch, mesh, attn_impl, lora_ctx, experts, carry, xs):
    """One Llama-family block over the flat ragged batch. ``experts``:
    None, or every layer's routed expert stacks, whole and outside the
    scan's ``xs`` (:func:`_split_expert_stacks`)."""
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
        h = h + _moe_mlp(hn2, lp["moe_mlp"]["deepspeed_moe"], cfg.moe_top_k, mesh,
                         experts, layer)
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


EXPERT_STACKS = ("experts_w1", "experts_w3", "experts_w2")


def _split_expert_stacks(layers, mesh):
    """→ (the scan's layer tree, the routed expert stacks it no longer
    holds or None). The stacks ``[L, E, in, out]`` leave the scan's
    ``xs`` and ride the step whole where the grouped GEMM can index them
    as one table (:func:`_layer_groups`): plain arrays, and no
    expert/tensor axis to shard them over. Quantized carriers (another
    container, whose ``ragged_dot`` dequantizes the stack it is given)
    and sharded experts (``E/ep`` a shard inside ``shard_map``) stay in
    ``xs`` and are sliced by layer, as every other weight is."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    from deepspeed_tpu.ops.grouped_gemm import shards_experts
    moe = layers.get("moe_mlp", {}).get("deepspeed_moe")
    if (moe is None or shards_experts(mesh)
            or any(isinstance(moe[n], QuantizedWeight) for n in EXPERT_STACKS)):
        return layers, None
    rest = {n: w for n, w in moe.items() if n not in EXPERT_STACKS}
    return ({**layers, "moe_mlp": {**layers["moe_mlp"], "deepspeed_moe": rest}},
            {n: moe[n] for n in EXPERT_STACKS})


def _layer_groups(stacks, layer):
    """Every layer's routed experts ``[L, E, in, out]`` → (one table of
    ``L x E`` groups ``[L*E, in, out]``: a bitcast, layer ``layer``'s
    first group in it). A grouped matmul takes its weights as one buffer
    (the Pallas kernel's index map and ``ragged_dot``'s group sizes both
    start at the layer's first group), so a layer's experts cut out of
    the stack would be copied first, in every layer of every step:
    2.8 GB for Mixtral's 8, 47 % of the device's time; 1.1 GB for
    Moonlight's 64 (PERF.md, PRs 28, 29 and 31)."""
    table = jax.tree.map(lambda w: w.reshape((-1,) + w.shape[2:]), stacks)
    return table, layer * jax.tree.leaves(stacks)[0].shape[1]


def _layer_of(stack, layer):
    """Layer ``layer`` (may be traced) of a stack ``{name: [L, ...]}``, without
    its ``experts``, which ride every step whole. A stack's body cuts its
    layers so, and a kind's check hooks (``expert_layer``, ``mamba_layer``, ...)
    to run one alone **as the step programs compute it**: the same slices."""
    return jax.tree.map(lambda w: w[layer], {k: v for k, v in stack.items() if k != "experts"})


def _experts_apart(layers):
    """→ (a stack of layers for a scan's ``xs``, its ``mlp`` without the routed experts;
    those ``[L, E, in, out]``, which ride the step whole: see :func:`_routed_experts`)."""
    mlp = layers["mlp"]
    return {**layers, "mlp": {k: v for k, v in mlp.items() if k != "experts"}}, mlp["experts"]


# what a routed-expert layer behind a share counts, over the tokens that are not padding: the
# picks whose expert is held, the zero-compute picks, the held experts with at least one row
EXPERT_COUNTS = ("n_picks_held", "n_picks_zero", "n_groups_live")
# and behind a share that holds fewer experts than the router has: the passes its held picks
# took through the grouped matmul (``expert_share_ffn``: one a layer unless a step's held
# picks outgrow a pass's rows)
SHARE_COUNTS = EXPERT_COUNTS + ("n_share_passes",)


class Router(NamedTuple):
    """What differs between the kinds' biased top-k routers (``kind.router(cfg, params)``)."""
    weight: jax.Array           # [D, columns]
    bias: jax.Array             # [columns]: joins the scores for the choice, not the weights
    top_k: int
    scale: float                # routed_scaling_factor
    score: Callable = jax.nn.sigmoid    # or jax.nn.softmax, over the columns
    # of the scores' matmul, of which the picks are a step function (None: the default)
    precision: jax.lax.Precision | None = jax.lax.Precision.HIGHEST
    eps: float | None = 1e-20   # the picks' weights over (their sum + eps); None: as they are
    share: ExpertShare | None = None    # the columns held here; None: every one


def _route(x, r, real=None):
    """Scores in float32; the ``top_k`` columns with the largest score +
    bias, weighted by their **unbiased** scores, normalised or not, times
    ``scale``. ``real`` [T] bool (or a function that gives it here, where
    LongCat's program has the comparison): a row that is padding picks
    nothing (-1). → (picks, weights) [T, k]."""
    scores = r.score(jnp.dot(x.astype(jnp.float32), r.weight.astype(jnp.float32),
                             precision=r.precision))
    _, picks = jax.lax.top_k(scores + r.bias.astype(jnp.float32), r.top_k)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    if r.eps is not None:
        weights = weights / (weights.sum(-1, keepdims=True) + r.eps)
    weights = weights * r.scale
    if real is not None:
        picks = jnp.where((real() if callable(real) else real)[:, None], picks, -1)
    return picks, weights


def _routed_experts(x, r, experts, layer, real=None, enter=None, leave=None):
    """One routed-expert layer on the normalised stream x [T, D]:
    :func:`_route`, then the picks through the dropless grouped matmul over
    the table of every layer's experts, read in place (:func:`_layer_groups`;
    ``experts`` ``{gate,up,down}_proj [L, E, in, out]``, or ``{up,down}_proj``
    of ungated ``relu(u W1)^2 W2`` experts; ``layer`` this one's index among
    them). ``enter`` / ``leave``: the projections into and out of the latent
    the experts work in (Nemotron-H). Behind a share (``r.share``; or, with
    padding rows to leave out, every column: it says only that a pick of -1
    is a row of no group) held picks alone become rows, zero-compute picks
    give ``(their weights) * x``, the rest is left out, and the layer counts:
    → (y [T, D], ``SHARE_COUNTS`` int32 [4] behind ``r.share``, ``EXPERT_COUNTS``
    [3] where every column is held, None without a share)."""
    with jax.named_scope("ds.moe_routed"):
        picks, weights = _route(x, r, real)
        columns = r.weight.shape[-1]
        share = r.share
        if share is None and real is not None:
            share = ExpertShare(0, columns, columns)
        table, first_group = _layer_groups(experts, layer)
        gated = "gate_proj" in table
        u = x if enter is None else _proj(x, enter)
        stacks = (table["gate_proj" if gated else "up_proj"],
                  table["up_proj"] if gated else None, table["down_proj"])
        activation = jax.nn.silu if gated else relu2
        if share is None:
            y = dropless_moe_ffn(u, picks, weights, *stacks, num_experts=columns,
                                 widen_boundary=False, first_group=first_group,
                                 activation=activation)
        else:
            y, passes = expert_share_ffn(u, picks, weights, *stacks, share,
                                         first_group=first_group, activation=activation)
        if leave is not None:
            y = _proj(y, leave)
        if share is None:
            return y, None
        held, zero = share.parts(picks)
        if r.share is None:     # every column is held: a pick names its group outright
            live = jnp.any(picks[..., None] == jnp.arange(share.held), axis=(0, 1))
            return y, jnp.stack([held.sum(), zero.sum(), live.sum()]).astype(jnp.int32)
        of_expert = picks[..., None] == share.first + jnp.arange(share.held)
        live = jnp.any(of_expert & held[..., None], axis=(0, 1))
        return y, jnp.stack([held.sum(), zero.sum(), live.sum(), passes]).astype(jnp.int32)


def _moe_mlp(x, p, k, mesh=None, experts=None, layer=None):
    """Dropless top-k MoE over the flat [T, D] batch (Mixtral serving —
    reference inference/v2 cutlass MoE gather/scatter), with a router of
    its own and not :func:`_route`: top k of the softmax itself, no bias,
    quantized carriers, a mesh. At serving time
    capacity dropping is undesirable, so every token reaches its full
    top-k: tokens are replicated k× and pushed through the grouped GEMM
    (``ops/grouped_gemm.py`` — the Pallas grouped matmul on TPU,
    ``lax.ragged_dot`` over expert-sorted rows elsewhere), then combined
    with the renormalized gate weights.

    Under a mesh with expert/tensor parallelism the grouped GEMM runs in
    ``dropless_moe_ffn``'s manual shard_map (``E/ep`` experts a shard, a
    psum over ('expert', 'tensor')): expert weights never leave their shard.

    Quantized serving: the MoE subtree stays BOXED through the v2 scan
    like every other projection and dequantizes inside the grouped GEMM;
    only the [D, E] router sliver dequantizes here (its fp32 matmul
    needs the logits exactly as the unboxed path computed them).
    ``DS_FUSED_GMM=0`` restores the old dequantize-at-entry subtree.

    ``experts`` / ``layer``: every layer's expert stacks, kept out of the
    scan (:func:`_split_expert_stacks`), and this layer's index: the
    grouped GEMM reads the layer's groups where they lie in the table.
    Without them the stacks are ``p``'s own, this layer's slice."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    if not fused_gmm_enabled():
        from deepspeed_tpu.inference.quantization import dequantize_tree
        p = dequantize_tree(p, x.dtype)
    gk = p["gate"]["wg"]["kernel"]
    if isinstance(gk, QuantizedWeight):
        gk = gk.dequantized(x.dtype)
    gates = jax.nn.softmax(x.astype(jnp.float32) @ gk.astype(jnp.float32), axis=-1)
    topk_vals, topk_idx = jax.lax.top_k(gates, k)  # [T, k]
    if k > 1:
        topk_vals = topk_vals / jnp.maximum(topk_vals.sum(-1, keepdims=True), 1e-9)
    stacks, first_group = (p, None) if experts is None else _layer_groups(experts, layer)
    return dropless_moe_ffn(x, topk_idx, topk_vals,
                            *(stacks[n] for n in EXPERT_STACKS),
                            num_experts=gates.shape[-1], mesh=mesh,
                            first_group=first_group,
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


def _scanned_stack(kind, params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
    """``kind.stack`` of every kind whose stack is :meth:`layers`' pattern:
    the leading layers, run one by one, then one scan over identical
    blocks. → (h, kc, vc, extra — which such a kind has none of —, the
    scan's counts, a row a layer)."""
    layer_ids = jnp.arange(kc.shape[0], dtype=jnp.int32)
    h, leading, step, xs = kind.layers(params, cfg, h, batch, dtype, mesh, attn_impl, lora,
                                       layer_ids)
    carry = (h, kc, vc)
    for lead_step, lead_xs in leading:
        carry, _ = lead_step(carry, lead_xs)
    (h, kc, vc), counts = jax.lax.scan(step, carry, xs)
    return h, kc, vc, extra, counts


class ModelKind:
    """What the ragged engine asks of a model family: the per-layer state it
    keeps in the paged pool (``state_kind``, :meth:`state_layers`,
    :meth:`state_rows`), what it keeps beyond the pool, its layer stack
    (:meth:`stack`; by default :meth:`layers`' pattern: the leading layers, run
    one by one, then the step and ``xs`` of the layer scan), what a step counts
    on the device, its final norm. A kind is a class, never an instance, and
    states what differs from these defaults: keys and values of every layer,
    nothing beyond them, no adapters, no counts."""
    name = None
    config = None           # the config class :func:`kind_of` finds this kind by
    state_kind = "kv"       # two pools of expanded keys and values, [L, NB, bs, Hkv*Dh]
    lora = False
    # names of the device-side counts a step of this kind's stack gives (int32, summed over
    # its layers; they ride out with the step's result into its step record)
    step_counts = ()
    seq_rows = 0            # per-sequence rows of the batch (``seq_state``'s length)
    slot_state = ()         # the entries of extra_state a slot is a row of
    # whether the prefix cache may keep **snapshots** of this kind's slots (a copy of a
    # sequence's whole slot as it stood at a block boundary: ``prefix_cache/manager.py``), and
    # the named scope the engine's slot-to-slot copy program bears
    snapshots = False
    snapshot_scope = "ds.snapshot"
    experts_at = None       # the entry of params["model"] that holds the routed experts, whole
    # the optional subsystems (``InferenceEngineV2._refuse_unsupported``'s names) a kind
    # whose state is ``kv`` refuses all the same; any other state refuses them all
    refuses = ()
    stack = classmethod(_scanned_stack)

    @staticmethod
    def state_layers(cfg):
        """→ the pools' first axis: the layers of state a token holds."""
        return cfg.num_hidden_layers

    @staticmethod
    def state_rows(cfg):
        """→ the row widths of the engine's two pools (values a token a layer)."""
        width = cfg.num_key_value_heads * cfg.head_dim
        return width, width

    @staticmethod
    def extra_state(cfg, num_blocks, slots, dtype):
        """→ the tree of state beyond the two paged pools (zeros), or None."""
        return None

    @staticmethod
    def window(cfg):
        """→ None, or ``(the positions a window layer's row attends to, the
        window layers)``: the engine then keeps a second pool for those
        layers (``ragged/kv_cache.WindowPool``), whose arrays
        (``WindowPool.arrays``: ``wk`` / ``wv``) are the step's ``extra``
        and whose table a sequence the step's ``seq_state`` row."""
        return None

    @staticmethod
    def seq_state(cfg, slot, prompt_len):
        """A sequence's row of the batch, for a kind with ``seq_rows``."""
        return (slot,)

    @classmethod
    def experts_form(cls, params, mesh):
        """How a layer reaches its routed experts, for the engine's
        start-up line: ``table`` (the stacks ride the step whole),
        ``sliced`` (the scan cuts the layer's out) or None (no experts)."""
        return "table" if cls.experts_at in params["model"] else None

    @staticmethod
    def final_norm(params, cfg, h):
        return _rms(h, params["model"]["norm"]["scale"], cfg.rms_norm_eps)

    @classmethod
    def base_only(cls, mesh, lora):
        """Refuses adapters and a mesh, for a kind whose stack serves neither."""
        if lora is not None or mesh is not None:
            raise NotImplementedError(f"the {cls.name} layer stack serves base-only"
                                      + ("" if mesh is None else " on one device"))


class LlamaKind(ModelKind):
    """The Llama family (Llama, Mistral, Mixtral, Qwen2, InternLM, Gemma):
    one scan over identical blocks, adapters, a mesh."""
    name = "llama"
    config = LlamaConfig
    lora = True

    @staticmethod
    def layers(params, cfg, h, batch, dtype, mesh, attn_impl, lora, layer_ids):
        """→ (h, leading [(step, xs), ...], scan step, scan xs)."""
        if cfg.layer_types or cfg.sliding_window:
            raise NotImplementedError("the Llama family's layer_types / sliding_window are the "
                                      "training block's (models/llama.py); not served")
        cos, sin = map(jnp.asarray, rope_frequencies(cfg.head_dim, cfg.max_position_embeddings,
                                                     cfg.rope_theta, scaling=rope_scaling_of(cfg)))
        lora_ctx = None
        layers, experts = _split_expert_stacks(params["model"]["layers"], mesh)
        xs = (layer_ids, layers)
        if lora is not None:
            la, lb, scales, seq_adapters, lora_impl = lora
            # per-token adapter slot: pad tokens hit the pad row, which
            # carries slot 0 (base) by construction
            slots = seq_adapters[batch["token_seq"]]
            lora_ctx = (slots, scales, lora_impl)
            xs = (layer_ids, layers, la, lb)
        step = functools.partial(_layer_step, cfg, cos, sin, batch, mesh, attn_impl,
                                 lora_ctx, experts)
        return h, (), step, xs

    @staticmethod
    def experts_form(params, mesh):
        layers = params["model"]["layers"]
        if "moe_mlp" not in layers:
            return None
        return "sliced" if _split_expert_stacks(layers, mesh)[1] is None else "table"


class GPTKind(LlamaKind):
    """The GPT family (GPT-2/J/NeoX, OPT, Bloom, Falcon, Phi): the same
    state, its own block and position schemes."""
    name = "gpt"
    config = GPTConfig
    lora = False

    @staticmethod
    def layers(params, cfg, h, batch, dtype, mesh, attn_impl, lora, layer_ids):
        GPTKind.base_only(None, lora)
        cos = sin = None
        if cfg.position_embedding == "rope" and cfg.rotary_dim > 0:
            cos, sin = map(jnp.asarray, rope_frequencies(
                cfg.rotary_dim, cfg.max_position_embeddings, cfg.rope_theta))
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
        return h, (), step, (layer_ids, params["model"]["layers"])

    @staticmethod
    def final_norm(params, cfg, h):
        if cfg.norm_type == "layernorm":
            return _layernorm(h, params["model"]["final_layernorm"], cfg.layer_norm_eps)
        return _rms(h, params["model"]["final_norm"]["scale"], cfg.layer_norm_eps)


LATENT_ROPE_LANES = 128     # the rotated key's row, padded to one lane tile
LATENT_FETCH_COUNTS = ("n_blocks_named", "n_blocks_fetched")


def _latent_stack(kind, params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
    """:func:`_scanned_stack` for a latent state, then
    ``LATENT_FETCH_COUNTS``, once a step and not once a layer (every state
    layer's call has the step's tables and positions): over all the
    program's rows, padding included, the blocks their contexts name and
    those of them one call of the latent kernel starts a copy for
    (``paged_mla_attention.fetch_counts``, its fetch rule; the gather
    reads whatever is named). They follow the scan's own counts, summed."""
    from deepspeed_tpu.ops.pallas import paged_mla_attention as pm
    h, kc, vc, extra, counts = _scanned_stack(kind, params, cfg, h, kc, vc, extra, batch, dtype,
                                              mesh, attn_impl, lora)
    tab, bs = batch["block_tables"][batch["token_seq"]], kc.shape[2]
    n, _ = pm.mla_tile(bs, (kc.shape[3] + vc.shape[3]) * kc.dtype.itemsize, kc.dtype.itemsize,
                       tab.shape[1], cfg.num_attention_heads)
    named, fetched = pm.fetch_counts(tab, batch["token_pos"], bs, n, _live_rows(batch))
    if attn_impl is None or attn_impl.selected.get(tab.shape[0]) != "pallas_paged_mla":
        fetched = named
    fetch = jnp.stack([named, fetched])
    if counts is not None:
        fetch = jnp.concatenate([counts.sum(axis=0), fetch])
    return h, kc, vc, extra, fetch[None]


class MoonlightKind(ModelKind):
    """Moonlight / DeepSeek-V3 (``models/moonlight.py``): a **latent**
    state and ``dense x first_k_dense_replace, moe x (L - that)``.

    Per token and layer the pool holds ``kv_a_layernorm(c_kv)``
    (``kv_lora_rank`` values) in the engine's first pool and the
    **rotated** ``k_rope`` (``qk_rope_head_dim`` values, zero-padded to
    one 128-lane tile, which is what Mosaic's block DMA and the HBM
    tiling would make of a 64-wide row anyway) in its second: 512 + 128
    = 640 values = 1280 B a token a layer in bf16 for Moonlight, against
    8192 B for its keys and values expanded. Never expanded keys or
    values: ``kv_b_proj`` is absorbed, its key half into the query and
    its value half after the attention."""
    name = "moonlight"
    config = MoonlightConfig
    state_kind = "latent"
    step_counts = LATENT_FETCH_COUNTS
    experts_at = "layers"
    stack = classmethod(_latent_stack)

    @staticmethod
    def state_rows(cfg):
        return cfg.kv_lora_rank, -(-cfg.qk_rope_head_dim // LATENT_ROPE_LANES) * LATENT_ROPE_LANES

    @staticmethod
    def layers(params, cfg, h, batch, dtype, mesh, attn_impl, lora, layer_ids):
        MoonlightKind.base_only(mesh, lora)
        cos, sin = map(jnp.asarray, rope_frequencies(
            cfg.qk_rope_head_dim, cfg.max_position_embeddings, cfg.rope_theta))
        n_dense = cfg.first_k_dense_replace
        sliced, experts = _experts_apart(params["model"]["layers"])
        step = functools.partial(_moonlight_layer_step, cfg, cos, sin, batch, attn_impl, experts)
        dense = params["model"]["dense_layers"]
        lead = [(step, (layer_ids[i], jax.tree.map(lambda x, i=i: x[i], dense)))
                for i in range(n_dense)]
        return h, lead, step, (layer_ids[n_dense:], sliced)

    @staticmethod
    def router(cfg, mlp):
        """``noaux_tc``: the matmul as it comes, normalised where
        ``norm_topk_prob``; every column an expert held here, nothing counted."""
        gate = mlp["gate"]
        return Router(gate["weight"], gate["e_score_correction_bias"], cfg.num_experts_per_tok,
                      cfg.routed_scaling_factor, precision=None,
                      eps=1e-20 if cfg.norm_topk_prob else None)


class LongcatKind(MoonlightKind):
    """LongCat-Flash (``models/longcat.py``): the latent state of
    :class:`MoonlightKind`, **two state layers a model layer** (a double
    layer holds two latent attentions: the pools' first axis is ``2 x
    num_layers``, and double layer ``l`` writes rows ``2l`` and ``2l +
    1``), no leading layers, and an expert layer that is one share of an
    expert-parallel deployment behind a router with zero-compute
    columns. Each step counts ``SHARE_COUNTS`` over its expert layers,
    then the latent state's two (:func:`_latent_stack`)."""
    name = "longcat"
    config = LongcatFlashConfig
    step_counts = SHARE_COUNTS + LATENT_FETCH_COUNTS

    @staticmethod
    def state_layers(cfg):
        return 2 * cfg.num_layers

    @staticmethod
    def layers(params, cfg, h, batch, dtype, mesh, attn_impl, lora, layer_ids):
        LongcatKind.base_only(mesh, lora)
        rope = (*_rope_rows(batch["token_pos"], cfg.qk_rope_head_dim, cfg.rope_theta),
                jnp.arange(h.shape[0], dtype=jnp.int32))
        # every weight but the experts is [L, in, out] under its half's name, which the scan
        # reads in place a layer at a time
        sliced, experts = _experts_apart(params["model"]["layers"])
        step = functools.partial(_longcat_layer_step, cfg, rope, batch, attn_impl, experts)
        return h, (), step, (layer_ids.reshape(-1, 2), sliced)

    @staticmethod
    def router(cfg, mlp):
        """Softmax over every column, zero-compute ones too; not normalised; a share."""
        router = mlp["router"]
        return Router(router["classifier"]["weight"], router["e_score_correction_bias"],
                      cfg.moe_topk, cfg.routed_scaling_factor, score=jax.nn.softmax, eps=None,
                      share=ExpertShare(cfg.first_expert_held, cfg.held, cfg.n_routed_experts,
                                        cfg.zero_expert_num))

    @staticmethod
    def expert_layer(params, cfg, layer, x):
        """``M(x)`` of double layer ``layer`` alone (:func:`_layer_of`): x
        [T, D], every row a token → m."""
        mlp = params["model"]["layers"]["mlp"]
        return _routed_experts(x, LongcatKind.router(cfg, _layer_of(mlp, layer)), mlp["experts"],
                               layer, jnp.ones(x.shape[0], bool))[0]


class SalaKind(ModelKind):
    """MiniCPM-SALA (``models/minicpm_sala.py``): a stack of **two kinds
    of layer in an irregular order**, whose state is of three kinds.

    - The ``minicpm4`` (sparse-attention) layers keep keys and values in
      the engine's two paged pools, one pool layer a (sparse layer,
      key-value head): ``[Ls * Hkv, NB, bs, d]``, so that what a
      key-value head's selection reads is whole rows of one pool layer
      and the paged kernel's block copy is the head's 128 lanes alone.
      The other layers hold nothing there (``state_layers`` is not the
      model's depth).
    - ``extra_state``'s ``pooled_keys`` ``[Ls * Hkv, NB, bs / stride, d]``:
      the mean of every stride-group of a block's key rows, which the
      selection scores (a pooled key is the mean of two neighbouring
      groups) instead of the keys themselves: 1 / stride of their bytes.
    - ``extra_state``'s ``slots`` ``[Ll, slots + 1, H, d, d]`` float32: a
      ``lightning-attn`` layer's state a sequence, the same at token 10
      and at token 500,000. A tracked sequence owns a slot
      (``ragged/slot_pool.py``) beside its blocks; slot 0 is padding's.
      A sequence's first rows (position 0) take the state as zero, so
      a slot needs no clearing between owners.

    The batch carries, a sequence, ``seq_state`` = (its slot, the first
    position that attends sparsely). Each step counts, over its tokens
    that are not padding: the blocks the sparse layers' (token, key-value
    head)s read and the blocks their contexts hold, and the rows through
    the linear layers."""
    name = "sala"
    config = MiniCPMSalaConfig
    state_kind = "sparse_kv+slots"
    step_counts = ("n_blocks_selected", "n_blocks_context", "n_linear_rows")
    seq_rows = 2            # per-sequence rows of the batch: (slot, sparse_from)
    slot_state = ("slots",)

    @staticmethod
    def state_layers(cfg):
        return len(cfg.sparse_positions) * cfg.num_key_value_heads

    @staticmethod
    def state_rows(cfg):
        return cfg.head_dim, cfg.head_dim

    @staticmethod
    def extra_state(cfg, num_blocks, slots, dtype):
        H, d = cfg.num_attention_heads, cfg.head_dim
        groups = cfg.sparse_block_size // cfg.sparse_kernel_stride
        return {"pooled_keys": jnp.zeros((SalaKind.state_layers(cfg), num_blocks, groups, d),
                                         dtype),
                "slots": jnp.zeros((len(cfg.linear_positions), slots + 1, H, d, d),
                                   jnp.float32)}

    @staticmethod
    def seq_state(cfg, slot, prompt_len):
        return slot, cfg.sparse_from(prompt_len)

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        """The sparse layers one by one, each from its own tree, and one
        scan over every run of linear layers between them: the scan's
        ``xs`` is the run's indices and its body reads layer ``i`` out of
        the whole stack, as a scan reads its ``xs``, so no run is cut out
        of the stack. → (h, kc, vc, extra, the counts in one row)."""
        SalaKind.base_only(mesh, lora)
        if kc.shape[2] != cfg.sparse_block_size:
            raise ValueError(f"kv_block_size {kc.shape[2]} is not the selection's block "
                             f"size {cfg.sparse_block_size}")
        model = params["model"]
        ctx = _SalaStep(cfg, batch)
        linear, kb, slots = model["linear_layers"], extra["pooled_keys"], extra["slots"]
        log_decay = jnp.asarray([cfg.log_decay(p) for p in cfg.linear_positions], jnp.float32)

        def linear_step(carry, i):
            h, slots = carry
            lp = _layer_of(linear, i)
            return _sala_linear_layer(ctx, lp, log_decay[i], i, h, slots), None

        n_sparse = n_linear = 0
        run = []
        for mixer in cfg.mixer_types + (None,):
            if mixer == "lightning-attn":
                run.append(n_linear)
                n_linear += 1
                continue
            if run:
                (h, slots), _ = jax.lax.scan(linear_step, (h, slots),
                                             jnp.asarray(run, jnp.int32))
                run = []
            if mixer is not None:
                h, kc, vc, kb = _sala_sparse_layer(ctx, model["sparse_layers"][str(n_sparse)],
                                                   n_sparse, h, kc, vc, kb, attn_impl)
                n_sparse += 1
        real = ctx.real.astype(jnp.int32)
        counts = jnp.stack([n_sparse * jnp.sum(real[:, None] * ctx.counts),
                            n_sparse * cfg.num_key_value_heads * jnp.sum(real * (ctx.own + 1)),
                            n_linear * jnp.sum(real)]).astype(jnp.int32)
        return h, kc, vc, {"pooled_keys": kb, "slots": slots}, counts[None]

    @staticmethod
    def final_norm(params, cfg, h):
        return ModelKind.final_norm(params, cfg, h) / jnp.asarray(cfg.logit_divisor, h.dtype)

    @staticmethod
    def sparse_layer(params, cfg, layer, x, kc, vc, kb, batch, attn_impl=None):
        """Sparse layer ``layer``'s mixer alone, as the step programs
        compute it (the same writes, selection and paged attention), for a
        check that wants it without the rest: x [T, D] the normalised
        stream → (y [T, D], kc, vc, kb, the table [T, Hkv, W] of logical
        blocks each (token, key-value head) read, counts [T, Hkv])."""
        ctx = _SalaStep(cfg, batch)
        attn = params["model"]["sparse_layers"][str(layer)]["self_attn"]
        y, kc, vc, kb, tables = _sala_sparse_mixer(ctx, attn, layer, x, kc, vc, kb, attn_impl)
        return y, kc, vc, kb, tables, ctx.counts


class _SalaStep:
    """What every layer of one step shares: the batch, each token's
    sequence row, position, block and slot, whether it attends densely,
    and how many blocks it reads."""

    def __init__(self, cfg, batch):
        self.cfg, self.batch = cfg, batch
        self.seq, self.pos = batch["token_seq"], batch["token_pos"]
        tables = batch["block_tables"]
        self.n_rows = tables.shape[0]                      # sequences a step + padding's
        self.real = self.seq < self.n_rows - 1
        bs = cfg.sparse_block_size
        self.own = self.pos // bs
        state = batch["seq_state"]
        self.slot = state[:, 0]
        self.dense = self.pos < state[self.seq, 1]
        # how many blocks a (token, key-value head) reads is a function of its position
        # alone (which blocks is the selection's): all of a dense row's, at most topk of a
        # sparse one's
        n = jnp.where(self.dense, self.own + 1, jnp.minimum(self.own + 1, cfg.sparse_topk))
        self.counts = jnp.broadcast_to(n[:, None], (n.shape[0], cfg.num_key_value_heads))


def _rope_at(x, positions, theta):
    """x [T, H, d] rotated by halves at ``positions`` [T]."""
    return _rope_flat(x, *_rope_rows(positions, x.shape[-1], theta), jnp.arange(x.shape[0]))


def _sala_mlp(cfg, lp, h):
    x = _rms(h, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    return h + jnp.asarray(cfg.residual_scale, h.dtype) * _swiglu(x, lp["mlp"])


def _row_spans(seq, pos, S):
    """Where each of the ``S`` sequence rows' tokens lie in a step. ``seq``
    / ``pos`` [T]: each row's sequence row (padding's is the last) and
    position. → (first, length) [S]: a sequence row's first position and
    its number of rows in this step; a row with no token (and padding's,
    at position 0) is zero rows long and starts at 0, as a sequence's
    first rows do - where the state it carried is taken as zero whatever
    its slot held."""
    first = jnp.full((S,), jnp.iinfo(jnp.int32).max, jnp.int32).at[seq].min(pos)
    last = jnp.zeros((S,), jnp.int32).at[seq].max(pos)
    present = first <= last
    return jnp.where(present, first, 0), jnp.where(present, last - first + 1, 0)


class _PackedRows(NamedTuple):
    """What :func:`_packed_rows` gives; see there."""
    since: jax.Array
    until: jax.Array
    whole: jax.Array
    weights: jax.Array


def _packed_rows(seq, pos, S, log_decay):
    """The decays of a recurrence over the flat ragged batch, for any
    recurrence ``S_t = a_t S_{t-1} + (its own term)`` whose state a
    sequence carries from step to step in a slot: SALA's linear layers
    (a constant decay a head) and Mamba-2 (a decay a token and head).

    ``seq`` / ``pos`` [T]: each row's sequence row (of ``S``; padding's is
    the last) and position; ``log_decay`` [T, H] float32, ``log a_t <= 0``
    of each row. Rows of one sequence (a prompt chunk, or one decode row)
    are taken by a same-sequence-and-causal mask, so nothing here asks how
    the rows are ordered. →

    - ``since`` [T, H]: the sum of the log-decays from the sequence's
      first row of this step up to and with row t: ``exp`` of it is what
      the carried state has decayed by when row t reads it;
    - ``until`` [T, H]: the sum over the sequence's rows after t: what row
      t's own term has decayed by in the state the step leaves;
    - ``whole`` [S, H]: the sum over all of a sequence's rows;
    - ``weights`` [H, T, T]: ``exp`` of the sum over the rows after u up to
      and with t, for u <= t of one sequence; 0 elsewhere.

    Every exponent is a sum of terms <= 0. The sums are one product of
    the mask with the log-decays at the highest precision (the default
    would round the summands to bfloat16), so a constant log-decay gives
    ``log lambda * (a distance)`` to float32's rounding."""
    f32 = jnp.float32
    apart = pos[:, None] - pos[None, :]
    mask = (seq[:, None] == seq[None, :]) & (apart >= 0)                 # [t, u]: u <= t
    highest = jax.lax.Precision.HIGHEST
    since = jnp.dot(mask.astype(f32), log_decay, precision=highest)
    of_seq = (seq[:, None] == jnp.arange(S)[None, :]).astype(f32)
    whole = jnp.dot(of_seq.T, log_decay, precision=highest)
    between = since.T[:, :, None] - since.T[:, None, :]                  # [H, t, u]
    weights = jnp.where(mask[None], jnp.exp(jnp.minimum(between, 0.0)), 0.0)
    return _PackedRows(since, whole[seq] - since, whole, weights)


def _sala_linear_layer(ctx, lp, log_decay, layer, h, slots):
    """One ``lightning-attn`` layer over the flat ragged batch: the packed
    linear step. Rows of one sequence (a prompt chunk, or one decode row)
    see the sequence's carried state, decayed by their distance from the
    chunk's first row, and the chunk's earlier rows through a
    same-sequence-and-causal decay mask; the state each sequence leaves is
    written back to its slot. Where a sequence's rows lie is
    :func:`_row_spans`' and the decays are :func:`_packed_rows`', given
    this layer's constant log-decay a head at every row: every exponent is
    a sum of terms <= 0, nothing overflows, and head 0's decay is 1.
    → (h, slots)."""
    cfg = ctx.cfg
    T, H, d, S = h.shape[0], cfg.num_attention_heads, cfg.head_dim, ctx.n_rows
    f32 = jnp.float32
    a = lp["self_attn"]
    with jax.named_scope("ds.sala.linear"):
        x = _rms(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
        # The barrier keeps the head-major layouts the einsums below want from reaching
        # back through the projections into their weights: XLA otherwise stores q, k and
        # v_proj transposed and copies the whole linear stack (1.2 GB) a step to get them.
        q, k, v = (y.reshape(T, H, d) for y in jax.lax.optimization_barrier(
            tuple(_proj(x, a[name]) for name in ("q_proj", "k_proj", "v_proj"))))
        q = _rms(q, a["q_norm"]["scale"], cfg.rms_norm_eps)
        k = _rms(k, a["k_norm"]["scale"], cfg.rms_norm_eps)
        q, k = _rope_at(q, ctx.pos, cfg.rope_theta), _rope_at(k, ctx.pos, cfg.rope_theta)

        seq = ctx.seq
        rows = _packed_rows(seq, ctx.pos, S, jnp.broadcast_to(log_decay, (T, H)))
        of_seq = (seq[:, None] == jnp.arange(S)[None, :]).astype(x.dtype)          # [T, S]
        carried = slots[layer, ctx.slot]                                           # [S, H, d, d]
        fresh = _row_spans(seq, ctx.pos, S)[0] == 0
        carried = jnp.where(fresh[:, None, None, None], 0.0, carried)

        # the carried state, seen from each row: lambda ** (rows since the chunk began + 1)
        q_in = (q.astype(f32) * jnp.exp(rows.since)[..., None]).astype(x.dtype)
        inter = jnp.einsum("ts,thd,shde->the", of_seq, q_in, carried.astype(x.dtype),
                           preferred_element_type=f32)
        # the chunk's own rows: the decay mask [H, T, T], same sequence and causal
        scores = jnp.einsum("thd,uhd->htu", q, k, preferred_element_type=f32) * rows.weights
        intra = jnp.einsum("htu,uhe->the", scores.astype(x.dtype), v,
                           preferred_element_type=f32)
        o = ((inter + intra) / math.sqrt(d)).reshape(T, H * d)
        # what each sequence leaves: its state decayed over the chunk + the chunk's own
        k_out = (k.astype(f32) * jnp.exp(rows.until)[..., None]).astype(x.dtype)
        added = jnp.einsum("us,uhd,uhe->shde", of_seq, k_out, v, preferred_element_type=f32)
        state = carried * jnp.exp(rows.whole)[..., None, None] + added
        slots = slots.at[layer, ctx.slot].set(state)

        o = _rms(o.astype(x.dtype), a["o_norm"]["scale"], cfg.rms_norm_eps)
        o = o * jax.nn.sigmoid(_proj(x, a["o_gate_proj"]))
        h = h + jnp.asarray(cfg.residual_scale, h.dtype) * _proj(o, a["o_proj"])
    return _sala_mlp(cfg, lp, h), slots


def _sala_select(ctx, q, kb, layer_heads):
    """InfLLM-v2 selection for every (token, key-value head) of the step.
    q [T, Hkv, G, d] (normalised); ``kb`` the pooled-key pool; →
    tables [T, Hkv, W] int32: the **logical** blocks read, ascending, the
    columns past a row's count holding ``MB`` (no block); a dense row
    reads its whole context. ``W`` = max(topk, dense_len / block)."""
    cfg, batch = ctx.cfg, ctx.batch
    T, Hkv, G, d = q.shape
    tables = batch["block_tables"]
    MB = tables.shape[1]
    per = cfg.sparse_block_size // cfg.sparse_kernel_stride
    st, ks = cfg.sparse_kernel_stride, cfg.sparse_kernel_size
    W = min(MB, max(cfg.sparse_topk, cfg.sparse_dense_len // cfg.sparse_block_size))
    f32 = jnp.float32
    with jax.named_scope("ds.sala.select"):
        # a sequence's group means, gathered once a sequence, then laid out a token
        groups = kb[layer_heads[None, :, None], tables[:, None, :]]       # [S, Hkv, MB, per, d]
        groups = groups.reshape(tables.shape[0], Hkv, MB * per, d)[ctx.seq]
        s = jnp.einsum("tkgd,tkjd->tkgj", q, groups, preferred_element_type=f32)
        # kernel j = groups j and j + 1: its pooled key is the mean of their means
        s = 0.5 * (s[..., :-1] + s[..., 1:]) / math.sqrt(d)               # [T, Hkv, G, J]
        J = MB * per - 1
        ended = (st * jnp.arange(J) + ks)[None, :] <= (ctx.pos + 1)[:, None]       # [T, J]
        s = jax.nn.softmax(jnp.where(ended[:, None, None], s, -1e30), axis=-1)
        s = jnp.where(ended[:, None], s.sum(axis=2), 0.0)                 # [T, Hkv, J]
        # block i is overlapped by kernels per*i - 1 .. per*i + per - 1
        s = jnp.concatenate([s, jnp.zeros((T, Hkv, 1), f32)], axis=-1).reshape(T, Hkv, MB, per)
        before = jnp.concatenate([jnp.zeros((T, Hkv, 1), f32), s[:, :, :-1, per - 1]], axis=-1)
        score = jnp.maximum(s.max(axis=-1), before)                       # [T, Hkv, MB]
        blocks = jnp.arange(MB)
        own = ctx.own[:, None]
        forced = (blocks[None] < cfg.sparse_init_blocks) | (
            (blocks[None] <= own) & (blocks[None] > own - cfg.sparse_window_size
                                     // cfg.sparse_block_size))
        score = jnp.where(forced[:, None], 1e30, score)
        score = jnp.where((blocks[None] <= own)[:, None], score, -1.0)
        # The topk best, ascending, without a sort (a top_k and a sort of 416 scores a
        # (token, head) were 17 of a 512-token step's 92 ms: chip, PR 34). A score that is
        # not negative orders as its bits do, so the topk-th largest is found a bit at a
        # time: the largest v that at least topk scores reach. Every score above it is
        # chosen, and of those equal to it the earliest, as top_k breaks its ties.
        topk = min(cfg.sparse_topk, MB)
        bits = jnp.where(score >= 0, jax.lax.bitcast_convert_type(score, jnp.int32), -1)

        def raise_floor(i, v):
            higher = v | jnp.left_shift(jnp.int32(1), 30 - i)
            reach = jnp.sum(bits >= higher[..., None], axis=-1) >= topk
            return jnp.where(reach, higher, v)

        floor = jax.lax.fori_loop(0, 31, raise_floor, jnp.zeros((T, Hkv), jnp.int32))[..., None]
        above, tied = bits > floor, bits == floor
        room = topk - jnp.sum(above, axis=-1, keepdims=True)
        chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))     # [T, Hkv, MB]
        # column c holds the c-th chosen block: as many blocks as have c or fewer chosen up
        # to and with them (MB, no block, once c is past the count)
        upto = jnp.cumsum(chosen, axis=-1)
        index = jnp.sum(upto[..., :, None] <= jnp.arange(topk), axis=-2)  # [T, Hkv, topk]
        index = jnp.concatenate([index, jnp.full((T, Hkv, W - topk), MB, index.dtype)], axis=-1)
        whole = jnp.where(jnp.arange(W)[None] <= own, jnp.arange(W)[None], MB)     # [T, W]
        return jnp.where(ctx.dense[:, None, None], whole[:, None], index).astype(jnp.int32)


def _sala_sparse_mixer(ctx, a, layer, x, kc, vc, kb, attn_impl):
    """The ``minicpm4`` mixer on the normalised stream x [T, D]: write
    the rows' keys and values and the group means they complete, select,
    attend over the selected blocks. → (y, kc, vc, kb, the selection's
    table of logical blocks [T, Hkv, W])."""
    cfg, batch = ctx.cfg, ctx.batch
    T = x.shape[0]
    H, Hkv, d, bs = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                     cfg.sparse_block_size)
    st = cfg.sparse_kernel_stride
    per = bs // st
    NB = kc.shape[1]
    tables = batch["block_tables"]
    heads = layer * Hkv + jnp.arange(Hkv, dtype=jnp.int32)       # this layer's pool layers
    q = _rms(_proj(x, a["q_proj"]).reshape(T, H, d), a["q_norm"]["scale"], cfg.rms_norm_eps)
    k = _rms(_proj(x, a["k_proj"]).reshape(T, Hkv, d), a["k_norm"]["scale"], cfg.rms_norm_eps)
    v = _proj(x, a["v_proj"]).reshape(T, Hkv, d)

    mine = tables[ctx.seq]                                       # [T, MB]: each token's table
    blk = jnp.take_along_axis(mine, ctx.own[:, None], axis=1)    # [T, 1]
    off = (ctx.pos % bs)[:, None]
    kc = kc.at[heads[None, :], blk, off].set(k.astype(kc.dtype))
    vc = vc.at[heads[None, :], blk, off].set(v.astype(vc.dtype))
    # the stride-group a row completes: its mean, read back from the pool (a group's
    # earlier rows may be an earlier step's); every other row writes the null block
    group = ctx.pos // st
    done = (ctx.pos % st == st - 1)[:, None]
    rows = kc.reshape(kc.shape[0], NB, per, st, d)[heads[None, :], blk, (group % per)[:, None]]
    mean = rows.astype(jnp.float32).mean(axis=2)                 # [T, Hkv, d]
    kb = kb.at[heads[None, :], jnp.where(done, blk, 0), (group % per)[:, None]].set(
        mean.astype(kb.dtype))

    tables = _sala_select(ctx, q.reshape(T, Hkv, H // Hkv, d), kb, heads)
    with jax.named_scope("ds.sala.sparse_attn"):
        from deepspeed_tpu.inference.v2.modules.heuristics import instantiate_attn
        from deepspeed_tpu.ops.pallas.paged_attention import selected_tables
        flat_k = kc.reshape(1, kc.shape[0] * NB, bs, d)          # pool layers on end: a bitcast
        flat_v = vc.reshape(1, vc.shape[0] * NB, bs, d)
        MB = mine.shape[1]
        physical = jnp.take_along_axis(
            mine[:, None, :], jnp.minimum(tables, MB - 1), axis=2)             # [T, Hkv, W]
        physical = physical + (heads * NB)[None, :, None]
        tab, at = selected_tables(physical, ctx.counts, ctx.pos, bs)
        qv = q.reshape(T * Hkv, H // Hkv, d)
        name, attn_fn = instantiate_attn(None, d, bs, qv.shape, flat_k.shape, None,
                                         max_blocks=tab.shape[1],
                                         override=attn_impl.override if attn_impl else None)
        if attn_impl is not None:
            attn_impl.selected[T] = name
        # selected_tables lays the (token, head) rows token-major
        o = attn_fn(qv, flat_k, flat_v, tab, at, jnp.int32(0), _live_rows(batch) * Hkv,
                    selected=True).reshape(T, H * d)
    o = o * jax.nn.sigmoid(_proj(x, a["o_gate_proj"]))
    return _proj(o, a["o_proj"]), kc, vc, kb, tables


def _sala_sparse_layer(ctx, lp, layer, h, kc, vc, kb, attn_impl):
    cfg = ctx.cfg
    x = _rms(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
    y, kc, vc, kb, _ = _sala_sparse_mixer(ctx, lp["self_attn"], layer, x, kc, vc, kb, attn_impl)
    h = h + jnp.asarray(cfg.residual_scale, h.dtype) * y
    return _sala_mlp(cfg, lp, h), kc, vc, kb


def _run_segments(segments, counters_of, layer, carry):
    """A stack of several kinds of layer, run as its config's ``segments``
    cut it (``[(unit, repeats), ...]`` over a letter a layer): **one scan
    over a period** wherever a unit of layers repeats, its body the unit's
    layers in order; single layers elsewhere. A layer reads its own
    parameters out of its kinds' whole stacks, so it is told where:
    ``counters_of(letter)`` names the stacks a layer of that letter draws
    from (one for a layer that is one sublayer, an operator's and a
    feed-forward's for a layer that is both), and ``layer(letter, at,
    carry)`` → carry is given ``at[name]``, the layer's index in each (a
    traced value inside a scan). → (carry, the layers run of each stack)."""
    names = sorted({name for unit, _ in segments for t in unit for name in counters_of(t)})
    done = dict.fromkeys(names, 0)
    for unit, repeats in segments:
        base = dict(done)
        per = {name: sum(name in counters_of(t) for t in unit) for name in names}

        def period(carry, r, unit=unit, base=base, per=per):
            seen = dict.fromkeys(names, 0)
            for letter in unit:
                at = {name: base[name] + r * per[name] + seen[name]
                      for name in counters_of(letter)}
                carry = layer(letter, at, carry)
                for name in at:
                    seen[name] += 1
            return carry, None

        if repeats == 1:
            carry, _ = period(carry, 0)
        else:
            carry, _ = jax.lax.scan(period, carry, jnp.arange(repeats, dtype=jnp.int32))
        for name in names:
            done[name] += per[name] * repeats
    return carry, done


class NemotronHKind(ModelKind):
    """Nemotron-H (``models/nemotron_h.py``): **every layer one sublayer
    alone** - a Mamba-2 mixer, an attention or an expert layer, as the
    pattern's letter says - and state of two kinds side by side.

    - The ``*`` (attention) layers keep keys and values in the engine's two
      paged pools, ``[La, NB, bs, Hkv * d]``; the other layers hold nothing
      there (``state_layers`` is not the model's depth).
    - ``extra_state``'s ``ssm`` ``[Lm, slots + 1, H, P, N]`` float32 and
      ``conv`` ``[Lm, slots + 1, K - 1, C]``: an ``M`` layer's state a
      sequence - the recurrence's matrix a head and the last ``K - 1`` rows
      of ``xBC`` before the convolution's activation - the same at token 10
      and at token 500,000; slots as :class:`SalaKind`'s, one of both a
      tracked sequence.

    :meth:`stack` runs the pattern as ``cfg.segments`` cuts it
    (:func:`_run_segments`). The routed experts are one share of an
    expert-parallel deployment (``ops/grouped_gemm.ExpertShare``) and ride
    every step whole, one table of ``Le x held`` groups. Each step counts,
    over its tokens that are not padding: ``SHARE_COUNTS`` (no pick is
    zero-compute: the name is the expert-share readers'), the rows through
    the ``M`` layers, and the (sequence, ``M`` layer)s whose state it read and
    wrote - each of those one slot fetched and written back by
    ``ops/pallas/ssm_state.ssm_state_step``, the kernel that takes the
    ``ssm`` pool in place (``AttentionChoice.state_step`` says whether a
    program got it)."""
    name = "nemotron_h"
    config = NemotronHConfig
    state_kind = "kv+slots"
    step_counts = SHARE_COUNTS + ("n_ssm_rows", "n_state_slots")
    seq_rows = 1            # (slot,)
    slot_state = ("ssm", "conv")
    experts_at = "moe_layers"

    @staticmethod
    def state_layers(cfg):
        return max(1, cfg.count("*"))

    @staticmethod
    def extra_state(cfg, num_blocks, slots, dtype):
        Lm = cfg.count("M")
        return {"ssm": jnp.zeros((Lm, slots + 1, cfg.mamba_num_heads, cfg.mamba_head_dim,
                                  cfg.ssm_state_size), jnp.float32),
                "conv": jnp.zeros((Lm, slots + 1, cfg.conv_kernel - 1, cfg.conv_dim), dtype)}

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        NemotronHKind.base_only(mesh, lora)
        model = params["model"]
        ctx = _SlotStep(cfg, batch, extra["conv"].shape[1], attn_impl)
        stacks = {"M": model.get("mamba_layers"), "*": model.get("attn_layers"),
                  "E": model.get(NemotronHKind.experts_at, {})}
        experts = stacks["E"].get("experts")

        def layer(letter, i, carry):
            h, kc, vc, ssm, conv, picks = carry
            lp = _layer_of(stacks[letter], i)
            x = _rms(h, lp["norm"]["scale"], cfg.layer_norm_epsilon)
            if letter == "M":
                with jax.named_scope("ds.nemotron.mamba"):
                    y, ssm, conv = _mamba_mixer(ctx, lp, i, x, ssm, conv)
            elif letter == "*":
                with jax.named_scope("ds.nemotron.attn"):
                    y, kc, vc = _plain_gqa_attention(cfg, lp, i, x, kc, vc, batch, attn_impl)
            else:
                with jax.named_scope("ds.nemotron.latent_moe"):
                    y, n = _nemotron_moe(cfg, ctx.real, lp, experts, i, x)
                picks = picks + n
            return h + y, kc, vc, ssm, conv, picks

        carry = (h, kc, vc, extra["ssm"], extra["conv"],
                 jnp.zeros((len(SHARE_COUNTS),), jnp.int32))
        carry, done = _run_segments(cfg.segments, lambda letter: (letter,),
                                    lambda letter, at, carry: layer(letter, at[letter], carry),
                                    carry)
        h, kc, vc, ssm, conv, picks = carry
        real = ctx.real.astype(jnp.int32)
        live = jnp.sum(ctx.here.astype(jnp.int32))
        counts = jnp.concatenate([picks, jnp.stack([done["M"] * jnp.sum(real),
                                                    done["M"] * live])]).astype(jnp.int32)
        return h, kc, vc, {"ssm": ssm, "conv": conv}, counts[None]

    @staticmethod
    def final_norm(params, cfg, h):
        return _rms(h, params["model"]["norm"]["scale"], cfg.layer_norm_epsilon)

    @staticmethod
    def router(cfg, p):
        """Sigmoid scores, the picks' weights over their sum; this rank's share."""
        router = p["router"]
        return Router(router["weight"], router["e_score_correction_bias"],
                      cfg.num_experts_per_tok, cfg.routed_scaling_factor,
                      share=ExpertShare(cfg.first_expert_held, cfg.held, cfg.n_routed_experts))

    @staticmethod
    def expert_layer(params, cfg, layer, x):
        """Expert layer ``layer`` (its index among the ``E`` layers) alone
        (:func:`_layer_of`): x [T, D] the normalised stream, every row a token → y."""
        moe = params["model"]["moe_layers"]
        return _nemotron_moe(cfg, jnp.ones(x.shape[0], bool), _layer_of(moe, layer),
                             moe["experts"], layer, x)[0]

    @staticmethod
    def mamba_layer(params, cfg, layer, x, ssm, conv, batch):
        """``M`` layer ``layer``'s mixer (its index among the ``M`` layers)
        alone - the same packed recurrence, the same reads and writes of the
        slot pool: x [T, D] the normalised stream → (y [T, D], ssm, conv)."""
        lp = _layer_of(params["model"]["mamba_layers"], layer)
        return _mamba_mixer(_SlotStep(cfg, batch, conv.shape[1]), lp, layer, x, ssm, conv)


class _SlotStep:
    """What every layer of one step shares: each token's sequence row and
    position, each sequence row's slot, where its rows lie in the step, and
    which sequence row names each of the pools' ``pool_slots`` slots;
    ``choice``, the engine's ``heuristics.AttentionChoice`` (None = nobody
    asks), is told which state step the program got."""

    def __init__(self, cfg, batch, pool_slots, choice=None):
        self.cfg, self.choice = cfg, choice
        self.seq, self.pos = batch["token_seq"], batch["token_pos"]
        self.n_rows = S = batch["block_tables"].shape[0]     # sequences a step + padding's
        # a program of one row a sequence by construction says so (a burst's step:
        # ``ragged_forward``); a kernel with a form for longer runs leaves it out of such a program
        self.one_row_runs = batch.get("query_tiles", ()) is None
        self.real = self.seq < S - 1
        self.slot = batch["seq_state"][:, 0]
        T = self.seq.shape[0]
        # a sequence's rows are one run of the batch, positions ascending (the wrapper
        # appends a chunk at a time): row ``first_row + j`` is its j-th of this step
        self.first, self.length = _row_spans(self.seq, self.pos, S)
        self.fresh = self.first == 0    # (or no row at all): what the slot held is not read
        # the sequence rows with a token in this step: their slots' states are read and
        # written; padding's row (the last) owns none
        self.here = (self.length > 0) & (jnp.arange(S) < S - 1)
        self.first_row = jnp.minimum(
            jnp.full((S,), T, jnp.int32).at[self.seq].min(jnp.arange(T, dtype=jnp.int32)), T - 1)
        # the live sequence row that names a slot (live rows name distinct slots), or
        # padding's row where none does: such a slot keeps what it holds
        self.row_of_slot = jnp.full((pool_slots,), S - 1, jnp.int32).at[
            jnp.where(self.here, self.slot, pool_slots)].set(jnp.arange(S, dtype=jnp.int32),
                                                             mode="drop")


def _conv_with_tail(stream, kernel, bias, pool, layer, rows):
    """A depth-wise causal convolution over the flat ragged batch whose
    **tail is a slot**: stream [T, C], kernel [K, C] (tap ``j`` reads ``K -
    1 - j`` rows back), ``bias`` [C] or None, ``pool`` [L, slots + 1, K - 1,
    C] the carried tails, ``layer`` the pool's layer, ``rows`` the step's
    :class:`_SlotStep`. A row fewer than ``K - 1`` rows into its
    sequence's rows of this step reads the rows before them out of the
    tail its sequence carried in its slot (zero at position 0); every
    sequence leaves the tail of what it has now seen, the last ``K - 1``
    rows of its stream. → (filtered [T, C] float32, pool). What enters
    the stream before (a gate) and what follows (an activation, a gate)
    are the caller's.

    **Who moves the tails.** Nothing is gathered or scattered a slab ``[K -
    1, C]`` at a time, and no ``[S, K - 1, C]`` tensor exists: a slab of 3
    rows fills no tile, so every such tensor is laid out again before and
    after each use, and the pool itself - which arrives with its ``K - 1``
    rows outermost - is copied whole into a slot-major layout before the
    layer loop and back after it (PERF.md, PR 46). The layer's tails are
    read where they lie, **a row of every slot at a time**, into one table
    of rows beside the stream's and a row of zeros; a tap is one gather of
    rows from that table (a row's own earlier rows, its slot's tail, or
    zero where the sequence starts here), and so is row ``i`` of every
    slot's new tail, written back over the layer's ``[slots + 1, C]`` whole:
    a slot a live row names takes its sequence's, and **a slot no live row
    names** - padding's slot 0 among them - **takes its own row again, bit
    for bit** (``rows.row_of_slot``). No arithmetic but the taps' own."""
    T, C = stream.shape
    K = kernel.shape[0]
    NS = pool.shape[1]
    f32, i32 = jnp.float32, jnp.int32
    seq, first_row = rows.seq, rows.first_row
    moved = jnp.promote_types(stream.dtype, pool.dtype)
    held = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)       # [NS, K - 1, C]
    # the table: row k of slot ns at k * NS + ns, the stream's row t at base + t, then zeros
    base, zero = (K - 1) * NS, (K - 1) * NS + T
    table = jnp.concatenate([held[:, k].astype(moved) for k in range(K - 1)]
                            + [stream.astype(moved), jnp.zeros((1, C), moved)], axis=0)
    at = jnp.arange(T, dtype=i32)
    rank = at - first_row[seq]                                  # a row's index in its chunk
    starts, slot = rows.fresh[seq], rows.slot[seq]              # a row's sequence's
    acc = None if bias is None else bias.astype(f32)[None, :]
    kernel = kernel.astype(f32)
    for j in range(K):
        back = K - 1 - j                                     # tap j reads `back` rows back
        tap = stream
        if back:
            carried = jnp.where(starts, zero, jnp.clip(rank + j, 0, K - 2) * NS + slot)
            tap = table[jnp.where(rank >= back, base + at - back, carried)]
        term = kernel[j][None, :] * tap.astype(f32)
        acc = term if acc is None else acc + term
    # row i of the new tails, a slot a row: chunk row ``into`` of the slot's sequence, or
    # row ``length + i`` of the tail it carried
    of = rows.row_of_slot
    named = of < rows.n_rows - 1
    length, first, fresh = rows.length[of], first_row[of], rows.fresh[of]
    own = jnp.arange(NS, dtype=i32)
    for i in range(K - 1):
        into = length - (K - 1) + i
        kept = jnp.where(fresh, zero, jnp.clip(length + i, 0, K - 2) * NS + own)
        new = jnp.where(into >= 0, base + jnp.clip(first + into, 0, T - 1), kept)
        rows_i = table[jnp.where(named, new, i * NS + own)]
        pool = pool.at[layer, :, i].set(rows_i.astype(pool.dtype))
    return acc, pool


MAMBA_ROUND = 4     # sequences with more than one row in a step, taken this many at a time


def _mamba_mixer(ctx, p, layer, x, ssm, conv):
    """One Mamba-2 mixer over the flat ragged batch, on the normalised
    stream x [T, D]: the packed recurrence. → (y [T, D], ssm, conv).

    The convolution's tail is the sequence's slot (:func:`_conv_with_tail`).

    The recurrence is :func:`_packed_rows`' with the rows' own log-decays
    ``Delta_t A``: a row sees its chunk's earlier rows through the decay
    mask ``[H, T, T]`` (scores a group of heads, ``C_t . B_u``, once a
    group) and the state its sequence carried. A state is ``H x P x N`` =
    a million floats, so neither it is laid out a row nor the rows a
    sequence by a one-hot product over all ``S`` sequence rows, as the
    linear layers of :class:`SalaKind` can afford with 25 states of a
    sixteenth the size: **every sequence's first row of the step** - its
    only row, in a decode step - reads and updates the carried state in
    one visit of its sequence's slot, **one pass over the pool's layer
    where it lies** (``ops/pallas/ssm_state.ssm_state_step``: the pool
    aliased in and out of a kernel that fetches, uses and rewrites the
    slots this step's sequences own and no other; where the kernel does not
    run, ``xla_ssm_state_step``: the same by slot over the whole layer),
    and the sequences with further rows (prompt chunks: a few a step) take
    theirs ``MAMBA_ROUND`` sequences a round, by a one-hot product over
    those alone, for as many rounds as there are such sequences - their
    reads of the state they carried before that visit, their additions to
    the state the step leaves after it, into the pool by slot. Float32
    throughout; the matmuls at the default precision."""
    cfg = ctx.cfg
    T, S = x.shape[0], ctx.n_rows
    H, P, G, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size
    I, C, per = cfg.mamba_inner, cfg.conv_dim, cfg.mamba_num_heads // cfg.n_groups
    f32 = jnp.float32
    seq, pos, slot, first_row = ctx.seq, ctx.pos, ctx.slot, ctx.first_row
    zxbcdt = _proj(x, p["in_proj"])
    z, xbc, dt = zxbcdt[:, :I], zxbcdt[:, I:I + C], zxbcdt[:, I + C:]

    acc, conv = _conv_with_tail(xbc, p["conv_kernel"], p["conv_bias"], conv, layer, ctx)
    act = jax.nn.silu(acc)

    # ---- the recurrence
    xs = act[:, :I].reshape(T, H, P)
    b = act[:, I:I + G * N].reshape(T, G, N)
    c = act[:, I + G * N:].reshape(T, G, N)
    delta = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))   # [T, H]
    rows = _packed_rows(seq, pos, S, delta * -jnp.exp(p["A_log"].astype(f32)))
    v = delta[..., None] * xs                                            # [T, H, P]
    # the chunk's own rows: scores a group, the decay mask a head
    cb = jnp.einsum("tgn,ugn->gtu", c.astype(x.dtype), b.astype(x.dtype),
                    preferred_element_type=f32)
    att = (rows.weights.reshape(G, per, T, T) * cb[:, None]).reshape(H, T, T)
    y = jnp.einsum("htu,uhp->thp", att.astype(x.dtype), v.astype(x.dtype),
                   preferred_element_type=f32)

    # The carried states, one visit a slot (the docstring). The write is in place, so whatever
    # reads a sequence's *prior* state comes before it and whatever adds to the new one after:
    # the sequences with further rows, MAMBA_ROUND a round, twice over.
    here = ctx.here
    multi = ctx.length > 1
    n_rounds = (jnp.sum(multi.astype(jnp.int32)) + MAMBA_ROUND - 1) // MAMBA_ROUND
    order = jnp.concatenate([jnp.argsort(~multi, stable=True).astype(jnp.int32),
                             jnp.full((MAMBA_ROUND,), S - 1, jnp.int32)])
    row_ids = jnp.arange(T, dtype=jnp.int32)
    highest = jax.lax.Precision.HIGHEST

    def round_of(r):
        """→ (the round's sequence rows [R], whose further rows each row of the batch is
        [R, T]); a place past the last such sequence names padding's row and no row."""
        chosen = jax.lax.dynamic_slice_in_dim(order, r * MAMBA_ROUND, MAMBA_ROUND)
        rows_of = ((seq[None, :] == chosen[:, None]) & multi[chosen][:, None]
                   & (row_ids[None, :] != first_row[chosen][:, None])).astype(f32)
        return chosen, rows_of[:, :, None, None]

    since = jnp.exp(rows.since).reshape(T, G, per, 1)

    def reads(r, y):
        # their carried states, read from the pool again: these few. (Float32 operands as
        # they are: at the default precision XLA rounds the whole pool to bfloat16, a
        # round, before it gathers these from it.)
        chosen, rows_of = round_of(r)
        prior = jnp.where(ctx.fresh[chosen][:, None, None, None], 0.0, ssm[layer, slot[chosen]])
        return y + (jnp.einsum("rtgn,rgapn->tgap", rows_of * c[None],
                               prior.reshape(MAMBA_ROUND, G, per, P, N),
                               precision=highest) * since).reshape(T, H, P)

    y = jax.lax.fori_loop(0, n_rounds, reads, y)

    from deepspeed_tpu.ops.pallas import ssm_state
    impl = ssm_state.state_step_impl(ssm.shape, G, S)
    if ctx.choice is not None:
        ctx.choice.state_step[T] = impl
    step = ssm_state.ssm_state_step if impl == ssm_state.KERNEL else ssm_state.xla_ssm_state_step
    ssm, seen = step(ssm, layer, slot, ctx.fresh, here, c[first_row], b[first_row],
                     jnp.exp(rows.whole), jnp.exp(rows.until[first_row])[..., None] * v[first_row])
    seen = seen * jnp.where(here[:, None], jnp.exp(rows.since[first_row]), 0.0)[..., None]
    y = y.at[first_row].add(seen)

    v_left = (jnp.exp(rows.until)[..., None] * v).reshape(T, G, per, P)

    def writes(r, ssm):
        chosen, rows_of = round_of(r)
        return ssm.at[layer, slot[chosen]].add(
            jnp.einsum("rtgn,tgap->rgapn", rows_of * b[None], v_left,
                       precision=highest).reshape(MAMBA_ROUND, H, P, N))

    ssm = jax.lax.fori_loop(0, n_rounds, writes, ssm)

    y = y + p["D"].astype(f32)[None, :, None] * xs
    y = (y.reshape(T, I) * jax.nn.silu(z.astype(f32))).reshape(T, G, I // G)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg.layer_norm_epsilon)
    y = (y.reshape(T, I) * p["gate_norm"]["scale"].astype(f32)).astype(x.dtype)
    return _proj(y, p["out_proj"]), ssm, conv


def _plain_gqa_attention(cfg, p, layer, x, kc, vc, batch, attn_impl, softmax_scale=None):
    """A position-free attention mixer on the normalised stream
    (:class:`NemotronHKind`'s ``*`` layers, :class:`JambaKind`'s,
    :class:`SolarOpen2Kind`'s and :class:`GraniteHybridKind`'s attention
    layers: the recurrent layers carry the order): grouped-query attention
    over the paged pool's layer ``layer``, queries and keys as projected, no
    bias; where the layer has a ``gate_proj`` (Solar Open 2's
    ``use_gqa_gate``), its output times ``sigmoid(x W_gate)``, element-wise,
    before ``W_o``. ``softmax_scale``: what multiplies the scores where that
    is not ``1 / sqrt(d)`` (Granite's ``attention_multiplier``): the queries
    are scaled beforehand, as :func:`_gpt_layer_step` does, since every
    attention implementation divides by ``sqrt(d)``. → (y, kc, vc)."""
    T = x.shape[0]
    Hq, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _proj(x, p["q_proj"]).reshape(T, Hq, d)
    if softmax_scale is not None:
        q = (q.astype(jnp.float32) * (softmax_scale * math.sqrt(d))).astype(q.dtype)
    k = _proj(x, p["k_proj"]).reshape(T, Hkv, d)
    v = _proj(x, p["v_proj"]).reshape(T, Hkv, d)
    out, kc, vc = _paged_attend(q, k, v, kc, vc, layer, batch, d, impl=attn_impl)
    out = out.reshape(T, Hq * d)
    if "gate_proj" in p:
        gate = jax.nn.sigmoid(_proj(x, p["gate_proj"]).astype(jnp.float32))
        out = (out.astype(jnp.float32) * gate).astype(x.dtype)
    return _proj(out, p["o_proj"]), kc, vc


def _nemotron_moe(cfg, real, p, experts, layer, x):
    """One LatentMoE layer on the normalised stream, as this share gives it,
    and its ``SHARE_COUNTS``; ``real`` [T]: the rows that are not padding.
    The routed experts work in the latent ``u = x W_down``, ungated, and
    ``W_up`` leaves it; the shared expert on the full width."""
    y, counts = _routed_experts(x, NemotronHKind.router(cfg, p), experts, layer, real,
                                enter=p["latent_down"], leave=p["latent_up"])
    with jax.named_scope("ds.moe_shared"):
        s = p["shared_experts"]
        return y + _proj(relu2(_proj(x, s["up_proj"])), s["down_proj"]), counts


class Lfm2Kind(ModelKind):
    """LFM2-MoE (``models/lfm2.py``): **every layer an operator and a
    feed-forward** - the operator a gated short convolution or a
    grouped-query attention, as ``layer_types`` says; the feed-forward a
    dense SwiGLU in the leading layers, whole experts behind a biased
    sigmoid router after them - and state of two kinds side by side.

    - The ``full_attention`` operators keep keys and values (normalised a
      head, rotated) in the engine's two paged pools, ``[La, NB, bs, Hkv *
      d]``; the ``conv`` layers hold nothing there.
    - ``extra_state``'s ``conv`` ``[Lc, slots + 1, K - 1, D]`` in the
      stream's dtype: a ``conv`` operator's state a sequence, **the last
      ``K - 1`` rows of the gated stream** before the convolution, the same
      at token 10 and at token 100,000; slots as :class:`SalaKind`'s. The
      carry is :func:`_conv_with_tail`, the one Nemotron-H's Mamba mixers use.

    :meth:`stack` runs ``cfg.segments`` through :func:`_run_segments`: the
    leading layers one by one, one scan a period. The experts of every
    layer ride each step whole, one table of ``Le x E`` groups. Each step
    counts, over its tokens that are not padding: ``EXPERT_COUNTS`` (every
    pick held, none zero-compute), the rows through the ``conv`` operators, the (sequence,
    ``conv`` layer)s whose tail it read and wrote, and - once a step, not a
    layer - ``n_ctx_seq_tokens``: over the step's sequences, the context
    positions each attends to, counted **once a sequence** however many
    rows it has in the step: the least any implementation of an attention
    layer must fetch."""
    name = "lfm2"
    config = Lfm2MoeConfig
    state_kind = "kv+slots"
    step_counts = EXPERT_COUNTS + ("n_conv_rows", "n_tail_slots", "n_ctx_seq_tokens")
    seq_rows = 1            # (slot,)
    slot_state = ("conv",)
    experts_at = "moe_ffn"

    @staticmethod
    def state_layers(cfg):
        return max(1, cfg.count("full_attention"))

    @staticmethod
    def extra_state(cfg, num_blocks, slots, dtype):
        return {"conv": jnp.zeros((cfg.count("conv"), slots + 1, cfg.conv_L_cache - 1,
                                   cfg.hidden_size), dtype)}

    @staticmethod
    def _counters(letter):
        """The stacks a layer of ``cfg.letters``' letter draws from."""
        return ("conv" if letter in "cC" else "attn", "dense" if letter.isupper() else "moe")

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        Lfm2Kind.base_only(mesh, lora)
        model = params["model"]
        ctx = _SlotStep(cfg, batch, extra["conv"].shape[1])
        stacks = {"conv": model.get("conv_layers"), "attn": model.get("attn_layers"),
                  "dense": model.get("dense_ffn"), "moe": model.get(Lfm2Kind.experts_at, {})}
        experts = stacks["moe"].get("experts")

        def layer(letter, at, carry):
            h, kc, vc, conv, picks = carry
            op, ffn = Lfm2Kind._counters(letter)
            lp = _layer_of(stacks[op], at[op])
            x = _rms(h, lp["operator_norm"]["scale"], cfg.norm_eps)
            if op == "conv":
                with jax.named_scope("ds.lfm2.conv"):
                    y, conv = _lfm2_conv(ctx, lp, at[op], x, conv)
            else:
                with jax.named_scope("ds.lfm2.attn"):
                    y, kc, vc = _lfm2_attention(cfg, lp, at[op], x, kc, vc, batch, attn_impl)
            h = h + y
            fp = _layer_of(stacks[ffn], at[ffn])
            x = _rms(h, fp["ffn_norm"]["scale"], cfg.norm_eps)
            if ffn == "dense":
                with jax.named_scope("ds.dense_ffn"):
                    y = _swiglu(x, fp)
            else:
                y, n = _routed_experts(x, Lfm2Kind.router(cfg, fp), experts, at[ffn], ctx.real)
                picks = picks + n
            return h + y, kc, vc, conv, picks

        carry = (h, kc, vc, extra["conv"], jnp.zeros((3,), jnp.int32))
        (h, kc, vc, conv, picks), done = _run_segments(cfg.segments, Lfm2Kind._counters, layer,
                                                       carry)
        here = ctx.here.astype(jnp.int32)
        counts = jnp.concatenate([picks, jnp.stack([
            done.get("conv", 0) * jnp.sum(ctx.real.astype(jnp.int32)),
            done.get("conv", 0) * jnp.sum(here),
            jnp.sum(here * (ctx.first + ctx.length))])]).astype(jnp.int32)
        return h, kc, vc, {"conv": conv}, counts[None]

    @staticmethod
    def final_norm(params, cfg, h):
        return _rms(h, params["model"]["embedding_norm"]["scale"], cfg.norm_eps)

    @staticmethod
    def router(cfg, fp):
        """Moonlight's router, the matmul at the highest precision; no share."""
        gate = fp["gate"]
        return Router(gate["weight"], gate["expert_bias"], cfg.num_experts_per_tok,
                      cfg.routed_scaling_factor, eps=TOPK_EPS)

    @staticmethod
    def conv_layer(params, cfg, layer, x, conv, batch):
        """``conv`` operator ``layer`` (its index among the ``conv`` layers)
        alone - the same gates, the same reads and writes of the slot pool:
        x [T, D] the normalised stream → (y [T, D], conv)."""
        lp = _layer_of(params["model"]["conv_layers"], layer)
        return _lfm2_conv(_SlotStep(cfg, batch, conv.shape[1]), lp, layer, x, conv)

    @staticmethod
    def attention_layer(params, cfg, layer, x, kc, vc, batch, attn_impl=None):
        """``full_attention`` operator ``layer`` (its index among the
        attention layers) alone - the same norms, rotation, writes into the
        pools and paged attention: x [T, D] the normalised stream → (y
        [T, D], kc, vc)."""
        lp = _layer_of(params["model"]["attn_layers"], layer)
        return _lfm2_attention(cfg, lp, layer, x, kc, vc, batch, attn_impl)

    @staticmethod
    def expert_layer(params, cfg, layer, x):
        """Expert feed-forward ``layer`` (its index among the expert layers)
        alone: x [T, D] the normalised stream, every row a token → y."""
        moe = params["model"]["moe_ffn"]
        return _routed_experts(x, Lfm2Kind.router(cfg, _layer_of(moe, layer)), moe["experts"],
                               layer, jnp.ones(x.shape[0], bool))[0]


def _lfm2_conv(rows, p, layer, x, conv):
    """One gated short convolution on the normalised stream x [T, D]:
    ``[B | C | x'] = x W_in``; ``g = B * x'`` enters the convolution, whose
    tail is the sequence's slot (:func:`_conv_with_tail`: no bias, no
    activation); ``C`` gates what leaves it. → (y [T, D], conv)."""
    D = x.shape[1]
    bcx = _proj(x, p["in_proj"])
    gated = bcx[:, :D] * bcx[:, 2 * D:]
    v, conv = _conv_with_tail(gated, p["conv_kernel"], None, conv, layer, rows)
    y = (bcx[:, D:2 * D].astype(jnp.float32) * v).astype(x.dtype)
    return _proj(y, p["out_proj"]), conv


def _lfm2_attention(cfg, p, layer, x, kc, vc, batch, attn_impl):
    """One ``full_attention`` operator on the normalised stream:
    grouped-query attention over the paged pool's layer ``layer``, queries
    and keys normalised a head (one scale over every head's ``d``) and
    rotated in halves. → (y, kc, vc)."""
    T = x.shape[0]
    Hq, Hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = _rms(_proj(x, p["q_proj"]).reshape(T, Hq, d), p["q_layernorm"]["scale"], cfg.norm_eps)
    k = _rms(_proj(x, p["k_proj"]).reshape(T, Hkv, d), p["k_layernorm"]["scale"], cfg.norm_eps)
    v = _proj(x, p["v_proj"]).reshape(T, Hkv, d)
    pos = batch["token_pos"]
    q, k = _rope_at(q, pos, cfg.rope_theta), _rope_at(k, pos, cfg.rope_theta)
    out, kc, vc = _paged_attend(q, k, v, kc, vc, layer, batch, d, impl=attn_impl)
    return _proj(out.reshape(T, Hq * d), p["out_proj"]), kc, vc


class JambaKind(ModelKind):
    """Jamba (``models/jamba.py``): **every layer a mixer and a dense
    feed-forward** - the mixer a Mamba-1 state-space layer or, once a
    period, a position-free grouped-query attention
    (:func:`_plain_gqa_attention`, :class:`NemotronHKind`'s) - and state of
    two kinds side by side.

    - The attention layers keep keys and values in the engine's two paged
      pools, ``[La, NB, bs, Hkv * d]``; the Mamba layers hold nothing there.
    - ``extra_state``'s ``ssm`` ``[Lm, slots + 1, N, I]`` float32 and
      ``conv`` ``[Lm, slots + 1, K - 1, I]``: a Mamba layer's state a
      sequence - ``N`` state columns of every channel, the channels along
      the lanes, and the last ``K - 1`` rows of ``x`` before the
      convolution's activation (:func:`_conv_with_tail`) - the same at token
      10 and at token 200,000; slots as :class:`SalaKind`'s.

    The recurrence's decay is an element's own - ``exp(Delta_t[c] A[n, c])``,
    another for every channel, state column and token - so no mask over a
    chunk's rows expresses it (:func:`_packed_rows` is Mamba-2's): every row
    of a sequence passes through its slot's state in order
    (``ops/pallas/selective_scan.selective_scan``: the pool aliased in and
    out, a slot fetched once, its sequence's rows run through it in VMEM,
    written back once; ``xla_selective_scan`` where the kernel does not
    run; ``AttentionChoice.state_step`` says which a program got).

    :meth:`stack` runs ``cfg.segments`` - a run of layers of one kind is
    one scan - through :func:`_run_segments`. Each step counts, over its
    tokens that are not padding: the rows through the Mamba layers, the
    (sequence, Mamba layer)s whose state it read and wrote (the names
    :class:`NemotronHKind`'s records use), and ``n_scan_runs``, those of
    them with more than one row in the step: the runs a chunk is cut
    into."""
    name = "jamba"
    config = JambaConfig
    state_kind = "kv+slots"
    step_counts = ("n_ssm_rows", "n_state_slots", "n_scan_runs")
    seq_rows = 1            # (slot,)
    slot_state = ("ssm", "conv")

    @staticmethod
    def state_layers(cfg):
        return max(1, cfg.count("a"))

    @staticmethod
    def extra_state(cfg, num_blocks, slots, dtype):
        Lm = cfg.count("m")
        return {"ssm": jnp.zeros((Lm, slots + 1, cfg.mamba_d_state, cfg.mamba_inner),
                                 jnp.float32),
                "conv": jnp.zeros((Lm, slots + 1, cfg.mamba_d_conv - 1, cfg.mamba_inner), dtype)}

    @staticmethod
    def _counters(letter):
        """The stacks a layer of ``cfg.letters``' letter draws from."""
        return ("mamba" if letter == "m" else "attn", "ffn")

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        JambaKind.base_only(mesh, lora)
        model = params["model"]
        ctx = _SlotStep(cfg, batch, extra["conv"].shape[1], attn_impl)
        stacks = {"mamba": model.get("mamba_layers"), "attn": model.get("attn_layers"),
                  "ffn": model["ffn"]}

        def layer(letter, at, carry):
            h, kc, vc, ssm, conv = carry
            op, _ = JambaKind._counters(letter)
            lp = _layer_of(stacks[op], at[op])
            x = _rms(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
            if op == "mamba":
                with jax.named_scope("ds.jamba.mamba"):
                    y, ssm, conv = _jamba_mamba(ctx, lp, at[op], x, ssm, conv)
            else:
                with jax.named_scope("ds.jamba.attn"):
                    y, kc, vc = _plain_gqa_attention(cfg, lp, at[op], x, kc, vc, batch,
                                                     attn_impl)
            h = h + y
            fp = _layer_of(stacks["ffn"], at["ffn"])
            with jax.named_scope("ds.jamba.mlp"):
                h = h + _swiglu(_rms(h, fp["pre_ff_layernorm"]["scale"], cfg.rms_norm_eps), fp)
            return h, kc, vc, ssm, conv

        carry = (h, kc, vc, extra["ssm"], extra["conv"])
        (h, kc, vc, ssm, conv), done = _run_segments(cfg.segments, JambaKind._counters, layer,
                                                     carry)
        Lm = done.get("mamba", 0)
        counts = jnp.stack([Lm * jnp.sum(ctx.real.astype(jnp.int32)),
                            Lm * jnp.sum(ctx.here.astype(jnp.int32)),
                            Lm * jnp.sum((ctx.here & (ctx.length > 1)).astype(jnp.int32))])
        return h, kc, vc, {"ssm": ssm, "conv": conv}, counts.astype(jnp.int32)[None]

    @staticmethod
    def final_norm(params, cfg, h):
        return _rms(h, params["model"]["final_layernorm"]["scale"], cfg.rms_norm_eps)

    @staticmethod
    def mamba_layer(params, cfg, layer, x, ssm, conv, batch):
        """Mamba layer ``layer``'s mixer (its index among the Mamba layers)
        alone - the same convolution, scan and reads and writes of the
        slot pools: x [T, D] the normalised stream → (y [T, D], ssm, conv)."""
        lp = _layer_of(params["model"]["mamba_layers"], layer)
        return _jamba_mamba(_SlotStep(cfg, batch, conv.shape[1]), lp, layer, x, ssm, conv)

    @staticmethod
    def attention_layer(params, cfg, layer, x, kc, vc, batch, attn_impl=None):
        """Attention layer ``layer``'s mixer (its index among the attention
        layers) alone - the same writes into the pools and paged attention:
        x [T, D] the normalised stream → (y [T, D], kc, vc)."""
        lp = _layer_of(params["model"]["attn_layers"], layer)
        return _plain_gqa_attention(cfg, lp, layer, x, kc, vc, batch, attn_impl)


def _jamba_mamba(ctx, p, layer, x, ssm, conv):
    """One Mamba-1 mixer over the flat ragged batch, on the normalised
    stream x [T, D] → (y [T, D], ssm, conv). The convolution's tail is the
    sequence's slot (:func:`_conv_with_tail`: bias, SiLU after); ``dt``,
    ``B`` and ``C`` come off the convolved stream and through the family's
    three inner norms; the recurrence is the selective scan
    (:class:`JambaKind`'s docstring), float32 throughout: ``Delta``, the
    decays, the accumulation and ``S C``."""
    cfg = ctx.cfg
    I, N, R = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    f32, eps = jnp.float32, cfg.rms_norm_eps
    xz = _proj(x, p["in_proj"])
    acc, conv = _conv_with_tail(xz[:, :I], p["conv_kernel"], p["conv_bias"], conv, layer, ctx)
    xs = jax.nn.silu(acc)                                                # [T, I] float32
    dbc = _proj(xs.astype(x.dtype), p["x_proj"])
    dt = _rms(dbc[:, :R], p["dt_layernorm"]["scale"], eps)
    b = _rms(dbc[:, R:R + N], p["b_layernorm"]["scale"], eps).astype(f32)
    c = _rms(dbc[:, R + N:], p["c_layernorm"]["scale"], eps).astype(f32)
    delta = jax.nn.softplus(_proj(dt, p["dt_proj"]).astype(f32) + p["dt_bias"].astype(f32))

    from deepspeed_tpu.ops.pallas import selective_scan as scan
    impl = scan.scan_impl(ssm.shape, x.shape[0], ctx.n_rows)
    if ctx.choice is not None:
        ctx.choice.state_step[x.shape[0]] = impl
    run = scan.selective_scan if impl == scan.KERNEL else scan.xla_selective_scan
    with jax.named_scope("ds.jamba.scan"):
        ssm, y = run(ssm, layer, ctx.seq, ctx.slot, ctx.first_row,
                     jnp.where(ctx.here, ctx.length, 0), ctx.fresh, xs, delta, b, c,
                     -jnp.exp(p["A_log"].astype(f32)))
    y = (y + p["D"].astype(f32) * xs) * jax.nn.silu(xz[:, I:].astype(f32))
    return _proj(y.astype(x.dtype), p["out_proj"]), ssm, conv


class SolarOpen2Kind(ModelKind):
    """Solar Open 2 (``models/solar_open2.py``): **every layer a mixer and a
    routed feed-forward** - the mixer a Kimi-delta-attention (KDA) layer or,
    once a period of four, a gated position-free grouped-query attention
    (:func:`_plain_gqa_attention` with its ``gate_proj``); the feed-forward
    one share of an expert-parallel deployment behind a sigmoid router,
    beside a shared expert - and state of two kinds side by side.

    - The attention layers keep keys and values in the engine's two paged
      pools, ``[Lg, NB, bs, Hkv * d]``; the KDA layers hold nothing there.
    - ``extra_state``'s ``kda`` ``[Lk, slots + 1, H, d, d]`` float32 and
      ``conv`` ``[Lk, slots + 1, K - 1, 3 I]``: a KDA layer's state a
      sequence - a ``d x d`` matrix a head, key rows and value columns
      (4.19 MB a layer at 64 x 128 x 128: Nemotron-H's is 4.25),
      and the last ``K - 1`` rows of ``[W_q | W_k | W_v] a`` before the
      three convolutions' activation, side by side
      (:func:`_conv_with_tail`) - the same at token 10 and at token
      1,000,000; slots as :class:`SalaKind`'s.

    The recurrence's transition is **not diagonal** - ``(I - beta k k^T)
    Diag(alpha)``: a token decays every key row by a factor of its own,
    then rotates the state toward its key before it writes - so neither a
    decay mask (:func:`_packed_rows`) nor an element-wise scan expresses a
    chunk: every row of a sequence passes through its slot's state in order
    (``ops/pallas/kda.kda_delta_rule``: the pool aliased in and out, a slot
    fetched once, its sequence's rows run through it in VMEM, written back
    once - a row at a time on the vector unit where the sequence has few
    rows in the step, every decode row among them, and a block of 64 at a
    time in the rule's chunked (WY) form, float32 products on the matrix
    unit, where it has ``kda.MIN_CHUNK_RUN`` or more: a prompt chunk;
    ``xla_kda_delta_rule`` where the kernel does not run;
    ``AttentionChoice.state_step`` says which a program got).

    :meth:`stack` runs ``cfg.segments`` through :func:`_run_segments`. The
    routed experts ride every step whole, one table of ``L x held`` groups.
    Each step counts, over its tokens that are not padding:
    ``SHARE_COUNTS``, the rows through the KDA layers, the (sequence, KDA
    layer)s whose state it read and wrote (:class:`NemotronHKind`'s and
    :class:`JambaKind`'s name), ``n_scan_runs``, those of them with more
    than one row in the step, and ``n_kda_chunk_rows``, the rows of
    ``n_kda_rows`` that went through the rule's block form (``kda.chunk_rows``:
    what the kernel decides by, whichever implementation the program got)."""
    name = "solar_open2"
    config = SolarOpen2Config
    state_kind = "kv+slots"
    step_counts = SHARE_COUNTS + ("n_kda_rows", "n_state_slots", "n_scan_runs",
                                   "n_kda_chunk_rows")
    seq_rows = 1            # (slot,)
    slot_state = ("kda", "conv")
    experts_at = "moe"

    @staticmethod
    def state_layers(cfg):
        return max(1, cfg.count("g"))

    @staticmethod
    def extra_state(cfg, num_blocks, slots, dtype):
        Lk, H, d = cfg.count("k"), cfg.kda_heads, cfg.kda_head_dim
        return {"kda": jnp.zeros((Lk, slots + 1, H, d, d), jnp.float32),
                "conv": jnp.zeros((Lk, slots + 1, cfg.kda_conv - 1, 3 * cfg.kda_inner), dtype)}

    @staticmethod
    def _counters(letter):
        """The stacks a layer of ``cfg.letters``' letter draws from."""
        return ("kda" if letter == "k" else "gqa", "moe")

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        SolarOpen2Kind.base_only(mesh, lora)
        model = params["model"]
        ctx = _SlotStep(cfg, batch, extra["conv"].shape[1], attn_impl)
        stacks = {"kda": model.get("kda_layers"), "gqa": model.get("gqa_layers"),
                  "moe": model[SolarOpen2Kind.experts_at]}
        experts = stacks["moe"]["experts"]

        def layer(letter, at, carry):
            h, kc, vc, kda, conv, picks = carry
            op, _ = SolarOpen2Kind._counters(letter)
            lp = _layer_of(stacks[op], at[op])
            x = _rms(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
            if op == "kda":
                with jax.named_scope("ds.solar.kda"):
                    y, kda, conv = _solar_kda(ctx, lp, at[op], x, kda, conv)
            else:
                with jax.named_scope("ds.solar.attn"):
                    y, kc, vc = _plain_gqa_attention(cfg, lp, at[op], x, kc, vc, batch,
                                                     attn_impl)
            h = h + y
            fp = _layer_of(stacks["moe"], at["moe"])
            x = _rms(h, fp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
            y, n = _solar_moe(cfg, ctx.real, fp, experts, at["moe"], x)
            return h + y, kc, vc, kda, conv, picks + n

        carry = (h, kc, vc, extra["kda"], extra["conv"],
                 jnp.zeros((len(SHARE_COUNTS),), jnp.int32))
        (h, kc, vc, kda, conv, picks), done = _run_segments(
            cfg.segments, SolarOpen2Kind._counters, layer, carry)
        Lk = done.get("kda", 0)
        from deepspeed_tpu.ops.pallas.kda import chunk_rows
        length = jnp.where(ctx.here, ctx.length, 0)
        counts = jnp.concatenate([picks, jnp.stack([
            Lk * jnp.sum(ctx.real.astype(jnp.int32)),
            Lk * jnp.sum(ctx.here.astype(jnp.int32)),
            Lk * jnp.sum((ctx.here & (ctx.length > 1)).astype(jnp.int32)),
            Lk * jnp.sum(jnp.where(chunk_rows(length, h.shape[0]), length, 0))])])
        return h, kc, vc, {"kda": kda, "conv": conv}, counts.astype(jnp.int32)[None]

    @staticmethod
    def router(cfg, fp):
        """Sigmoid scores, the picks' weights over their sum; this rank's share."""
        gate = fp["gate"]
        return Router(gate["weight"], gate["e_score_correction_bias"], cfg.num_experts_per_tok,
                      cfg.routed_scaling_factor,
                      share=ExpertShare(cfg.first_expert_held, cfg.held, cfg.n_routed_experts))

    @staticmethod
    def kda_layer(params, cfg, layer, x, kda, conv, batch):
        """KDA layer ``layer``'s mixer (its index among the KDA layers) alone
        - the same convolutions, delta rule and reads and writes of the slot
        pools: x [T, D] the normalised stream → (y [T, D], kda, conv)."""
        lp = _layer_of(params["model"]["kda_layers"], layer)
        return _solar_kda(_SlotStep(cfg, batch, conv.shape[1]), lp, layer, x, kda, conv)

    @staticmethod
    def attention_layer(params, cfg, layer, x, kc, vc, batch, attn_impl=None):
        """Attention layer ``layer``'s mixer (its index among the attention
        layers) alone - the same writes into the pools, paged attention and
        gate: x [T, D] the normalised stream → (y [T, D], kc, vc)."""
        lp = _layer_of(params["model"]["gqa_layers"], layer)
        return _plain_gqa_attention(cfg, lp, layer, x, kc, vc, batch, attn_impl)

    @staticmethod
    def expert_layer(params, cfg, layer, x):
        """Routed feed-forward ``layer`` (the layer's position in the stack)
        alone (:func:`_layer_of`): x [T, D] the normalised stream, every row
        a token → y."""
        moe = params["model"]["moe"]
        return _solar_moe(cfg, jnp.ones(x.shape[0], bool), _layer_of(moe, layer),
                          moe["experts"], layer, x)[0]


def _solar_moe(cfg, real, fp, experts, layer, x):
    """One routed feed-forward on the normalised stream, as this share gives
    it, and its ``SHARE_COUNTS``; ``real`` [T]: the rows that are not
    padding. The held picks through the grouped matmul; the shared expert
    on every row."""
    y, counts = _routed_experts(x, SolarOpen2Kind.router(cfg, fp), experts, layer, real)
    with jax.named_scope("ds.moe_shared"):
        return y + _swiglu(x, fp["shared_experts"]), counts


def _solar_kda(ctx, p, layer, x, kda, conv):
    """One Kimi-delta-attention mixer over the flat ragged batch, on the
    normalised stream x [T, D] → (y [T, D], kda, conv). The three
    convolutions' tail is the sequence's slot (:func:`_conv_with_tail`: one
    call over ``q | k | v`` side by side, no bias, SiLU after); the decays
    and ``beta`` come off the stream through their projections with float32
    results (the decay's low-rank factor stays float32 into its second
    matrix, at the highest precision: [T, 128] x [128, I]); the recurrence
    is the delta rule (:class:`SolarOpen2Kind`'s docstring). Float32 throughout what the recurrence reads and does: the
    L2 norms, ``softplus``, ``beta``, the state, ``S^T q`` and the head
    norm."""
    cfg = ctx.cfg
    T = x.shape[0]
    H, d, I = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_inner
    f32 = jnp.float32

    def wide(low, p):   # a projection whose result is float32 (a float32 ``low``: exactly)
        return jnp.dot(low, p["kernel"].astype(low.dtype), preferred_element_type=f32,
                       precision=jax.lax.Precision.HIGHEST if low.dtype == f32 else None)

    acc, conv = _conv_with_tail(_proj(x, p["qkv_proj"]), p["conv_kernel"], None, conv, layer, ctx)
    act = jax.nn.silu(acc)                                               # [T, 3 I] float32
    q, k, v = (act[:, i * I:(i + 1) * I].reshape(T, H, d) for i in range(3))
    q = q * (jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(d))
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    # the decay compounds over a sequence: its two factors keep float32 between them
    step = jax.nn.softplus(wide(wide(x, p["f_a_proj"]), p["f_b_proj"])
                           + p["dt_bias"].astype(f32))
    log_alpha = -jnp.exp(p["A_log"].astype(f32))[:, None] * step.reshape(T, H, d)
    beta = 2.0 * jax.nn.sigmoid(wide(x, p["b_proj"]))                    # [T, H], in (0, 2)

    from deepspeed_tpu.ops.pallas import kda as rule
    impl = rule.delta_rule_impl(kda.shape, T, ctx.n_rows)
    if ctx.choice is not None:
        ctx.choice.state_step[T] = impl
    run = (functools.partial(rule.kda_delta_rule, one_row_runs=ctx.one_row_runs)
           if impl == rule.KERNEL else rule.xla_kda_delta_rule)
    with jax.named_scope("ds.solar.kda_state"):
        kda, o = run(kda, layer, ctx.seq, ctx.slot, ctx.first_row,
                     jnp.where(ctx.here, ctx.length, 0), ctx.fresh, q, k, v, log_alpha, beta)
    gate = jax.nn.sigmoid(wide(_proj(x, p["g_a_proj"]), p["g_b_proj"])
                          + p["g_b_proj"]["bias"].astype(f32))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_norm_eps)
    o = (o * p["o_norm"]["scale"].astype(f32)).reshape(T, I) * gate
    return _proj(o.astype(x.dtype), p["o_proj"]), kda, conv


class LagunaKind(ModelKind):
    """Laguna (``models/laguna.py``): **window and full attention layers 3 :
    1**, of different shapes - 64 query heads under a window of 512, 48 over
    the whole context, both over the same 8 key-value heads of 128 - each
    with a sigmoid gate a head on its output and a routed feed-forward
    behind it (a dense SwiGLU in the leading layer), and **a cache of two
    lifetimes**:

    - the full layers keep keys and values in the engine's two paged pools,
      ``[Lf, NB, bs, Hkv * d]``, under the sequence's block table, as every
      ``kv`` kind does;
    - the window layers keep theirs in the step's ``extra``, ``wk`` / ``wv``
      ``[Lw, NBw, bs, Hkv * d]``, the **window pool**
      (``ragged/kv_cache.WindowPool``), under a table of its own: a ring a
      sequence, ``seq_state`` [S, ring], from which the blocks that fall
      wholly behind ``pos - window + 1`` go back to the allocator after each
      step. A sequence holds ``ceil(window / bs) + 1`` of them at any length
      where a table over its context would hold ``pos / bs``; a row reads
      the blocks its window touches and no other (:func:`_paged_attend`'s
      ``window``: ``paged_attention.window_tables``, the kernel's call named
      ``paged_window_attention`` in a device trace).

    Why pools and not a ring of rows a slot in ``extra_state`` (the other
    form ISSUE 52 allowed): a ring is laid for the widest step - ``window +
    token_budget`` rows a tracked sequence, 17 blocks where a decoding
    sequence needs 9 - whatever the sequences do, and a prompt chunk's rows
    would wrap in it mid-tile; blocks from an allocator cost a sequence what
    it holds, the paged kernel reads them as it reads any block, and the
    gate counts them as it counts the others.

    The two kinds of layer rotate differently (:func:`_laguna_rope`: the
    full layers the first half of a head's columns at YaRN's frequencies,
    cos and sin times the attention factor; the window layers all columns,
    plain), so a step computes two pairs of cos and sin rows, once.
    :meth:`stack` runs ``cfg.segments`` through :func:`_run_segments`; the
    routed experts are one share of an expert-parallel deployment and ride
    every step whole. Each step counts, over its tokens that are not
    padding: ``SHARE_COUNTS``; ``n_ctx_seq_tokens`` (:class:`Lfm2Kind`'s:
    the context positions each of the step's sequences attends to in a full
    layer, once a sequence); ``n_win_seq_tokens``, the same for a window
    layer - the positions from its first row's lower bound to its last row:
    the least a window layer must fetch."""
    name = "laguna"
    config = LagunaConfig
    state_kind = "kv+window"
    step_counts = SHARE_COUNTS + ("n_ctx_seq_tokens", "n_win_seq_tokens")
    experts_at = "moe"

    @staticmethod
    def state_layers(cfg):
        return max(1, cfg.count(laguna.FULL))

    @staticmethod
    def window(cfg):
        Lw = cfg.count(laguna.WINDOW)
        return (cfg.sliding_window, Lw) if Lw else None

    @staticmethod
    def _counters(letter):
        """The stacks a layer of ``cfg.letters``' letter draws from."""
        return (laguna.FULL if letter in "fF" else laguna.WINDOW,
                "dense" if letter.isupper() else "moe")

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        LagunaKind.base_only(mesh, lora)
        model = params["model"]
        stacks = {**{kind: model.get(name) for kind, name in laguna.STACKS.items()},
                  "dense": model.get("dense_ffn"), "moe": model.get(LagunaKind.experts_at, {})}
        experts = stacks["moe"].get("experts")
        S = batch["block_tables"].shape[0]
        real = batch["token_seq"] < S - 1
        rope = {kind: _laguna_rope(cfg, kind, batch["token_pos"]) for kind in laguna.STACKS
                if stacks[kind] is not None}

        def layer(letter, at, carry):
            h, kc, vc, wk, wv, picks = carry
            op, ffn = LagunaKind._counters(letter)
            lp = _layer_of(stacks[op], at[op])
            x = _rms(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
            if op == laguna.FULL:
                with jax.named_scope("ds.laguna.full_attn"):
                    y, kc, vc = _laguna_attention(cfg, op, lp, at[op], x, kc, vc, batch,
                                                  attn_impl, rope[op])
            else:
                with jax.named_scope("ds.laguna.window_attn"):
                    y, wk, wv = _laguna_attention(cfg, op, lp, at[op], x, wk, wv, batch,
                                                  attn_impl, rope[op])
            h = h + y
            fp = _layer_of(stacks[ffn], at[ffn])
            x = _rms(h, fp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
            if ffn == "dense":
                with jax.named_scope("ds.dense_ffn"):
                    y = _swiglu(x, fp)
            else:
                y, n = _laguna_moe(cfg, real, fp, experts, at[ffn], x)
                picks = picks + n
            return h + y, kc, vc, wk, wv, picks

        extra = extra or {"wk": None, "wv": None}
        carry = (h, kc, vc, extra["wk"], extra["wv"],
                 jnp.zeros((len(SHARE_COUNTS),), jnp.int32))
        (h, kc, vc, wk, wv, picks), _ = _run_segments(cfg.segments, LagunaKind._counters, layer,
                                                      carry)
        first, length = _row_spans(batch["token_seq"], batch["token_pos"], S)
        here = ((length > 0) & (jnp.arange(S) < S - 1)).astype(jnp.int32)
        end = first + length
        behind = jnp.maximum(first - (cfg.sliding_window - 1), 0)
        counts = jnp.concatenate([picks, jnp.stack([
            jnp.sum(here * end), jnp.sum(here * (end - behind))])]).astype(jnp.int32)
        return h, kc, vc, (None if wk is None else {"wk": wk, "wv": wv}), counts[None]

    @staticmethod
    def router(cfg, fp):
        """Sigmoid scores, the picks' weights over their sum times the scaling
        factor (:class:`MoonlightKind`'s form at the highest precision); this
        rank's share."""
        gate = fp["gate"]
        return Router(gate["weight"], gate["e_score_correction_bias"], cfg.num_experts_per_tok,
                      cfg.moe_routed_scaling_factor,
                      share=ExpertShare(cfg.first_expert_held, cfg.held, cfg.num_experts))

    @staticmethod
    def attention_layer(params, cfg, kind, layer, x, kc, vc, batch, attn_impl=None):
        """Attention layer ``layer`` of ``kind`` (``laguna.FULL`` / ``WINDOW``;
        its index among that kind's layers) alone - the same projections,
        rotation, writes into its pool (``kc`` / ``vc``: the full pools or the
        window pool's arrays), paged attention and gate: x [T, D] the
        normalised stream → (y [T, D], kc, vc)."""
        lp = _layer_of(params["model"][laguna.STACKS[kind]], layer)
        return _laguna_attention(cfg, kind, lp, layer, x, kc, vc, batch, attn_impl,
                                 _laguna_rope(cfg, kind, batch["token_pos"]))

    @staticmethod
    def expert_layer(params, cfg, layer, x):
        """Routed feed-forward ``layer`` (its index among the routed layers)
        alone (:func:`_layer_of`): x [T, D] the normalised stream, every row
        a token → y."""
        moe = params["model"]["moe"]
        return _laguna_moe(cfg, jnp.ones(x.shape[0], bool), _layer_of(moe, layer),
                           moe["experts"], layer, x)[0]


def _laguna_rope(cfg, kind, positions):
    """→ (cos, sin) [T, r / 2] float32 of a ``kind`` layer at this batch's
    ``positions``: ``r`` the rotated columns of a head, the kind's
    frequencies (``LagunaConfig.rope``: YaRN's for a full layer), times its
    attention factor."""
    inv_freq, factor = cfg.rope(kind)
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def _laguna_attention(cfg, kind, p, layer, x, kc, vc, batch, attn_impl, rope):
    """One Laguna attention mixer on the normalised stream: grouped-query
    attention over layer ``layer`` of the pool it is given - a full layer's
    over the sequence's context, a window layer's over the last
    ``sliding_window`` positions of the window pool - queries and keys
    rotated over their first ``r`` columns by halves, the rest as projected;
    the output times ``sigmoid(x W_g)`` **a head** before ``W_o``. Not
    :func:`_plain_gqa_attention` (position-free, a gate an element) nor
    :func:`_lfm2_attention` (head norms, no gate): the third mixer has a
    rotation of part of a head, a head count by kind and a window, which
    neither knows. → (y, kc, vc)."""
    T = x.shape[0]
    Hq, Hkv, d = cfg.heads(kind), cfg.num_key_value_heads, cfg.head_dim
    cos, sin = (t[:, None, :] for t in rope)
    r = 2 * cos.shape[-1]

    def rotated(t):
        t32 = t.astype(jnp.float32)
        t1, t2 = t32[..., :r // 2], t32[..., r // 2:r]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin, t32[..., r:]],
                               axis=-1).astype(t.dtype)

    q = rotated(_proj(x, p["q_proj"]).reshape(T, Hq, d))
    k = rotated(_proj(x, p["k_proj"]).reshape(T, Hkv, d))
    v = _proj(x, p["v_proj"]).reshape(T, Hkv, d)
    out, kc, vc = _paged_attend(q, k, v, kc, vc, layer, batch, d, impl=attn_impl,
                                window=cfg.sliding_window if kind == laguna.WINDOW else None)
    gate = jax.nn.sigmoid(_proj(x, p["g_proj"]).astype(jnp.float32))          # [T, Hq]
    out = (out.astype(jnp.float32) * gate[:, :, None]).astype(x.dtype)
    return _proj(out.reshape(T, Hq * d), p["o_proj"]), kc, vc


def _laguna_moe(cfg, real, fp, experts, layer, x):
    """One routed feed-forward on the normalised stream, as this share gives
    it, and its ``SHARE_COUNTS``; ``real`` [T]: the rows that are not
    padding. The held picks through the grouped matmul; the shared expert on
    every row."""
    y, counts = _routed_experts(x, LagunaKind.router(cfg, fp), experts, layer, real)
    with jax.named_scope("ds.moe_shared"):
        return y + _swiglu(x, fp["shared_experts"]), counts


class OuroKind(ModelKind):
    """Ouro (``models/ouro.py``): **one stack of ``L`` layers run ``R =
    total_ut_steps`` times with the same weights**, every pass writing keys
    and values of its own - so **the pools are ``R x L`` layers deep under
    ``L`` layers of parameters**: pass ``u``, layer ``l`` reads
    ``layers[l]`` and reads and writes pool layer ``u L + l`` (the published
    cache index), never another pass's. Every other kind's pool layer and
    weight layer are one index; here the scan's ``xs`` pair ``u L +
    arange(L)`` with the stack.

    :meth:`passes` is **a Python loop of ``R`` scans over the layers**, each
    with the same stack as its ``xs``: ``R`` loops in the program that read
    a layer's matrices as :class:`LlamaKind`'s one loop does, the pools
    carried through all of them and written in place, and what no pass
    changes (the rows' rotation, the gate's vector) computed once before
    them (:meth:`_pass`). Not a scan over the passes of that scan: with the
    stack invariant to an outer loop, the chip's compiler hoists the
    relayout it wants of ``q_proj`` and ``k_proj`` out of both - two copies
    of a whole stack (2 x 403 MB at the published size) every step and 815
    MB of temporaries, where this form has 8 MB. The choice is the
    compiler's and a small thing moves it: with the gate's vector cut out
    of its matrix inside every pass, this form too compiled to the two
    copies and 807 MB (PERF.md, PR 54: all three compiled for the chip, the
    last also measured on it). A block has four norms - each sublayer's
    output is normed before it joins the residual (``input_layernorm_2``,
    ``post_attention_layernorm_2``) - and the model's norm closes **every**
    pass, so what enters pass ``u + 1`` is normed and :meth:`final_norm` has
    nothing left to do. After each pass the exit gate reads the normed stream
    (``sigmoid(x_u w_g + b_g)``, float32); :meth:`stack` sends to the head,
    row by row, the ``x_u`` of the first pass whose cumulative exit
    probability reaches ``early_exit_threshold`` (``ouro.exit_steps``; at the
    published threshold 1 the last). All ``R`` passes run for every row, as
    the published code's do: the threshold selects, it skips no compute.

    Adapters, weight-only quantization and a mesh are refused by name at
    construction (``refuses``: the adapters' slabs are ``L`` deep and a row
    meets each ``R`` times; neither of the others is tested here); the prefix
    cache, the KV tier and drafting read the pools through the cache's own
    ``R L`` layers and serve. Each step counts: ``n_stack_passes`` (``R``:
    what an adaptive exit would lower), ``n_loop_token_layers`` (rows that
    are not padding x passes x layers) and ``n_exit_early_rows`` (such rows
    whose exit step is under ``R - 1``: 0 at threshold 1)."""
    name = "ouro"
    config = OuroConfig
    step_counts = ("n_stack_passes", "n_loop_token_layers", "n_exit_early_rows")
    refuses = ("LoRA serving", "weight-only quantization", "tensor/expert-parallel sharding")

    @staticmethod
    def state_layers(cfg):
        return cfg.state_layers

    @staticmethod
    def pool_layers(cfg, u):
        """→ [L] int32: the pool layers pass ``u`` reads and writes, layer
        ``l``'s at ``l`` (the published ``current_ut * num_hidden_layers +
        layer_idx``)."""
        L = cfg.num_hidden_layers
        return u * L + jnp.arange(L, dtype=jnp.int32)

    @staticmethod
    def _pass(params, cfg, batch, attn_impl):
        """→ ``one_pass(u, h, kc, vc)`` → (x_u, g_u, kc, vc), with what no pass
        changes computed here, once: the rows' rotation, the gate's vector."""
        model, eps = params["model"], cfg.rms_norm_eps
        rope = tuple(t[:, None, :]
                     for t in _rope_rows(batch["token_pos"], cfg.head_dim, cfg.rope_theta))
        step = functools.partial(_ouro_layer_step, cfg, rope, batch, attn_impl)
        gate = model["early_exit_gate"]
        w, b = gate["kernel"][:, 0].astype(jnp.float32), gate["bias"][0].astype(jnp.float32)

        def one_pass(u, h, kc, vc):
            (h, kc, vc), _ = jax.lax.scan(step, (h, kc, vc),
                                          (OuroKind.pool_layers(cfg, u), model["layers"]))
            with jax.named_scope("ds.ouro.loop_norm"):
                h = _rms(h, model["norm"]["scale"], eps)
            with jax.named_scope("ds.ouro.gate"):
                # [T, D] x [D, 1] as a product and a sum a row: float32, whatever a
                # backend makes of a float32 matmul
                g = jax.nn.sigmoid(jnp.sum(h.astype(jnp.float32) * w, axis=-1) + b)
            return h, g, kc, vc

        return one_pass

    @staticmethod
    def run_pass(params, cfg, u, h, kc, vc, batch, attn_impl=None):
        """Pass ``u`` (may be traced) of the stack alone: h [T, D], the stream
        that enters it → (x_u [T, D]: what the model's norm leaves of it, g_u
        [T] float32: its gate, kc, vc). The check hooks' way to run one pass
        **on a given stream** (the seeded weights amplify a difference ~2.6
        times a pass: PERF.md, PR 54); :meth:`passes`' own step."""
        return OuroKind._pass(params, cfg, batch, attn_impl)(u, h, kc, vc)

    @staticmethod
    def passes(params, cfg, h, kc, vc, batch, attn_impl=None):
        """h [T, D] (the embedded rows) through all ``R`` passes → (x [R, T,
        D]: every pass's normed stream ``x_u``, g [R, T] float32: its gate,
        kc, vc)."""
        one_pass = OuroKind._pass(params, cfg, batch, attn_impl)
        x, g = [], []
        for u in range(cfg.total_ut_steps):
            h, g_u, kc, vc = one_pass(u, h, kc, vc)
            x.append(h)
            g.append(g_u)
        return jnp.stack(x), jnp.stack(g), kc, vc

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        OuroKind.base_only(mesh, lora)
        R = cfg.total_ut_steps
        x, g, kc, vc = OuroKind.passes(params, cfg, h, kc, vc, batch, attn_impl)
        exit_step = ouro.exit_steps(g, cfg.early_exit_threshold)               # [T]
        h = jnp.take_along_axis(x, exit_step[None, :, None], axis=0)[0]
        real = batch["token_seq"] < batch["block_tables"].shape[0] - 1
        rows = jnp.sum(real.astype(jnp.int32))
        counts = jnp.stack([jnp.int32(R), rows * (R * cfg.num_hidden_layers),
                            jnp.sum((real & (exit_step < R - 1)).astype(jnp.int32))])
        return h, kc, vc, extra, counts.astype(jnp.int32)[None]

    @staticmethod
    def final_norm(params, cfg, h):
        """The model's norm closed the pass whose stream this is."""
        return h


def _ouro_layer_step(cfg, rope, batch, attn_impl, carry, xs):
    """One Ouro block over the flat ragged batch: ``rope`` = (cos, sin) [T, 1,
    d / 2] of the step's rows, ``xs`` = (the **pool's** layer ``u L + l``,
    layer ``l``'s parameters). Not :func:`_layer_step`:
    four norms where that knows two, and neither adapters nor a mesh."""
    h, kc, vc = carry
    layer, lp = xs
    T = h.shape[0]
    H, Hkv, d, eps = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                      cfg.rms_norm_eps)
    attn = lp["self_attn"]
    with jax.named_scope("ds.ouro.attn"):
        x = _rms(h, lp["input_layernorm"]["scale"], eps)
        q = _rotate_halves(_proj(x, attn["q_proj"]).reshape(T, H, d), *rope)
        k = _rotate_halves(_proj(x, attn["k_proj"]).reshape(T, Hkv, d), *rope)
        v = _proj(x, attn["v_proj"]).reshape(T, Hkv, d)
        out, kc, vc = _paged_attend(q, k, v, kc, vc, layer, batch, d, impl=attn_impl)
        h = h + _rms(_proj(out.reshape(T, H * d), attn["o_proj"]),
                     lp["input_layernorm_2"]["scale"], eps)
    with jax.named_scope("ds.ouro.mlp"):
        x = _rms(h, lp["post_attention_layernorm"]["scale"], eps)
        h = h + _rms(_swiglu(x, lp["mlp"]), lp["post_attention_layernorm_2"]["scale"], eps)
    return (h, kc, vc), None


class GraniteHybridKind(ModelKind):
    """Granite 4.0-H (``models/granite_hybrid.py``): **every layer a mixer
    and a routed feed-forward**, each added to the stream times
    ``residual_multiplier`` - the mixer :class:`NemotronHKind`'s Mamba-2
    layer (:func:`_mamba_mixer`, here in **one group**: every head reads the
    same ``B`` and ``C`` row) or, once a period, the position-free attention
    (:func:`_plain_gqa_attention`, scores times ``attention_multiplier``);
    the feed-forward small SwiGLU experts behind a router that takes the
    softmax over its picks (:func:`_routed_experts`), one share of an
    expert-parallel deployment, beside a shared SwiGLU. State as
    :class:`NemotronHKind`'s: the attention layers' keys and values in the two
    paged pools ``[La, NB, bs, Hkv * d]``, and ``extra_state``'s ``ssm``
    ``[Lm, slots + 1, H, P, N]`` float32 and ``conv`` ``[Lm, slots + 1, K - 1,
    C]``, a slot a sequence.

    The vocabulary is tied: ``ragged_forward`` multiplies the embedding rows
    by ``embedding_multiplier`` and takes the head from the same matrix;
    :meth:`final_norm` divides by ``logits_scaling`` (16: a power of two, so
    dividing the normalised row is dividing the logits, bit for bit).

    **The slot may be snapshotted** (``snapshots``): a slot's content after
    exactly ``p`` tokens is a function of those tokens alone and a row at
    position 0 is the only one that ignores it, so a copy taken at a block
    boundary, restored into another sequence's slot, lets that sequence start
    at ``p`` (``prefix_cache/manager.py``). Each step counts ``SHARE_COUNTS``,
    the rows through the mamba layers and the (sequence, mamba layer)s whose
    state it read and wrote, as :class:`NemotronHKind` does, and of those the
    ones whose sequence starts in the step (``n_fresh_slots``: written, not read)."""
    name = "granite_hybrid"
    config = GraniteHybridConfig
    state_kind = "kv+slots"
    step_counts = SHARE_COUNTS + ("n_ssm_rows", "n_state_slots", "n_fresh_slots")
    seq_rows = 1            # (slot,)
    slot_state = ("ssm", "conv")
    snapshots = True
    snapshot_scope = "ds.granite.snapshot"
    experts_at = "moe_layers"

    @staticmethod
    def state_layers(cfg):
        return max(1, cfg.count("attention"))

    @staticmethod
    def extra_state(cfg, num_blocks, slots, dtype):
        Lm = cfg.count("mamba")
        return {"ssm": jnp.zeros((Lm, slots + 1, cfg.mamba_n_heads, cfg.mamba_d_head,
                                  cfg.mamba_d_state), jnp.float32),
                "conv": jnp.zeros((Lm, slots + 1, cfg.mamba_d_conv - 1, cfg.conv_dim), dtype)}

    @staticmethod
    def _counters(letter):
        """The stacks a layer of ``cfg.letters``' letter draws from."""
        return (letter, "f")

    @staticmethod
    def stack(params, cfg, h, kc, vc, extra, batch, dtype, mesh, attn_impl, lora):
        GraniteHybridKind.base_only(mesh, lora)
        model = params["model"]
        ctx = _SlotStep(cfg, batch, extra["conv"].shape[1], attn_impl)
        stacks = {"m": model.get("mamba_layers"), "a": model.get("attn_layers"),
                  "f": model[GraniteHybridKind.experts_at]}
        experts = stacks["f"]["experts"]
        r = jnp.asarray(cfg.residual_multiplier, h.dtype)

        def layer(letter, at, carry):
            h, kc, vc, ssm, conv, picks = carry
            i = at[letter]
            lp = _layer_of(stacks[letter], i)
            x = _rms(h, lp["norm"]["scale"], cfg.rms_norm_eps)
            if letter == "m":
                with jax.named_scope("ds.granite.mamba"):
                    y, ssm, conv = _mamba_mixer(ctx, lp, i, x, ssm, conv)
            else:
                with jax.named_scope("ds.granite.attn"):
                    y, kc, vc = _plain_gqa_attention(cfg, lp, i, x, kc, vc, batch, attn_impl,
                                                     softmax_scale=cfg.attention_multiplier)
            h = h + r * y
            fp = _layer_of(stacks["f"], at["f"])
            with jax.named_scope("ds.granite.moe"):
                y, n = _granite_moe(cfg, ctx.real, fp, experts, at["f"],
                                    _rms(h, fp["norm"]["scale"], cfg.rms_norm_eps))
            return h + r * y, kc, vc, ssm, conv, picks + n

        carry = (h, kc, vc, extra["ssm"], extra["conv"],
                 jnp.zeros((len(SHARE_COUNTS),), jnp.int32))
        carry, done = _run_segments(cfg.segments, GraniteHybridKind._counters, layer, carry)
        h, kc, vc, ssm, conv, picks = carry
        mamba = done.get("m", 0)
        counts = jnp.concatenate([picks, jnp.stack([
            mamba * jnp.sum(ctx.real.astype(jnp.int32)),
            mamba * jnp.sum(ctx.here.astype(jnp.int32)),
            mamba * jnp.sum((ctx.here & ctx.fresh).astype(jnp.int32))])]).astype(jnp.int32)
        return h, kc, vc, {"ssm": ssm, "conv": conv}, counts[None]

    @staticmethod
    def final_norm(params, cfg, h):
        return _rms(h, params["model"]["norm"]["scale"], cfg.rms_norm_eps) \
            * jnp.asarray(1.0 / cfg.logits_scaling, h.dtype)

    @staticmethod
    def router(cfg, fp):
        """The softmax over the picks: over every column, the picks' weights over
        their sum (``exp(l_j) / Z`` over ``sum_picks exp(l_i) / Z``); no bias,
        no scale; this rank's share."""
        weight = fp["router"]["weight"]
        return Router(weight, jnp.zeros((weight.shape[-1],), jnp.float32),
                      cfg.num_experts_per_tok, 1.0, score=jax.nn.softmax,
                      share=ExpertShare(cfg.first_expert_held, cfg.held, cfg.num_local_experts))

    @staticmethod
    def expert_layer(params, cfg, layer, x):
        """Layer ``layer``'s feed-forward alone (:func:`_layer_of`): x [T, D] the
        normalised stream, every row a token → y (the routed share and the
        shared expert, before the residual multiplier)."""
        moe = params["model"]["moe_layers"]
        return _granite_moe(cfg, jnp.ones(x.shape[0], bool), _layer_of(moe, layer),
                            moe["experts"], layer, x)[0]

    @staticmethod
    def mamba_layer(params, cfg, layer, x, ssm, conv, batch):
        """``mamba`` layer ``layer``'s mixer (its index among the mamba layers)
        alone - the same packed recurrence, the same reads and writes of the
        slot pool: x [T, D] the normalised stream → (y [T, D], ssm, conv)."""
        lp = _layer_of(params["model"]["mamba_layers"], layer)
        return _mamba_mixer(_SlotStep(cfg, batch, conv.shape[1]), lp, layer, x, ssm, conv)

    @staticmethod
    def attention_layer(params, cfg, layer, x, kc, vc, batch, attn_impl=None):
        """Attention mixer ``layer`` (its index among the attention layers) alone."""
        lp = _layer_of(params["model"]["attn_layers"], layer)
        return _plain_gqa_attention(cfg, lp, layer, x, kc, vc, batch, attn_impl,
                                    softmax_scale=cfg.attention_multiplier)


def _granite_moe(cfg, real, fp, experts, layer, x):
    """One Granite feed-forward on the normalised stream, as this share gives
    it, and its ``SHARE_COUNTS``; ``real`` [T]: the rows that are not padding."""
    y, counts = _routed_experts(x, GraniteHybridKind.router(cfg, fp), experts, layer, real)
    with jax.named_scope("ds.moe_shared"):
        return y + _swiglu(x, fp["shared_experts"]), counts


# Every kind, a kind whose config class derives another's before that one's.
KINDS = (GraniteHybridKind, OuroKind, LagunaKind, SolarOpen2Kind, JambaKind, Lfm2Kind, NemotronHKind, SalaKind, LongcatKind,
         MoonlightKind, GPTKind, LlamaKind)


def kind_of(cfg):
    """→ the first of ``KINDS`` whose ``config`` class ``cfg`` is an instance of."""
    for kind in KINDS:
        if isinstance(cfg, kind.config):
            return kind
    raise TypeError(f"no model kind serves a {type(cfg).__name__}")


def _rope_deinterleaved(x, cos, sin, positions):
    """``modeling_deepseek.apply_rotary_pos_emb``: the pairs (2i, 2i+1) of
    the last dim go to (i, i + d/2), then rotate by halves."""
    T, H, d = x.shape
    x = x.reshape(T, H, d // 2, 2).swapaxes(-1, -2).reshape(T, H, d)
    return _rope_flat(x, cos, sin, positions)


def _latent_attend(q_lat, q_rope, c_kv, k_rope, kc, vc, layer, batch, scale, impl):
    """Scatter layer ``layer``'s new latent rows into the two pools (in
    place, as :func:`_paged_attend` does) and attend with absorbed
    weights: per head ``softmax((q_lat . c + q_rope . k_rope) * scale) @
    c`` over the token's block-tabled context, every head reading the
    same rows. → (o_lat [T, H, rank], kc, vc)."""
    bs, rank, lanes = kc.shape[2], kc.shape[3], vc.shape[3]
    T, H = q_lat.shape[:2]
    blk = batch["block_tables"][batch["token_seq"], batch["token_pos"] // bs]
    off = batch["token_pos"] % bs
    kc = kc.at[layer, blk, off].set(c_kv.astype(kc.dtype))
    pad = jnp.zeros((T, lanes - k_rope.shape[-1]), vc.dtype)
    vc = vc.at[layer, blk, off].set(jnp.concatenate([k_rope.astype(vc.dtype), pad], axis=-1))

    from deepspeed_tpu.inference.v2.modules.heuristics import instantiate_attn
    tab = batch["block_tables"][batch["token_seq"]]
    # one query row per head over the whole pooled row; pre-scaled, so that no
    # implementation has to know the 192 of the softmax scale
    q = jnp.concatenate([q_lat, q_rope, jnp.zeros((T, H, lanes - q_rope.shape[-1]), q_lat.dtype)],
                        axis=-1) * jnp.asarray(scale, q_lat.dtype)
    name, attn_fn = instantiate_attn(None, rank, bs, q.shape, kc.shape, None,
                                     max_blocks=tab.shape[1],
                                     override=impl.override if impl else None,
                                     state_kind="latent")
    if impl is not None:
        impl.selected[T] = name
    return attn_fn(q, kc, vc, tab, batch["token_pos"], layer, _live_rows(batch)), kc, vc


def _swiglu(x, p):
    return _proj(jax.nn.silu(_proj(x, p["gate_proj"])) * _proj(x, p["up_proj"]), p["down_proj"])


def _moonlight_moe(x, p, experts, layer, cfg):
    """The routed experts (:func:`_routed_experts`: ``experts`` every
    expert layer's, ``layer`` this one's index among them), then the shared
    experts on every token."""
    routed, _ = _routed_experts(x, MoonlightKind.router(cfg, p), experts, layer)
    with jax.named_scope("ds.moe_shared"):
        return routed + _swiglu(x, p["shared_experts"])


def _latent_attention(cfg, attn, norm_scale, h, rope, kc, vc, layer, batch, attn_impl):
    """``h + o_proj(latent attention(RMS(h)))`` with ``kv_b_proj``
    absorbed, writing state layer ``layer``: the attention half of a
    Moonlight layer and of either half of a LongCat double layer. → (h,
    kc, vc).

    The query is full-rank (``attn["q_proj"]``) or low-rank
    (``q_b_proj(RMS(q_a_proj(x)))``), whichever the layer's params hold.
    ``cfg.query_scale`` (LongCat's ``mla_scale_q_lora``) multiplies the
    query, which enters the scores linearly, so it rides the softmax
    scale; ``cfg.latent_scale`` (``mla_scale_kv_lora``) multiplies the
    normalised compressed row, inside its norm's float32, and the pool
    holds the scaled row (the rotated key is not scaled). A config
    without them has both at 1. ``rope``: (cos, sin, index): the tables
    and each token's row in them."""
    T = h.shape[0]
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cos, sin, pos = rope
    with jax.named_scope("ds.mla"):
        hn = _rms(h, norm_scale, cfg.rms_norm_eps)
        if "q_proj" in attn:
            q = _proj(hn, attn["q_proj"])
        else:
            q = _proj(_rms(_proj(hn, attn["q_a_proj"]), attn["q_a_layernorm"]["scale"],
                           cfg.rms_norm_eps), attn["q_b_proj"])
        q = q.reshape(T, H, dn + dr)
        kv_a = _proj(hn, attn["kv_a_proj_with_mqa"])                       # [T, r + dr]
        latent_norm = attn["kv_a_layernorm"]["scale"]
        if getattr(cfg, "latent_scale", 1.0) != 1.0:
            latent_norm = latent_norm.astype(jnp.float32) * cfg.latent_scale
        c_kv = _rms(kv_a[:, :r], latent_norm, cfg.rms_norm_eps)
        q_rope = _rope_deinterleaved(q[..., dn:], cos, sin, pos)
        k_rope = _rope_deinterleaved(kv_a[:, None, r:], cos, sin, pos)[:, 0]
        w_kv = attn["kv_b_proj"]["kernel"].reshape(r, H, dn + dv)
        q_lat = jnp.einsum("thd,rhd->thr", q[..., :dn], w_kv[..., :dn])    # W_UK absorbed
        o_lat, kc, vc = _latent_attend(q_lat, q_rope, c_kv, k_rope, kc, vc, layer, batch,
                                       getattr(cfg, "query_scale", 1.0) / math.sqrt(dn + dr),
                                       attn_impl)
        out = jnp.einsum("thr,rhv->thv", o_lat, w_kv[..., dn:])            # W_UV after attention
        return h + _proj(out.reshape(T, H * dv), attn["o_proj"]), kc, vc


def _moonlight_layer_step(cfg, cos, sin, batch, attn_impl, experts, carry, xs):
    """One Moonlight layer over the flat ragged batch: latent attention
    with ``kv_b_proj`` absorbed, then the dense SwiGLU (a leading layer)
    or the experts (a scanned layer) — the layer's own params say which.
    ``experts``: every expert layer's routed experts, stacked and whole
    (:meth:`MoonlightKind.layers`)."""
    h, kc, vc = carry
    layer, lp = xs
    h, kc, vc = _latent_attention(cfg, lp["self_attn"], lp["input_layernorm"]["scale"], h,
                                  (cos, sin, batch["token_pos"]), kc, vc, layer, batch,
                                  attn_impl)
    hn2 = _rms(h, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    if "gate" in lp["mlp"]:
        h = h + _moonlight_moe(hn2, lp["mlp"], experts, layer - cfg.first_k_dense_replace, cfg)
    else:
        h = h + _swiglu(hn2, lp["mlp"])
    return (h, kc, vc), None


def _longcat_layer_step(cfg, rope, batch, attn_impl, experts, carry, xs):
    """One LongCat double layer over the flat ragged batch
    (``models/longcat.py`` has the equations): attention, then the expert
    layer ``m = M(x)`` and the first dense SwiGLU on the same normalised
    stream ``x``; attention and the second dense SwiGLU; ``m`` joins the
    residual only there, so the expert branch has no data dependence on
    the second half. ``xs``: (this layer's two state layers, its params,
    each half's under ``"0"`` / ``"1"``); → the carry and the expert
    layer's ``SHARE_COUNTS``."""
    h, kc, vc = carry
    ids, lp = xs

    def half(i):
        return {k: v[str(i)] for k, v in lp.items() if k != "mlp"}

    a, b = half(0), half(1)
    h, kc, vc = _latent_attention(cfg, a["self_attn"], a["input_layernorm"]["scale"], h, rope,
                                  kc, vc, ids[0], batch, attn_impl)
    x = _rms(h, a["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    # a padding token picks nothing: the last row of the block tables is padding's
    m, counts = _routed_experts(
        x, LongcatKind.router(cfg, lp["mlp"]), experts, ids[0] // 2,
        real=lambda: batch["token_seq"] < batch["block_tables"].shape[0] - 1)
    with jax.named_scope("ds.dense_ffn"):
        h = h + _swiglu(x, a["mlps"])
    h, kc, vc = _latent_attention(cfg, b["self_attn"], b["input_layernorm"]["scale"], h, rope,
                                  kc, vc, ids[1], batch, attn_impl)
    with jax.named_scope("ds.dense_ffn"):
        h = h + _swiglu(_rms(h, b["post_attention_layernorm"]["scale"], cfg.rms_norm_eps),
                        b["mlps"]) + m
    return (h, kc, vc), counts


def ragged_forward(params, kcache, vcache, batch, cfg, dtype=jnp.bfloat16, mesh=None,
                   attn_impl=None, lora=None, extra=None):
    """→ (last-token logits [max_seqs, vocab] fp32, kcache, vcache, then,
    where the model kind counts on the device (``kind.step_counts``), those
    counts, int32, summed over the layers, and last ``extra``): what the
    engine's programs carry from step to step, in their order.

    ``kcache``/``vcache``: the two pools of the model kind's state
    (:func:`kind_of`: keys and values ``[L, NB, bs, Hkv*Dh]``, or the
    latent rows and rotated keys of ``MoonlightKind``), carried through
    the layers and written in place (donate them); ``extra``: None, or the
    kind's own tree of further state (``kind.extra_state``), carried and
    donated likewise; ``batch``: the arrays of
    ``RaggedBatchWrapper.finalize()``. ``cfg`` is the config of any kind
    :func:`kind_of` knows; the layer wiring follows its kind. ``mesh``: an
    optional serving mesh — params/KV arrive sharded per
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
    kind = kind_of(cfg)
    batch = dict(batch, live_rows=_live_rows(batch))
    if "query_tiles" not in batch:
        # the step's rows as the paged kernel's grid takes them: adjacent rows of one sequence
        # share a walk of its context. Laid once a step, here and not where it is used, since
        # that is inside a scan over layers; _paged_attend alone reads it, and a kind whose
        # attention is not _paged_attend's leaves it unused, which costs its program nothing.
        # A program of one row a sequence by construction says so (a burst's
        # ``query_tiles: None``) and lowers the kernel a row a grid step.
        from deepspeed_tpu.ops.pallas.paged_attention import query_tiles
        tables = batch["block_tables"]
        batch["query_tiles"] = query_tiles(batch["token_seq"], batch["token_pos"],
                                           tables.shape[0] - 1, batch["live_rows"],
                                           tables.shape[1])
    embed = params["model"]["embed_tokens"]
    h = _c(embed[batch["token_ids"]].astype(dtype), (None, None), mesh)  # [T, D]
    mult = getattr(cfg, "embedding_multiplier", 1.0)
    if mult != 1.0:  # Gemma: sqrt(hidden_size)
        h = h * jnp.asarray(mult, h.dtype)

    h, kc, vc, extra, counts = kind.stack(params, cfg, h, kcache, vcache, extra, batch, dtype,
                                          mesh, attn_impl, lora)

    h = kind.final_norm(params, cfg, h)
    if "lm_head" in params:
        logits = h @ params["lm_head"]["kernel"].astype(h.dtype)
    else:  # tied embeddings
        logits = h @ embed.T.astype(h.dtype)
    logits = _c(logits, (None, "tensor"), mesh)  # vocab-sharded head
    sel = logits[batch["last_index"]]  # [max_seqs, V]
    if kind.step_counts:
        return sel.astype(jnp.float32), kc, vc, counts.sum(axis=0), extra
    return sel.astype(jnp.float32), kc, vc, extra
