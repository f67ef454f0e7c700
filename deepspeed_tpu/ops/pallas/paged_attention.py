"""Paged decode attention kernel: one query token vs a block-tabled KV.

TPU-native counterpart of the reference's ragged decode kernels
(``deepspeed/inference/v2/kernels/ragged_ops/atom_builder`` +
``blocked_flash`` over the blocked KV cache,
``csrc/.../ragged_ops/``). Each grid step handles ONE token: its block
table rides in SMEM (scalar prefetch), KV blocks are dynamically
indexed out of the pool, and scores accumulate flash-style (running
max / sum) with positions beyond the token's context masked. GQA is
handled by viewing the query heads as [Hkv, G, Dh].

Both paths take the WHOLE pool ``[L, NB, bs, Hkv*Dh]`` — the layout
``BlockedKVCache`` stores — and the layer as an index, so the layer
scan never cuts a layer out of the pool and no program reshapes it:
the kernel's block DMA reads ``pool[layer, blk]``, the reference
gathers ``pool[layer, block_tables]``.

The XLA reference path (``xla_paged_attention``) is the same math via
gather. Which of the two a program runs is decided in ONE place, the
``inference/v2/modules/heuristics`` registry (``supports()`` there reads
:func:`kernel_supported` and :func:`smem_table_fits`), where the choice
has a name the engine reports; the kernel entry itself never hands a
refused shape to the reference — it raises.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)

# The block tables + positions ride in SMEM via scalar prefetch and v5e
# SMEM is ~1 MB: oversized state configs (e.g. the default
# max_tokens=768 x max_context/bs tables) overflow it at COMPILE time
# ("Ran out of memory in memory space smem").
SMEM_TABLE_BYTES = 768 * 1024
# The gather reference materializes a dense [T, MB*bs, Hkv, Dh] copy of
# K and of V per layer; past this it is an opaque allocator OOM.
GATHER_LIMIT_BYTES = 2 << 30


def xla_paged_attention(q, kc, vc, block_tables, token_pos, layer, alibi_slopes=None):
    """Reference math. q: [T, H, Dh]; kc/vc: the pool [L, NB, bs, Hkv*Dh];
    block_tables: [T, MB] (per TOKEN, already indexed by its sequence);
    token_pos: [T]; layer: int32 scalar, the layer of the pool to read.
    → [T, H, Dh]; attends to positions <= token_pos.
    ``alibi_slopes``: optional [H] — adds the Bloom-style linear
    relative-position penalty slope_h * (k_pos - q_pos) to the scores."""
    T, H, Dh = q.shape
    bs, Hkv = kc.shape[2], kc.shape[3] // Dh
    gather_bytes = 2 * T * block_tables.shape[1] * bs * Hkv * Dh * kc.dtype.itemsize
    if gather_bytes > GATHER_LIMIT_BYTES:
        raise ValueError(
            f"the XLA gather attention would materialize {gather_bytes / 1e9:.0f} GB of KV "
            f"for block table [{T}, {block_tables.shape[1]}] — shrink "
            f"max_ragged_batch_size / max_context, or raise kv_block_size")
    ks = kc[layer, block_tables].reshape(T, -1, Hkv, Dh).astype(q.dtype)
    vs = vc[layer, block_tables].reshape(T, -1, Hkv, Dh).astype(q.dtype)
    if Hkv != H:
        from deepspeed_tpu.models.llama import repeat_kv
        ks, vs = repeat_kv(ks, vs, H // Hkv)
    scale = 1.0 / np.sqrt(Dh)
    scores = jnp.einsum("thd,tchd->thc", q, ks).astype(jnp.float32) * scale
    k_idx = jnp.arange(ks.shape[1])
    if alibi_slopes is not None:
        rel = (k_idx[None, :] - token_pos[:, None]).astype(jnp.float32)  # [T, C]
        scores = scores + alibi_slopes[None, :, None] * rel[:, None, :]
    mask = (k_idx[None, :] <= token_pos[:, None])[:, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("thc,tchd->thd", probs, vs)


def kernel_supported(head_dim, block_size, n_kv_heads=None):
    """Mosaic constraint: the per-block DMA copies a 2-D
    ``[block_size, Hkv*Dh]`` slice (the pool stores its KV-head and head
    dims flattened), so the lane dim is ``Hkv * head_dim``
    — a multiple of 128 for any head count when head_dim % 128 == 0, and
    the sublane dim is ``block_size`` (multiple of 8). ANY KV-head count
    is supported this way (round 4's Hkv % 8 restriction came from
    slicing the un-flattened [bs, Hkv, Dh] pool, whose second-minor dim
    had to tile the 8-sublane granule — 1/6/12/20-head pools crashed
    Mosaic; the flattened layout re-measured compiling and matching the
    XLA reference on a real v5e for all four counts, 2026-08-01). 64-dim-head models (e.g. Bloom-560M, GPT-2) and ALiBi
    models take the XLA gather path
    (see ``inference/v2/modules/heuristics.py``). A model whose heads are
    neither (Moonlight's 192-wide queries over a 576-value latent row) is
    not this kernel's at all: its state kind is ``latent`` and its kernel
    ``paged_mla_attention.paged_mla_decode_attention``, with
    ``mla_kernel_supported`` as its own constraint."""
    return head_dim % 128 == 0 and block_size % 8 == 0


def smem_table_fits(n_tokens, max_blocks):
    """Do the ``[n_tokens, max_blocks]`` int32 block table, the
    ``[n_tokens]`` positions and the layer index fit the kernel's SMEM
    budget?"""
    return (n_tokens * max_blocks + n_tokens + 1) * 4 <= SMEM_TABLE_BYTES


def _kernel(tab_ref, pos_ref, layer_ref, q_ref, kc_ref, vc_ref, o_ref,
            k_buf, v_buf, k_sem, v_sem, *, bs, max_blocks, groups, n_kv_heads):
    """One token: q_ref [1, H, Dh] (VMEM); kc/vc, the whole pool
    [L, NB, bs, Hkv*Dh], stay in HBM (ANY) — each table block of the
    layer is DMA'd into the VMEM scratch buffers as a 2-D [bs, Hkv*Dh]
    slice (lane dim a 128-multiple for ANY KV-head count); tab/pos/layer
    in SMEM via scalar prefetch. Per-head columns are 128-aligned lane
    slices of the buffer."""
    t = pl.program_id(0)
    layer = layer_ref[0]
    H, Dh = q_ref.shape[1], q_ref.shape[2]
    Hkv = n_kv_heads
    G = groups
    pos = pos_ref[t]
    scale = 1.0 / np.sqrt(Dh)
    # everything stays 2-D: Mosaic's vector layouts reject >2-D reshapes
    q = q_ref[0].astype(jnp.float32) * scale  # [H, Dh], heads grouped [Hkv x G]

    def block_step(i, carry):
        m, l, acc = carry  # [H, 1], [H, 1], [H, Dh]
        blk = tab_ref[t, i]
        ck = pltpu.make_async_copy(kc_ref.at[layer, blk], k_buf, k_sem)
        cv = pltpu.make_async_copy(vc_ref.at[layer, blk], v_buf, v_sem)
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()
        kbuf = k_buf[:]  # one read; heads are lane slices of it
        vbuf = v_buf[:]
        # per-kv-head 2-D matmuls, statically unrolled; head h occupies
        # lanes [h*Dh, (h+1)*Dh) of the flattened buffer
        s_parts = []
        for h in range(Hkv):
            kh = jax.lax.slice(kbuf, (0, h * Dh), (bs, (h + 1) * Dh)
                               ).astype(jnp.float32)  # [bs, Dh]
            qh = jax.lax.slice(q, (h * G, 0), ((h + 1) * G, Dh))  # [G, Dh]
            s_parts.append(jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                               precision=jax.lax.Precision.HIGHEST))
        s = jnp.concatenate(s_parts, axis=0)  # [H, bs]
        kv_pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(kv_pos <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv_parts = []
        for h in range(Hkv):
            vh = jax.lax.slice(vbuf, (0, h * Dh), (bs, (h + 1) * Dh)
                               ).astype(jnp.float32)  # [bs, Dh]
            ph = jax.lax.slice(p, (h * G, 0), ((h + 1) * G, bs))  # [G, bs]
            pv_parts.append(jax.lax.dot_general(ph, vh, (((1,), (0,)), ((), ())),
                                                precision=jax.lax.Precision.HIGHEST))
        pv = jnp.concatenate(pv_parts, axis=0)  # [H, Dh]
        acc_new = acc * alpha + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    a0 = jnp.zeros((H, Dh), jnp.float32)
    n_blocks = jnp.minimum(pos // bs + 1, max_blocks)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, block_step, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention(q, kc, vc, block_tables, token_pos, layer, interpret=None):
    """Pallas path of :func:`xla_paged_attention` (same contract)."""
    if interpret is None:
        from deepspeed_tpu.ops.pallas import default_interpret
        interpret = default_interpret()
    T, H, Dh = q.shape
    bs, Hkv = kc.shape[2], kc.shape[3] // Dh
    MB = block_tables.shape[1]
    groups = H // Hkv
    if not interpret:
        if not kernel_supported(Dh, bs, Hkv):
            raise ValueError(
                f"paged decode kernel needs head_dim % 128 == 0 and block_size % 8 == 0, "
                f"got head_dim={Dh}, block_size={bs}")
        if not smem_table_fits(T, MB):
            raise ValueError(
                f"paged decode block table [{T}, {MB}] overflows the kernel's "
                f"{SMEM_TABLE_BYTES >> 10} KB SMEM budget — shrink max_ragged_batch_size / "
                f"max_context, or raise kv_block_size")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tables, positions, layer
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda t, tab, pos, layer: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, Dh), lambda t, tab, pos, layer: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bs, Hkv * Dh), kc.dtype),
            pltpu.VMEM((bs, Hkv * Dh), vc.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, max_blocks=MB, groups=groups,
                               n_kv_heads=Hkv)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, Dh), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), token_pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, kc, vc)
