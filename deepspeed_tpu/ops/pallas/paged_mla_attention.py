"""Paged latent (MLA) decode attention: one query token, all of its heads,
against a block-tabled pool of latent rows that every head shares.

Multi-head latent attention with ``kv_b_proj`` absorbed (DeepSeek-V2,
section 2.1.2) attends in the compressed space: per token and layer the
cache holds one row — the normalised compressed values ``c``
(``kv_lora_rank`` wide) and the rotated ``k_rope`` — and head ``h``
scores it with its own query, ``q_lat[h] . c + q_rope[h] . k_rope``, and
takes ``softmax(scores) @ c``. The pool is two arrays in the layout
``BlockedKVCache`` stores, ``c_pool [L, NB, bs, rank]`` and ``r_pool [L,
NB, bs, lanes]`` (``k_rope`` zero-padded to whole 128-lane tiles); the
query arrives as one row a head over both, ``[T, H, rank + lanes]``,
**already scaled** (the softmax scale is the caller's: it knows the
un-absorbed head size). The result is ``[T, H, rank]``; ``W_UV`` and
``o_proj`` are the caller's.

The kernel follows ``paged_decode_attention`` (one grid step a token,
block table in SMEM, block DMA out of the whole pool by layer index,
running max and sum) with the two differences the latent row asks for:
the heads share each fetched block — it is fetched **once** and all ``H``
query rows ride one matmul against it, where the KV kernel slices a head
out of its block — and the next block's DMA is started before this
block's arithmetic, since one block serves sixteen heads' worth of
arithmetic and the copy would otherwise be exposed. The matmuls take the
pool's dtype in and accumulate float32 (``preferred_element_type``), as
the MXU does; the probabilities are rounded to the pool's dtype for the
second matmul, as :func:`xla_paged_mla_attention` does.

Which of the two a program runs is decided in the
``inference/v2/modules/heuristics`` registry (``pallas_paged_mla`` /
``xla_gather_mla``); the kernel entry raises on a shape it cannot take.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.paged_attention import (GATHER_LIMIT_BYTES, NEG_INF,
                                                      SMEM_TABLE_BYTES, smem_table_fits)


def xla_paged_mla_attention(q, c_pool, r_pool, block_tables, token_pos, layer):
    """Reference math by gather. q [T, H, rank + lanes], scaled;
    c_pool [L, NB, bs, rank]; r_pool [L, NB, bs, lanes]; block_tables
    [T, MB] (per token); token_pos [T]; layer int32 scalar →
    [T, H, rank]; attends to positions <= token_pos."""
    T, H, _ = q.shape
    bs, rank = c_pool.shape[2], c_pool.shape[3]
    gather_bytes = T * block_tables.shape[1] * bs * q.shape[2] * c_pool.dtype.itemsize
    if gather_bytes > GATHER_LIMIT_BYTES:
        raise ValueError(
            f"the XLA gather attention would materialize {gather_bytes / 1e9:.0f} GB of latent "
            f"rows for block table [{T}, {block_tables.shape[1]}] — shrink "
            f"max_ragged_batch_size / max_context, or raise kv_block_size")
    c = c_pool[layer, block_tables].reshape(T, -1, rank).astype(q.dtype)          # [T, C, rank]
    kr = r_pool[layer, block_tables].reshape(T, -1, r_pool.shape[3]).astype(q.dtype)
    scores = (jnp.einsum("thr,tcr->thc", q[..., :rank], c, preferred_element_type=jnp.float32)
              + jnp.einsum("thr,tcr->thc", q[..., rank:], kr, preferred_element_type=jnp.float32))
    mask = (jnp.arange(c.shape[1])[None, :] <= token_pos[:, None])[:, None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1).astype(q.dtype)
    return jnp.einsum("thc,tcr->thr", probs, c,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def mla_kernel_supported(rank, lanes, block_size):
    """Mosaic's constraints on the latent kernel: each block DMA copies a
    2-D ``[block_size, rank]`` and a ``[block_size, lanes]`` slice, so
    both widths are whole 128-lane tiles, and ``block_size`` is a whole
    number of bf16 sublane tiles (16) — it is also the lane dim of the
    ``[H, block_size]`` score tile, so a multiple of 128 keeps that tile
    whole; smaller blocks compile but each step then pays one DMA and one
    loop turn for few rows."""
    return rank % 128 == 0 and lanes % 128 == 0 and block_size % 16 == 0


def _kernel(tab_ref, pos_ref, layer_ref, q_ref, c_hbm, r_hbm, o_ref,
            c_buf, r_buf, sems, *, bs, max_blocks, rank):
    """One token: q_ref [1, H, rank + lanes] (VMEM); both pools stay in
    HBM and each table block of the layer is DMA'd into one of two VMEM
    slots, the next block's copy in flight while this block's two matmuls
    run over all H heads at once."""
    t = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[t]
    H = q_ref.shape[1]
    q = q_ref[0]
    q_lat, q_rope = q[:, :rank], q[:, rank:]
    n_blocks = jnp.minimum(pos // bs + 1, max_blocks)

    def copies(i, slot):
        blk = tab_ref[t, i]
        return (pltpu.make_async_copy(c_hbm.at[layer, blk], c_buf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(r_hbm.at[layer, blk], r_buf.at[slot], sems.at[1, slot]))

    for copy in copies(0, 0):
        copy.start()

    def block_step(i, carry):
        m, l, acc = carry  # [H, 1], [H, 1], [H, rank]
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            for copy in copies(i + 1, 1 - slot):
                copy.start()

        for copy in copies(i, slot):
            copy.wait()
        c = c_buf[slot]      # [bs, rank]: the keys' latent part AND the values
        kr = r_buf[slot]     # [bs, lanes]
        contract_last = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q_lat, c, contract_last, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_rope, kr, contract_last,
                                   preferred_element_type=jnp.float32))     # [H, bs]
        kv_pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(kv_pos <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)        # [H, rank]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    a0 = jnp.zeros((H, rank), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block_step, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_mla_decode_attention(q, c_pool, r_pool, block_tables, token_pos, layer,
                               interpret=None):
    """Pallas path of :func:`xla_paged_mla_attention` (same contract)."""
    if interpret is None:
        from deepspeed_tpu.ops.pallas import default_interpret
        interpret = default_interpret()
    T, H, width = q.shape
    bs, rank, lanes = c_pool.shape[2], c_pool.shape[3], r_pool.shape[3]
    MB = block_tables.shape[1]
    if width != rank + lanes:
        raise ValueError(f"query rows are {width} wide, the pooled rows {rank} + {lanes}")
    if not interpret:
        if not mla_kernel_supported(rank, lanes, bs):
            raise ValueError(
                f"paged latent decode kernel needs rank % 128 == 0, rope lanes % 128 == 0 and "
                f"block_size % 16 == 0, got rank={rank}, lanes={lanes}, block_size={bs}")
        if not smem_table_fits(T, MB):
            raise ValueError(
                f"paged latent decode block table [{T}, {MB}] overflows the kernel's "
                f"{SMEM_TABLE_BYTES >> 10} KB SMEM budget — shrink max_ragged_batch_size / "
                f"max_context, or raise kv_block_size")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tables, positions, layer
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, H, width), lambda t, tab, pos, layer: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda t, tab, pos, layer: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bs, rank), c_pool.dtype),
            pltpu.VMEM((2, bs, lanes), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, max_blocks=MB, rank=rank)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, rank), q.dtype),
        interpret=interpret,
        name="paged_mla_decode_attention",
    )(block_tables.astype(jnp.int32), token_pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, c_pool, r_pool)
