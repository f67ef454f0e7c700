"""Block-sparse attention (fwd + bwd) as Pallas TPU kernels.

TPU-native counterpart of the reference's triton block-sparse kernels
(``deepspeed/ops/sparse_attention/matmul.py:819`` SDD/DSD block matmuls
and ``softmax.py:296``): attention restricted to the key blocks a
``SparsityConfig`` layout admits, SKIPPING the non-admitted blocks
rather than masking them — total inner-loop work is exactly
layout-density x the dense block-pair count.

Mechanism (the ``paged_attention.py`` pattern): the [H, nq, nk] boolean
layout is compressed on the host into per-(head, row) admitted-block
index lists that ride in SMEM via scalar prefetch. Each grid step owns
one (batch, head, row) and an inner ``fori_loop`` DMAs just that row's
admitted K/V (or Q/dO) blocks from HBM into VMEM scratch — per-row work
is its admitted count with no per-block grid overhead (measured
~0.45us/grid-step on v5e, which a one-block-per-step grid would pay
density x nq x nk times, cancelling the sparsity win at 128-blocks).

Masking is block-granular (a layout decision), matching the reference's
semantics and the XLA masked-dense fallback. Rows with NO admitted
blocks output zeros (dense-masked softmax would emit uniform garbage);
K blocks admitted by no query get zero dk/dv.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


_INDICES_CACHE = {}


def layout_to_indices(layout):
    """[H, nq, nk] bool → (k_idx [H, nq, A], k_nnz [H, nq],
    q_idx [H, nk, Aq], q_nnz [H, nk]) int32 numpy arrays: per-(head, row)
    admitted-column lists (zero-padded) and their true lengths; the
    ``q_*`` pair is the transpose, for the dK/dV pass. Results are
    cached by layout content — the compression loops are pure functions
    of the (static, reused-every-step) layout."""
    layout = np.asarray(layout, bool)
    key = (layout.shape, layout.tobytes())
    hit = _INDICES_CACHE.get(key)
    if hit is not None:
        return hit

    def compress(lay):  # [H, R, C] → idx [H, R, A], nnz [H, R]
        nnz = lay.sum(-1)
        a = max(int(nnz.max()), 1)
        idx = np.zeros((lay.shape[0], lay.shape[1], a), np.int32)
        for h in range(lay.shape[0]):
            for r in range(lay.shape[1]):
                cols = np.nonzero(lay[h, r])[0]
                idx[h, r, :len(cols)] = cols
        return idx, nnz.astype(np.int32)

    k_idx, k_nnz = compress(layout)
    q_idx, q_nnz = compress(layout.transpose(0, 2, 1))
    if len(_INDICES_CACHE) > 64:  # layouts are few; guard pathological use
        _INDICES_CACHE.clear()
    _INDICES_CACHE[key] = (k_idx, k_nnz, q_idx, q_nnz)
    return k_idx, k_nnz, q_idx, q_nnz


def _fwd_kernel(kidx_ref, knnz_ref, q_ref, k_hbm, v_hbm, o_ref, lse_ref,
                k_buf, v_buf, k_sem, v_sem, *, sm_scale, block):
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    q = q_ref[0, 0]

    def step(j, carry):
        m, l, acc = carry
        blk = kidx_ref[h, i, j]
        ck = pltpu.make_async_copy(k_hbm.at[b, h, pl.ds(blk * block, block)], k_buf, k_sem)
        cv = pltpu.make_async_copy(v_hbm.at[b, h, pl.ds(blk * block, block)], v_buf, v_sem)
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()
        s = jax.lax.dot_general(q, k_buf[:], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new)
        l_new = alpha * l + jnp.sum(p_, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p_.astype(v_buf.dtype), v_buf[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block, 1), jnp.float32)
    a0 = jnp.zeros((block, q.shape[1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, knnz_ref[h, i], step, (m0, l0, a0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to(m + jnp.log(l_safe), lse_ref.shape[2:])


def _dq_kernel(kidx_ref, knnz_ref, q_ref, do_ref, lse_ref, delta_ref, k_hbm, v_hbm,
               dq_ref, k_buf, v_buf, k_sem, v_sem, *, sm_scale, block):
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]

    def step(j, dq):
        blk = kidx_ref[h, i, j]
        ck = pltpu.make_async_copy(k_hbm.at[b, h, pl.ds(blk * block, block)], k_buf, k_sem)
        cv = pltpu.make_async_copy(v_hbm.at[b, h, pl.ds(blk * block, block)], v_buf, v_sem)
        ck.start()
        cv.start()
        ck.wait()
        cv.wait()
        s = jax.lax.dot_general(q, k_buf[:], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        p_ = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v_buf[:], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p_ * (dp - delta) * sm_scale).astype(q.dtype)
        return dq + jax.lax.dot_general(ds, k_buf[:], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, knnz_ref[h, i],
                           step, jnp.zeros((block, q.shape[1]), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(qidx_ref, qnnz_ref, k_ref, v_ref, q_hbm, do_hbm, lse_hbm, delta_hbm,
                dk_ref, dv_ref, q_buf, do_buf, lse_buf, delta_buf,
                q_sem, do_sem, lse_sem, delta_sem, *, sm_scale, block):
    b = pl.program_id(0)
    h = pl.program_id(1)
    jk = pl.program_id(2)
    k = k_ref[0, 0]
    v = v_ref[0, 0]

    def step(i, carry):
        dk, dv = carry
        blk = qidx_ref[h, jk, i]
        copies = [
            pltpu.make_async_copy(q_hbm.at[b, h, pl.ds(blk * block, block)], q_buf, q_sem),
            pltpu.make_async_copy(do_hbm.at[b, h, pl.ds(blk * block, block)], do_buf, do_sem),
            pltpu.make_async_copy(lse_hbm.at[b, h, pl.ds(blk * block, block)], lse_buf, lse_sem),
            pltpu.make_async_copy(delta_hbm.at[b, h, pl.ds(blk * block, block)], delta_buf,
                                  delta_sem),
        ]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()
        q = q_buf[:]
        do = do_buf[:]
        lse = lse_buf[:, :1]
        delta = delta_buf[:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        p_ = jnp.exp(s - lse)
        p16 = p_.astype(q.dtype)
        dv_new = dv + jax.lax.dot_general(p16, do, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p_ * (dp - delta) * sm_scale).astype(q.dtype)
        dk_new = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        return dk_new, dv_new

    zeros = jnp.zeros((block, k.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, qnnz_ref[h, jk], step, (zeros, zeros))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _fwd_impl(q, k, v, k_idx, k_nnz, block, interpret):
    """q/k/v: [B, H, S, D] → (o, lse [B, H, S])."""
    B, H, S, D = q.shape
    kernel = functools.partial(_fwd_kernel, sm_scale=1.0 / np.sqrt(D), block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # k_idx, k_nnz
        grid=(B, H, S // block),
        in_specs=[
            pl.BlockSpec((1, 1, block, D), lambda b, h, i, ki, kn: (b, h, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block, D), lambda b, h, i, ki, kn: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block, 128), lambda b, h, i, ki, kn: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, D), k.dtype),
            pltpu.VMEM((block, D), v.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    o, lse = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, S, 128), jnp.float32)],
        interpret=interpret,
    )(k_idx, k_nnz, q, k, v)
    return o, lse[..., 0]


def _bwd_impl(q, k, v, o, lse, do, k_idx, k_nnz, q_idx, q_nnz, block, interpret):
    B, H, S, D = q.shape
    sm_scale = 1.0 / np.sqrt(D)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B, H, S]
    delta = jnp.broadcast_to(delta[..., None], (B, H, S, 128))
    lse_l = jnp.broadcast_to(lse[..., None], (B, H, S, 128))

    at_row = lambda b, h, i, ki, kn: (b, h, i, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, S // block),
            in_specs=[
                pl.BlockSpec((1, 1, block, D), at_row),
                pl.BlockSpec((1, 1, block, D), at_row),
                pl.BlockSpec((1, 1, block, 128), at_row),
                pl.BlockSpec((1, 1, block, 128), at_row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, block, D), at_row),
            scratch_shapes=[
                pltpu.VMEM((block, D), k.dtype),
                pltpu.VMEM((block, D), v.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(k_idx, k_nnz, q, do, lse_l, delta, k, v)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # q_idx, q_nnz
            grid=(B, H, S // block),
            in_specs=[
                pl.BlockSpec((1, 1, block, D), at_row),
                pl.BlockSpec((1, 1, block, D), at_row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block, D), at_row),
                pl.BlockSpec((1, 1, block, D), at_row),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, D), q.dtype),
                pltpu.VMEM((block, D), do.dtype),
                pltpu.VMEM((block, 128), jnp.float32),
                pltpu.VMEM((block, 128), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, S, D), q.dtype)],
        interpret=interpret,
    )(q_idx, q_nnz, k, v, q, do, lse_l, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _sparse(q, k, v, k_idx, k_nnz, q_idx, q_nnz, block, interpret):
    o, _ = _fwd_impl(q, k, v, k_idx, k_nnz, block, interpret)
    return o


def _sparse_fwd(q, k, v, k_idx, k_nnz, q_idx, q_nnz, block, interpret):
    o, lse = _fwd_impl(q, k, v, k_idx, k_nnz, block, interpret)
    return o, (q, k, v, o, lse, k_idx, k_nnz, q_idx, q_nnz)


def _sparse_bwd(block, interpret, res, do):
    q, k, v, o, lse, k_idx, k_nnz, q_idx, q_nnz = res
    dq, dk, dv = _bwd_impl(q, k, v, o, lse, do, k_idx, k_nnz, q_idx, q_nnz,
                           block, interpret)
    f0 = lambda x: np.zeros(x.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, f0(k_idx), f0(k_nnz), f0(q_idx), f0(q_nnz)


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def block_sparse_attention(q, k, v, layout, block, interpret=None):
    """Layout-sparse attention on [B, S, H, D] tensors.

    ``layout``: concrete [H or 1, S/block, S/block] boolean array (a
    ``SparsityConfig.make_layout`` product — host data, not a traced
    value). Admitted blocks attend bidirectionally at block granularity,
    exactly like the masked-dense path. → [B, S, H, D].
    """
    B, S, Hq, D = q.shape
    if interpret is None:
        from deepspeed_tpu.ops.pallas import default_interpret
        interpret = default_interpret()
    layout = np.asarray(layout, bool)
    if layout.shape[0] == 1 and Hq > 1:
        layout = np.broadcast_to(layout, (Hq,) + layout.shape[1:])
    assert layout.shape == (Hq, S // block, S // block), \
        f"layout {layout.shape} vs heads {Hq}, seq {S}, block {block}"
    k_idx, k_nnz, q_idx, q_nnz = layout_to_indices(layout)
    bhsd = lambda x: x.transpose(0, 2, 1, 3)  # [B, S, H, D] → [B, H, S, D]
    o = _sparse(bhsd(q), bhsd(k), bhsd(v),
                jnp.asarray(k_idx), jnp.asarray(k_nnz),
                jnp.asarray(q_idx), jnp.asarray(q_nnz), block, interpret)
    return o.transpose(0, 2, 1, 3)


def grid_fraction(layout):
    """Fraction of the dense (H x nq x nk) block-pair count the kernels'
    inner loops actually execute: sum of admitted counts / dense count —
    exactly the layout density. Exposed for tests/accounting."""
    layout = np.asarray(layout, bool)
    return float(layout.mean())
