"""Flash attention (fwd + bwd) as Pallas TPU kernels.

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/`` softmax/attention paths and the CUTLASS fMHA in
``csrc/deepspeed4science/evoformer_attn/``): an online-softmax blocked
attention that never materialises the [S, S] score matrix in HBM,
with a custom VJP whose backward pass is two more Pallas kernels
(dk/dv and dq) recomputing probabilities from the saved logsumexp.

Layout: [B, S, H, D] (batch, sequence, heads, head_dim) to match the
model stack; internally blocks run per (batch*head) over [S, D] tiles.
Causal masking is applied by global block indices; sequence lengths
that do not divide the block size are zero-padded and masked.

``flash_attention(force_pallas=None)`` is the unpinned entry: it takes
the kernels where ``use_pallas()`` says so and the fused-by-XLA
reference (identical math, fp32 softmax) elsewhere. A caller that pinned
the kernel passes ``force_pallas=True`` and never gets the reference.

``window=W`` (with ``causal``): a query attends the ``W`` newest keys,
``q - W < k <= q``. The three kernels of that call are programs of their
own (``flash_window_fwd`` / ``_dkv`` / ``_dq`` in a device trace): their
grids walk only the block pairs the band touches - a query block's keys
from the window's first block, a key block's queries up to the last row
that still sees it - and mask inside the edge blocks. ``window=None``
lowers what it lowered before there was a window.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask(s, iq, ik, block_q, block_k, seq_len, causal, seg_q=None, seg_k=None):
    """Additive validity mask for one [block_q, block_k] score tile.
    ``seg_q``/``seg_k``: [block_q, 1] / [block_k, 1] int32 segment ids —
    packed sequences attend only within equal ids."""
    q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = k_idx < seq_len
    if causal:
        valid = jnp.logical_and(valid, q_idx >= k_idx)
    if seg_q is not None:
        same = seg_q == jnp.transpose(seg_k)  # [block_q, block_k]
        valid = jnp.logical_and(valid, same)
    return jnp.where(valid, s, NEG_INF), valid


def _fwd_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, causal, block_q, block_k, seq_len, n_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: tiles strictly above the diagonal contribute nothing.
    run = jnp.asarray(True)
    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        # MXU inputs stay in the storage dtype (bf16): fp32 operands run
        # the MXU at a fraction of peak; accumulation is fp32 regardless
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, _ = _mask(s, iq, ik, block_q, block_k, seq_len, causal,
                     sq_ref[0][:, :1], sk_ref[0][:, :1])

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse is lane-replicated to [block_q, 128] to satisfy TPU tiling
        lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l_safe), lse_ref.shape[1:])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, sm_scale, causal, block_q, block_k, seq_len, n_q):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = jnp.asarray(True)
    if causal:
        run = (iq * block_q + block_q - 1) >= (ik * block_k)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, valid = _mask(s, iq, ik, block_q, block_k, seq_len, causal,
                         sq_ref[0][:, :1], sk_ref[0][:, :1])
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        p16 = p.astype(q.dtype)

        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(p16, do, (((0,), (0,)), ((), ())),
                                                    preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                                    preferred_element_type=jnp.float32)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
               dq_ref, dq_scr, *, sm_scale, causal, block_q, block_k, seq_len, n_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = jnp.asarray(True)
    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, valid = _mask(s, iq, ik, block_q, block_k, seq_len, causal,
                         sq_ref[0][:, :1], sk_ref[0][:, :1])
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                                    preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# the band: causal attention over the ``window`` newest keys
# ---------------------------------------------------------------------------

def _first_k_block(iq, block_q, block_k, window):
    """The first key block a query block's band touches (its first row's
    oldest key); ``iq`` a Python int (the grid's length) or traced (an
    index map, a kernel)."""
    first_key = iq * block_q - (window - 1)
    return (max(first_key, 0) if isinstance(iq, int) else jnp.maximum(first_key, 0)) // block_k


def _last_k_block(iq, block_q, block_k):
    """The last key block a query block sees under the causal mask."""
    return (iq * block_q + block_q - 1) // block_k


def _first_q_block(ik, block_q, block_k):
    return (ik * block_k) // block_q


def _last_q_block(ik, block_q, block_k, window, n_q):
    """The last query block with a row that still sees the key block's
    newest key."""
    last = (ik * block_k + block_k - 1 + window - 1) // block_q
    return min(last, n_q - 1) if isinstance(ik, int) else jnp.minimum(last, n_q - 1)


def window_block_pairs(seq_len, window, block_q=1024, block_k=1024):
    """(the block pairs the windowed kernels visit, the pairs a causal
    kernel runs) at these blocks: what the grid walks, for the census and
    the rooflines' readers."""
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    n_q = s_pad // block_q
    band = sum(_last_k_block(i, block_q, block_k)
               - _first_k_block(i, block_q, block_k, window) + 1 for i in range(n_q))
    causal = sum(_last_k_block(i, block_q, block_k) + 1 for i in range(n_q))
    return band, causal


def _band_steps(n_outer, first, last):
    """The inner grid's length: the most blocks any outer block walks."""
    return max(last(i) - first(i) + 1 for i in range(n_outer))


def _band_mask(s, iq, ik, block_q, block_k, seq_len, window, seg_q, seg_k):
    s, valid = _mask(s, iq, ik, block_q, block_k, seq_len, True, seg_q, seg_k)
    q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = jnp.logical_and(valid, k_idx > q_idx - window)
    return jnp.where(valid, s, NEG_INF), valid


def _win_fwd_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                    *, sm_scale, window, block_q, block_k, seq_len, n_w):
    iq = pl.program_id(1)
    j = pl.program_id(2)
    ik = _first_k_block(iq, block_q, block_k, window) + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ik <= _last_k_block(iq, block_q, block_k))
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, valid = _band_mask(s, iq, ik, block_q, block_k, seq_len, window,
                              sq_ref[0][:, :1], sk_ref[0][:, :1])
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row whose band lies wholly outside this block has seen nothing yet: exp(0) = 1
        # of a masked score must not count
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_w - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l_safe), lse_ref.shape[1:])


def _win_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, window, block_q, block_k, seq_len, n_q, n_w):
    ik = pl.program_id(1)
    j = pl.program_id(2)
    iq = _first_q_block(ik, block_q, block_k) + j

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(iq <= _last_q_block(ik, block_q, block_k, window, n_q))
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, valid = _band_mask(s, iq, ik, block_q, block_k, seq_len, window,
                              sq_ref[0][:, :1], sk_ref[0][:, :1])
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        p16 = p.astype(q.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(p16, do, (((0,), (0,)), ((), ())),
                                                    preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                                    preferred_element_type=jnp.float32)

    @pl.when(j == n_w - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _win_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
                   dq_ref, dq_scr, *, sm_scale, window, block_q, block_k, seq_len, n_w):
    iq = pl.program_id(1)
    j = pl.program_id(2)
    ik = _first_k_block(iq, block_q, block_k, window) + j

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(ik <= _last_k_block(iq, block_q, block_k))
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        s, valid = _band_mask(s, iq, ik, block_q, block_k, seq_len, window,
                              sq_ref[0][:, :1], sk_ref[0][:, :1])
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                                    preferred_element_type=jnp.float32)

    @pl.when(j == n_w - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _blocked_shapes(seq_len, block_q, block_k):
    block_q = min(block_q, max(seq_len, 1))
    block_k = min(block_k, max(seq_len, 1))
    s_pad_q = -(-seq_len // block_q) * block_q
    s_pad_k = -(-seq_len // block_k) * block_k
    # A single padded length keeps q/k/v congruent.
    s_pad = max(s_pad_q, s_pad_k)
    s_pad = -(-s_pad // block_q) * block_q
    s_pad = -(-s_pad // block_k) * block_k
    return block_q, block_k, s_pad


def _seg_lanes(seg, bh, s_pad):
    """[BH, S] int32 → [BH, S_pad, 128] lane-replicated (TPU tiling)."""
    if seg.shape[1] != s_pad:
        seg = jnp.pad(seg, ((0, 0), (0, s_pad - seg.shape[1])))
    return jnp.broadcast_to(seg[:, :, None], (bh, s_pad, 128)).astype(jnp.int32)


def _fwd_impl(q, k, v, seg, causal, sm_scale, block_q, block_k, interpret):
    """q/k/v: [BH, S, D]; seg: [BH, S] int32 → (o, lse [BH, S_pad])."""
    bh, seq_len, d = q.shape
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0))) if x.shape[1] != s_pad else x
    q_p, k_p, v_p = pad(q), pad(k), pad(v)
    seg_p = _seg_lanes(seg, bh, s_pad)
    n_q, n_k = s_pad // block_q, s_pad // block_k

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, seq_len=seq_len, n_k=n_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, 128), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_pad, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q_p, k_p, v_p, seg_p, seg_p)
    # Drop the lane replication before saving lse as a VJP residual
    # (128x HBM otherwise); the backward re-broadcasts it.
    return o[:, :seq_len], lse[:, :, 0]


def _bwd_impl(q, k, v, seg, o, lse, do, causal, sm_scale, block_q, block_k, interpret):
    bh, seq_len, d = q.shape
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0))) if x.shape[1] != s_pad else x
    q_p, k_p, v_p, do_p = pad(q), pad(k), pad(v), pad(do)
    seg_p = _seg_lanes(seg, bh, s_pad)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BH, S]
    if delta.shape[1] != s_pad:
        delta = jnp.pad(delta, ((0, 0), (0, s_pad - delta.shape[1])))
    # lane-replicate lse/delta to [BH, S_pad, 128] for TPU tiling
    delta = jnp.broadcast_to(delta[:, :, None], (bh, s_pad, 128))
    lse_p = jnp.broadcast_to(lse[:, :, None], (bh, s_pad, 128))
    n_q, n_k = s_pad // block_q, s_pad // block_k

    dkv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=seq_len, n_q=n_q),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, 128), lambda b, j, i: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(q_p, k_p, v_p, do_p, lse_p, delta, seg_p, seg_p)
    dk, dv = dkv

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=seq_len, n_k=n_k),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, 128), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q_p, k_p, v_p, do_p, lse_p, delta, seg_p, seg_p)

    return dq[:, :seq_len], dk[:, :seq_len], dv[:, :seq_len]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, seg, causal, sm_scale, block_q, block_k, interpret):
    o, _ = _fwd_impl(q, k, v, seg, causal, sm_scale, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, seg, causal, sm_scale, block_q, block_k, interpret):
    o, lse = _fwd_impl(q, k, v, seg, causal, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, seg, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, seg, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, seg, o, lse, do, causal, sm_scale,
                           block_q, block_k, interpret)
    dseg = np.zeros(seg.shape, dtype=jax.dtypes.float0)  # int operand: no tangent
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


def _win_fwd_impl(q, k, v, seg, window, sm_scale, block_q, block_k, interpret):
    """:func:`_fwd_impl` over the band of ``window`` keys."""
    bh, seq_len, d = q.shape
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0))) if x.shape[1] != s_pad else x
    q_p, k_p, v_p = pad(q), pad(k), pad(v)
    seg_p = _seg_lanes(seg, bh, s_pad)
    n_q = s_pad // block_q
    first = functools.partial(_first_k_block, block_q=block_q, block_k=block_k, window=window)
    last = functools.partial(_last_k_block, block_q=block_q, block_k=block_k)
    n_w = _band_steps(n_q, first, last)
    kv = lambda b, i, j: (b, jnp.minimum(first(i) + j, last(i)), 0)
    o, lse = pl.pallas_call(
        functools.partial(_win_fwd_kernel, sm_scale=sm_scale, window=window, block_q=block_q,
                          block_k=block_k, seq_len=seq_len, n_w=n_w),
        grid=(bh, n_q, n_w),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, 128), kv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_pad, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_window_fwd",
    )(q_p, k_p, v_p, seg_p, seg_p)
    return o[:, :seq_len], lse[:, :, 0]


def _win_bwd_impl(q, k, v, seg, o, lse, do, window, sm_scale, block_q, block_k, interpret):
    bh, seq_len, d = q.shape
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0))) if x.shape[1] != s_pad else x
    q_p, k_p, v_p, do_p = pad(q), pad(k), pad(v), pad(do)
    seg_p = _seg_lanes(seg, bh, s_pad)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BH, S]
    if delta.shape[1] != s_pad:
        delta = jnp.pad(delta, ((0, 0), (0, s_pad - delta.shape[1])))
    delta = jnp.broadcast_to(delta[:, :, None], (bh, s_pad, 128))
    lse_p = jnp.broadcast_to(lse[:, :, None], (bh, s_pad, 128))
    n_q, n_k = s_pad // block_q, s_pad // block_k

    first_q = functools.partial(_first_q_block, block_q=block_q, block_k=block_k)
    last_q = functools.partial(_last_q_block, block_q=block_q, block_k=block_k, window=window,
                               n_q=n_q)
    n_wq = _band_steps(n_k, first_q, last_q)
    qi = lambda b, jk, j: (b, jnp.minimum(first_q(jk) + j, last_q(jk)), 0)
    kj = lambda b, jk, j: (b, jk, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_win_dkv_kernel, sm_scale=sm_scale, window=window, block_q=block_q,
                          block_k=block_k, seq_len=seq_len, n_q=n_q, n_w=n_wq),
        grid=(bh, n_k, n_wq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), qi),
            pl.BlockSpec((1, block_k, d), kj),
            pl.BlockSpec((1, block_k, d), kj),
            pl.BlockSpec((1, block_q, d), qi),
            pl.BlockSpec((1, block_q, 128), qi),
            pl.BlockSpec((1, block_q, 128), qi),
            pl.BlockSpec((1, block_q, 128), qi),
            pl.BlockSpec((1, block_k, 128), kj),
        ],
        out_specs=[pl.BlockSpec((1, block_k, d), kj), pl.BlockSpec((1, block_k, d), kj)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_window_dkv",
    )(q_p, k_p, v_p, do_p, lse_p, delta, seg_p, seg_p)

    first_k = functools.partial(_first_k_block, block_q=block_q, block_k=block_k, window=window)
    last_k = functools.partial(_last_k_block, block_q=block_q, block_k=block_k)
    n_wk = _band_steps(n_q, first_k, last_k)
    qrow = lambda b, i, j: (b, i, 0)
    kv = lambda b, i, j: (b, jnp.minimum(first_k(i) + j, last_k(i)), 0)
    dq = pl.pallas_call(
        functools.partial(_win_dq_kernel, sm_scale=sm_scale, window=window, block_q=block_q,
                          block_k=block_k, seq_len=seq_len, n_w=n_wk),
        grid=(bh, n_q, n_wk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), qrow),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_q, d), qrow),
            pl.BlockSpec((1, block_q, 128), qrow),
            pl.BlockSpec((1, block_q, 128), qrow),
            pl.BlockSpec((1, block_q, 128), qrow),
            pl.BlockSpec((1, block_k, 128), kv),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), qrow),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_window_dq",
    )(q_p, k_p, v_p, do_p, lse_p, delta, seg_p, seg_p)
    return dq[:, :seq_len], dk[:, :seq_len], dv[:, :seq_len]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_window(q, k, v, seg, window, sm_scale, block_q, block_k, interpret):
    return _win_fwd_impl(q, k, v, seg, window, sm_scale, block_q, block_k, interpret)[0]


def _flash_window_fwd(q, k, v, seg, window, sm_scale, block_q, block_k, interpret):
    o, lse = _win_fwd_impl(q, k, v, seg, window, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, seg, o, lse)


def _flash_window_bwd(window, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, seg, o, lse = res
    dq, dk, dv = _win_bwd_impl(q, k, v, seg, o, lse, do, window, sm_scale,
                               block_q, block_k, interpret)
    return dq, dk, dv, np.zeros(seg.shape, dtype=jax.dtypes.float0)


_flash_window.defvjp(_flash_window_fwd, _flash_window_bwd)


def _reference(q, k, v, causal, sm_scale, seg=None, bias=None, window=None):
    """XLA fallback; identical math, fp32 softmax. [BH, S, D] layout;
    ``seg``: [BH, S] int32 segment ids; ``bias``: [BH, Sq, Sk]; ``window``
    (causal only): the newest keys a query attends."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    valid = jnp.ones(s.shape[-2:], bool)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        valid = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            valid = jnp.logical_and(valid, ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window))
    valid = jnp.broadcast_to(valid, s.shape)
    if seg is not None:
        valid = jnp.logical_and(valid, seg[:, :, None] == seg[:, None, :])
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=1024, block_k=1024,
                    segment_ids=None, bias=None, interpret=None, force_pallas=None, window=None):
    """Blocked flash attention on [B, S, H, D] tensors.

    ``force_pallas``: True runs the Pallas kernels or raises, False the
    XLA reference, None picks by ``use_pallas()``. The kernels compile
    unless ``interpret=True`` asks otherwise (the unit tests off-TPU).

    ``segment_ids``: [B, S] int32 — packed sequences attend only within
    equal ids (composes with ``causal``); supported by the kernels.
    ``bias``: additive [B, 1 or H, Sq, Sk] (Evoformer-style); bias
    tensors are O(S^2) by construction, so this path uses the XLA
    reference — blocking saves nothing over an S^2 operand — and is
    differentiable through bias.
    ``window``: a query attends its ``window`` newest keys, itself among
    them (``q - window < k <= q``); causal only, composes with
    ``segment_ids``. The kernels walk the band's block pairs alone.
    """
    b, s, h, d = q.shape
    if window is not None and not causal:
        raise ValueError("flash_attention(window=...) is the causal band: causal=False has no window")
    if window is not None and window < 1:
        raise ValueError(f"flash_attention: window {window} < 1")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    if force_pallas and bias is not None:
        raise ValueError("flash_attention(force_pallas=True) with an additive bias: "
                         "the bias path has no kernel, only the XLA reference")
    if force_pallas is None:
        force_pallas = use_pallas()
    if interpret is None:
        interpret = default_interpret()

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], s, d)

    def from_bh(x, heads):
        return x.reshape(b, heads, s, d).transpose(0, 2, 1, 3)

    seg_bh = None
    if segment_ids is not None:
        seg_bh = jnp.repeat(jnp.asarray(segment_ids, jnp.int32), h, axis=0)  # [B*H, S]

    if bias is not None:
        bias = jnp.broadcast_to(bias, (b, h, s, s)).reshape(b * h, s, s)
        out = _reference(to_bh(q), to_bh(k), to_bh(v), causal, sm_scale,
                         seg=seg_bh, bias=bias, window=window)
        return from_bh(out, h)
    if not force_pallas:
        out = _reference(to_bh(q), to_bh(k), to_bh(v), causal, sm_scale, seg=seg_bh,
                         window=window)
        return from_bh(out, h)
    if seg_bh is None:
        seg_bh = jnp.zeros((b * h, s), jnp.int32)
    if window is not None:
        out = _flash_window(to_bh(q), to_bh(k), to_bh(v), seg_bh, int(window), sm_scale,
                            block_q, block_k, interpret)
        return from_bh(out, h)
    out = _flash(to_bh(q), to_bh(k), to_bh(v), seg_bh, causal, sm_scale,
                 block_q, block_k, interpret)
    return from_bh(out, h)
