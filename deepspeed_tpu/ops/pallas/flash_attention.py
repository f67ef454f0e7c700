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
``q - W < k <= q``. The band runs the same three kernel bodies as the
triangle - ``window=None`` is a band with no far edge - under names of its
own in a device trace (``flash_window_fwd`` / ``_dkv`` / ``_dq`` beside
``flash_attention_fwd`` / ``_dkv`` / ``_dq``). The grids walk only the block
pairs that hold a pair of the mask - a query block's keys from its window's
first block to its diagonal's, a key block's queries from its diagonal's block
to the last row that still sees it - and a grid step past them names the block
before it, so nothing is read for it.

**A piece of scores is done by what it holds** (PR 62). A block of
``block_q x block_k`` scores is walked in pieces, and for each piece scalars
decide, before any vector work, which of three classes it is
(``_piece_class``, a function of the piece's first and last query and key,
``window`` and ``seq_len``). The side of a piece is derived from the block
and the kernel (``_grain``; never an argument, never the environment): the
backward kernels walk halves of a block (512 at the default 1024, where a
half is whole lane tiles; a smaller block is one piece), the forward kernel
the block itself - on a v5e a forward piece pays its own row maximum and row
sum across the lanes and its own pass over the running statistics, and
pieces of 512 (256) took 1.3 (2.2) times the block's time for 3/4 (5/8) of
its products, while the backward, which reduces nothing by row, ran the
band 14 % faster in pieces of 512 and 35 % slower in pieces of 256
(``tools/kernel_census.py --flash-window``, PR 62).

- **empty** - no pair of it counts (above the diagonal, behind the window's
  far edge, past ``seq_len``): no product, no exponential.
- **whole** - every pair counts: the products, the running maximum, the
  exponential and the sums, and no iota, no comparison, no ``where`` - forward
  and backward (``p = exp(s - lse)`` as it comes). A block that is whole is
  one piece: one product of the block's size, as before there were pieces.
- **crossed** - an edge runs through it (the diagonal, the far edge, the padded
  end, and with ``segment_ids`` any piece, since positions cannot say where a
  segment ends): the mask, over that piece alone.

``segment_ids=None`` hands the kernels no segment operand and builds no
segment term; with ids every computed piece is crossed, as every block was.
``flash_schedule`` is the static counter of all this - pairs skipped, whole
and crossed, over the pairs the mask needs - and ``piece_classes`` the classes
themselves, which the tests hold against the dense mask.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# a piece of scores, and what it holds
# ---------------------------------------------------------------------------

# Pieces a side of a block, by kernel (the default block of 1024 on a v5e, PR 62's census): the
# forward walks a block whole - a piece pays its own row maximum and row sum across the lanes and
# its own pass over the running statistics, and pieces of 512 (256) took 1.3 (2.2) times the
# block's time for 3/4 (5/8) of its products -; the backward kernels, which reduce nothing by row,
# walk pieces of 512 (the band's backward 6.6 -> 5.7 ms a call; 256: 8.9).
_FORWARD_PIECES = 1
_BACKWARD_PIECES = 2
_LANES = 128


def _grain(block, pieces):
    """The side of a piece: a block's ``pieces``-th where that is whole lane
    tiles, else the block (a small block is one piece)."""
    return block // pieces if block % (pieces * _LANES) == 0 else block


def _piece_class(qa, qb, ka, kb, seq_len, causal, window, segments):
    """→ (empty, whole) of the scores of queries ``qa..qb`` by keys ``ka..kb``
    (both ends counted): no pair of them counts / every pair does. A piece that
    is neither is crossed by an edge. Python ints, numpy arrays or the scalars
    of a kernel; ``segments``: ids were passed, and positions alone cannot say
    that every pair counts."""
    empty, whole = ka >= seq_len, kb < seq_len
    if causal:
        empty, whole = empty | (ka > qb), whole & (kb <= qa)
    if window is not None:      # (the last term: a padded query row whose whole band is padding)
        empty = empty | (kb <= qa - window) | (qa - window + 1 >= seq_len)
        whole = whole & (ka > qb - window)
    if segments:
        whole = False
    return empty, whole


def _k_blocks(iq, block_q, block_k, n_k, causal, window):
    """(first, last) key block that holds a pair of query block ``iq``: from its
    first row's oldest key to its last row's newest. ``iq`` a Python int (the
    grid's length) or traced (an index map, a kernel)."""
    first = 0
    if window is not None:
        oldest = iq * block_q - (window - 1)
        first = (max(oldest, 0) if isinstance(iq, int) else jnp.maximum(oldest, 0)) // block_k
    last = (iq * block_q + block_q - 1) // block_k if causal else n_k - 1
    return first, last


def _q_blocks(ik, block_q, block_k, n_q, causal, window):
    """(first, last) query block with a row that sees a key of block ``ik``."""
    first = (ik * block_k) // block_q if causal else 0
    last = n_q - 1
    if window is not None:
        newest = (ik * block_k + block_k - 1 + window - 1) // block_q
        last = min(newest, last) if isinstance(ik, int) else jnp.minimum(newest, last)
    return first, last


def _band(blocks, n_outer):
    """A grid that walks ``blocks(i) = (first, last)`` for each outer block:
    (the walked operand's index map, the inner grid's length - the most blocks
    any outer block walks). A step past ``last`` names ``last`` again, so
    nothing is read for it."""
    def index(b, i, j):
        first, last = blocks(i)
        return b, jnp.minimum(first + j, last), 0

    return index, max(last - first + 1 for first, last in map(blocks, range(n_outer)))


def piece_classes(seq_len, window=None, causal=True, block_q=1024, block_k=1024, grain=None,
                  backward=False):
    """The schedule as the kernels decide it, for every piece of the padded
    square of scores: an int array [query pieces, key pieces] of 0 (empty), 1
    (whole), 2 (crossed), and the pieces' sides: the forward kernel's, or the
    two ``backward`` kernels'. ``grain``: (rows, columns) of a piece in place of
    the derived ones."""
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    pieces = _BACKWARD_PIECES if backward else _FORWARD_PIECES
    gq, gk = grain or (_grain(block_q, pieces), _grain(block_k, pieces))
    qa = np.arange(0, s_pad, gq)[:, None]
    ka = np.arange(0, s_pad, gk)[None, :]
    empty, whole = _piece_class(qa, qa + gq - 1, ka, ka + gk - 1, seq_len, causal, window, False)
    classes = np.where(empty, 0, np.where(whole, 1, 2))
    return np.broadcast_to(classes, (s_pad // gq, s_pad // gk)), (gq, gk)


def flash_schedule(seq_len, window=None, causal=True, block_q=1024, block_k=1024, grain=None,
                   backward=False):
    """What one head's call computes, by class, beside what attention needs: the
    static counter of how often each class engages (the schedule is a function
    of the shapes). ``pairs`` are scores: ``skipped`` (empty pieces: no
    product), ``whole`` (products, no mask), ``crossed`` (products and the
    mask) sum to the padded square; ``needed`` the pairs of the mask itself;
    ``tiles`` the blocks a grid runs; of the forward kernel, or of each of the
    two ``backward`` kernels. Without segment ids, which keep every computed
    piece crossed."""
    classes, (gq, gk) = piece_classes(seq_len, window, causal, block_q, block_k, grain, backward)
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    n_q, n_k = s_pad // block_q, s_pad // block_k
    tiles = sum(last - first + 1 for first, last in
                (_k_blocks(i, block_q, block_k, n_k, causal, window) for i in range(n_q)))
    pairs = {name: int((classes == c).sum()) * gq * gk
             for c, name in enumerate(("skipped", "whole", "crossed"))}
    rows = np.arange(1, seq_len + 1)
    needed = int(np.minimum(rows, window or seq_len).sum()) if causal else seq_len * seq_len
    computed = pairs["whole"] + pairs["crossed"]
    return {"tiles": tiles, "piece": [gq, gk], "pairs": pairs, "needed": needed,
            "computed_over_needed": computed / needed,
            "masked_over_computed": pairs["crossed"] / computed}


def _walk(piece, iq, ik, pieces, *, block_q, block_k, seq_len, causal, window, segments):
    """One block of scores by what it holds: ``piece(q0, rows, k0, cols, masked)``
    once over the block where every pair of it counts, else over each of its
    ``pieces`` x ``pieces`` pieces that is not empty, ``masked`` where an edge
    crosses that piece.
    Scalars decide; no vector work is done for the decision."""
    def classes(q0, rows, k0, cols):
        qa, ka = iq * block_q + q0, ik * block_k + k0
        return _piece_class(qa, qa + rows - 1, ka, ka + cols - 1, seq_len, causal, window,
                            segments)

    def visit(q0, rows, k0, cols):
        empty, whole = classes(q0, rows, k0, cols)
        if whole is not False:
            pl.when(whole)(lambda: piece(q0, rows, k0, cols, False))
        pl.when(jnp.logical_not(empty | whole))(lambda: piece(q0, rows, k0, cols, True))

    gq, gk = _grain(block_q, pieces), _grain(block_k, pieces)
    n_cols = block_k // gk
    n = (block_q // gq) * n_cols
    if n == 1:
        return visit(0, block_q, 0, block_k)
    _, block_whole = classes(0, block_q, 0, block_k)

    def one(t, carry):
        visit(pl.multiple_of((t // n_cols) * gq, gq), gq, pl.multiple_of((t % n_cols) * gk, gk), gk)
        return carry

    if block_whole is False:
        return jax.lax.fori_loop(0, n, one, None)
    pl.when(block_whole)(lambda: piece(0, block_q, 0, block_k, False))
    pl.when(jnp.logical_not(block_whole))(lambda: jax.lax.fori_loop(0, n, one, None))


def _valid(shape, qa, ka, seq_len, causal, window, seg_q, seg_k):
    """The mask of a crossed piece whose first query is ``qa`` and first key
    ``ka``, from the pairs' distance ``q - k`` inside the piece and two scalar
    thresholds. ``seg_q``/``seg_k``: [rows, 1] / [cols, 1] int32 segment ids -
    packed sequences attend only within equal ids - or None."""
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    behind = jax.lax.broadcasted_iota(jnp.int32, shape, 0) - col + (qa - ka)     # q - k
    valid = col < seq_len - ka
    if causal:
        valid = jnp.logical_and(valid, behind >= 0)
    if window is not None:
        valid = jnp.logical_and(valid, behind < window)
    if seg_q is not None:
        valid = jnp.logical_and(valid, seg_q == jnp.transpose(seg_k))
    return valid


def _rows(ref, start, size):
    return ref[0, pl.ds(start, size), :]


def _scores_and_mask(q, k, seg, iq, ik, q0, k0, masked, *, sm_scale, block_q, block_k, seq_len,
                     causal, window):
    """float32 scores of a piece, scaled, and its mask (None: every pair
    counts). MXU inputs stay in the storage dtype (bf16): fp32 operands run the
    MXU at a fraction of peak; accumulation is fp32 regardless."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if not masked:
        return s, None
    seg_q = seg_k = None
    if seg is not None:
        seg_q = _rows(seg[0], q0, q.shape[0])[:, :1]
        seg_k = _rows(seg[1], k0, k.shape[0])[:, :1]
    return s, _valid(s.shape, iq * block_q + q0, ik * block_k + k0, seq_len, causal, window,
                     seg_q, seg_k)


def _fwd_kernel(*refs, has_seg, n_w, n_q, n_k, sm_scale, block_q, block_k, seq_len, causal, window):
    q_ref, k_ref, v_ref = refs[:3]
    seg = refs[3:5] if has_seg else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[-5:]
    iq = pl.program_id(1)
    j = pl.program_id(2)
    first, last = _k_blocks(iq, block_q, block_k, n_k, causal, window)
    ik = first + j
    edges = dict(block_q=block_q, block_k=block_k, seq_len=seq_len, causal=causal, window=window)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def piece(q0, rows, k0, cols, masked):
        v = _rows(v_ref, k0, cols)
        s, valid = _scores_and_mask(_rows(q_ref, q0, rows), _rows(k_ref, k0, cols), seg, iq, ik,
                                    q0, k0, masked, sm_scale=sm_scale, **edges)
        if masked:
            s = jnp.where(valid, s, NEG_INF)
        at = pl.ds(q0, rows)
        m_prev = m_scr[at, :1]
        l_prev = l_scr[at, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row that has seen no pair yet counts exp(0) = 1 for its masked scores: its first pair
        # (at the latest its own key, the last block's) brings alpha = 0 and takes that away, and
        # a padded row that never sees one is dropped; the backward selects its p
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[at, :] = acc_scr[at, :] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[at, :] = jnp.broadcast_to(m_new, (rows, m_scr.shape[1]))
        l_scr[at, :] = jnp.broadcast_to(l_new, (rows, l_scr.shape[1]))

    @pl.when(ik <= last)
    def _body():
        _walk(piece, iq, ik, _FORWARD_PIECES, segments=has_seg, **edges)

    @pl.when(j == n_w - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse is lane-replicated to [block_q, 128] to satisfy TPU tiling
        lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l_safe), lse_ref.shape[1:])


def _probabilities(q_ref, k_ref, lse_ref, seg, iq, ik, q0, rows, k0, cols, masked, **edges):
    """The backward's ``p`` of a piece, formed again from the saved logsumexp."""
    s, valid = _scores_and_mask(_rows(q_ref, q0, rows), _rows(k_ref, k0, cols), seg, iq, ik,
                                q0, k0, masked, **edges)
    p = jnp.exp(s - _rows(lse_ref, q0, rows)[:, :1])
    return jnp.where(valid, p, 0.0) if masked else p


def _dkv_kernel(*refs, has_seg, n_w, n_q, n_k, sm_scale, block_q, block_k, seq_len, causal, window):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    seg = refs[6:8] if has_seg else None
    dk_ref, dv_ref, dk_scr, dv_scr = refs[-4:]
    ik = pl.program_id(1)
    j = pl.program_id(2)
    first, last = _q_blocks(ik, block_q, block_k, n_q, causal, window)
    iq = first + j
    edges = dict(block_q=block_q, block_k=block_k, seq_len=seq_len, causal=causal, window=window)

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def piece(q0, rows, k0, cols, masked):
        q, do = _rows(q_ref, q0, rows), _rows(do_ref, q0, rows)
        p = _probabilities(q_ref, k_ref, lse_ref, seg, iq, ik, q0, rows, k0, cols, masked,
                           sm_scale=sm_scale, **edges)
        at = pl.ds(k0, cols)
        dv_scr[at, :] = dv_scr[at, :] + jax.lax.dot_general(
            p.astype(q.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, _rows(v_ref, k0, cols), (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - _rows(delta_ref, q0, rows)[:, :1]) * sm_scale).astype(q.dtype)
        dk_scr[at, :] = dk_scr[at, :] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq <= last)
    def _body():
        _walk(piece, iq, ik, _BACKWARD_PIECES, segments=has_seg, **edges)

    @pl.when(j == n_w - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(*refs, has_seg, n_w, n_q, n_k, sm_scale, block_q, block_k, seq_len, causal, window):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    seg = refs[6:8] if has_seg else None
    dq_ref, dq_scr = refs[-2:]
    iq = pl.program_id(1)
    j = pl.program_id(2)
    first, last = _k_blocks(iq, block_q, block_k, n_k, causal, window)
    ik = first + j
    edges = dict(block_q=block_q, block_k=block_k, seq_len=seq_len, causal=causal, window=window)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def piece(q0, rows, k0, cols, masked):
        k, do = _rows(k_ref, k0, cols), _rows(do_ref, q0, rows)
        p = _probabilities(q_ref, k_ref, lse_ref, seg, iq, ik, q0, rows, k0, cols, masked,
                           sm_scale=sm_scale, **edges)
        dp = jax.lax.dot_general(do, _rows(v_ref, k0, cols), (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - _rows(delta_ref, q0, rows)[:, :1]) * sm_scale).astype(k.dtype)
        at = pl.ds(q0, rows)
        dq_scr[at, :] = dq_scr[at, :] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik <= last)
    def _body():
        _walk(piece, iq, ik, _BACKWARD_PIECES, segments=has_seg, **edges)

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


def _padded(s_pad, *arrays):
    return [jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1])) + ((0, 0),) * (x.ndim - 2))
            if x.shape[1] != s_pad else x for x in arrays]


def _lanes(x, s_pad):
    """[BH, S] → [BH, S_pad, 128] lane-replicated (TPU tiling)."""
    x, = _padded(s_pad, x)
    return jnp.broadcast_to(x[:, :, None], x.shape + (_LANES,))


def _kernel_name(window, part):
    """The band's kernels are programs of their own in a device trace."""
    return f"flash_{'attention' if window is None else 'window'}_{part}"


def _segment_operands(seg, s_pad):
    """The two segment operands (by query row, by key row), or none."""
    return [] if seg is None else [_lanes(seg, s_pad).astype(jnp.int32)] * 2


def _fwd_impl(q, k, v, seg, causal, window, sm_scale, block_q, block_k, interpret):
    """q/k/v: [BH, S, D]; seg: [BH, S] int32 or None → (o, lse [BH, S_pad])."""
    bh, seq_len, d = q.shape
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    n_q, n_k = s_pad // block_q, s_pad // block_k
    kv, n_w = _band(functools.partial(_k_blocks, block_q=block_q, block_k=block_k, n_k=n_k,
                                      causal=causal, window=window), n_q)
    qrow = lambda b, i, j: (b, i, 0)
    has_seg = seg is not None
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, has_seg=has_seg, n_w=n_w, n_q=n_q, n_k=n_k, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, seq_len=seq_len, causal=causal, window=window),
        grid=(bh, n_q, n_w),
        in_specs=[
            pl.BlockSpec((1, block_q, d), qrow),
            pl.BlockSpec((1, block_k, d), kv),
            pl.BlockSpec((1, block_k, d), kv),
        ] + [pl.BlockSpec((1, block_q, _LANES), qrow), pl.BlockSpec((1, block_k, _LANES), kv)] * has_seg,
        out_specs=[
            pl.BlockSpec((1, block_q, d), qrow),
            pl.BlockSpec((1, block_q, _LANES), qrow),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name(window, "fwd"),
    )(*_padded(s_pad, q, k, v), *_segment_operands(seg, s_pad))
    # Drop the lane replication before saving lse as a VJP residual
    # (128x HBM otherwise); the backward re-broadcasts it.
    return o[:, :seq_len], lse[:, :, 0]


def _bwd_impl(q, k, v, seg, o, lse, do, causal, window, sm_scale, block_q, block_k, interpret):
    bh, seq_len, d = q.shape
    block_q, block_k, s_pad = _blocked_shapes(seq_len, block_q, block_k)
    n_q, n_k = s_pad // block_q, s_pad // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [BH, S]
    has_seg = seg is not None
    # lse/delta lane-replicated to [BH, S_pad, 128] for TPU tiling
    operands = (*_padded(s_pad, q, k, v, do), _lanes(lse, s_pad), _lanes(delta, s_pad),
                *_segment_operands(seg, s_pad))
    static = dict(has_seg=has_seg, n_q=n_q, n_k=n_k, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                  seq_len=seq_len, causal=causal, window=window)

    def in_specs(qrow, kv):
        return ([pl.BlockSpec((1, block_q, d), qrow), pl.BlockSpec((1, block_k, d), kv),
                 pl.BlockSpec((1, block_k, d), kv), pl.BlockSpec((1, block_q, d), qrow),
                 pl.BlockSpec((1, block_q, _LANES), qrow), pl.BlockSpec((1, block_q, _LANES), qrow)]
                + [pl.BlockSpec((1, block_q, _LANES), qrow),
                   pl.BlockSpec((1, block_k, _LANES), kv)] * has_seg)

    qi, n_wq = _band(functools.partial(_q_blocks, block_q=block_q, block_k=block_k, n_q=n_q,
                                       causal=causal, window=window), n_k)
    kj = lambda b, jk, j: (b, jk, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_w=n_wq, **static),
        grid=(bh, n_k, n_wq),
        in_specs=in_specs(qi, kj),
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
        name=_kernel_name(window, "dkv"),
    )(*operands)

    kv, n_wk = _band(functools.partial(_k_blocks, block_q=block_q, block_k=block_k, n_k=n_k,
                                       causal=causal, window=window), n_q)
    qrow = lambda b, i, j: (b, i, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_w=n_wk, **static),
        grid=(bh, n_q, n_wk),
        in_specs=in_specs(qrow, kv),
        out_specs=pl.BlockSpec((1, block_q, d), qrow),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=_kernel_name(window, "dq"),
    )(*operands)
    return dq[:, :seq_len], dk[:, :seq_len], dv[:, :seq_len]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, seg, causal, window, sm_scale, block_q, block_k, interpret):
    return _fwd_impl(q, k, v, seg, causal, window, sm_scale, block_q, block_k, interpret)[0]


def _flash_fwd(q, k, v, seg, causal, window, sm_scale, block_q, block_k, interpret):
    o, lse = _fwd_impl(q, k, v, seg, causal, window, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, seg, o, lse)


def _flash_bwd(causal, window, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, seg, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, seg, o, lse, do, causal, window, sm_scale,
                           block_q, block_k, interpret)
    # int operand: no tangent
    dseg = None if seg is None else np.zeros(seg.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


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
    out = _flash(to_bh(q), to_bh(k), to_bh(v), seg_bh, causal, None if window is None else int(window),
                 sm_scale, block_q, block_k, interpret)
    return from_bh(out, h)
