"""Whole rows copied by index: the two moves of an expert layout.

A rank of the training exchange (``ops/grouped_gemm.expert_share_exchange_ffn``)
moves a held pick's row twice: from its token's row into its slot of the
grouped matmul's tile-aligned layout, and the down product's row from that
slot into its token's sum. Both are one of two functions here, each the
other's transpose:

- :func:`gather_rows` ``(src [N, D], idx [S]) -> [S, D]``: slot ``s`` gets
  ``src[idx[s]]``; a slot whose index is the sentinel (``>= N``: a tile's
  padding, a pass's filler) is **written as zeros and reads nothing**.
- :func:`gather_sum_rows` ``(src [S, D], slots [T, k], w [T, k] or None) ->
  [T, D]`` float32: ``out[t] = sum_j w[t, j] src[slots[t, j]]``, in the
  order of ``j``, a sentinel slot (``>= S``) **skipped** - not multiplied by
  zero: what lies in the rows nobody names (NaN or not) reaches no sum.

``idx`` and ``slots`` name each other's places (``idx[slots[t, j]] == t``
for every live slot), so the cotangent of one function is the other applied
to the cotangent: the two ``custom_vjp`` rules below call each other and
derive nothing. A call that is differentiated is given both arrays. (The
exchange itself differentiates neither since PR 60: its backward is written
out and calls :func:`gather_rows` and :func:`gather_sum_rows_onto` - the sum
written onto an accumulator, so that one pass of many adds where it writes.)

**The kernels.** A DMA can take eight rows of a tiled ``[N, D]`` array or
none (Mosaic: a slice along dimension 0 must be aligned to the tiling), so
the source is first laid out a row at a time, ``[N, 1, W]`` of 32-bit words
(``moe_rows_pack``, a plain pass: a 16-bit row's two halves share a word);
there ``src.at[row]`` is one contiguous copy. ``moe_rows_gather`` /
``moe_rows_sum`` then take a block of slots (of tokens) a grid step: the
block's **live** rows are listed first in two SMEM blocks (source row,
buffer row - :func:`_copy_lists`, made by XLA from integers), one DMA is
started a listed row into a ``[rows, 1, W]`` VMEM buffer - the next block's
before this block's are waited for, so a block's copies fly under the
arithmetic of the block before - and all of a block's copies are waited for
with one wait a set bit of their count (a DMA semaphore counts bytes:
:func:`_wait_rows`; a wait a row cost four times the rest of the kernel).
The vector unit reads the buffer dense, splits the words, selects zeros at
the sentinel (a buffer row nobody wrote holds whatever it held) and, in the
sum, multiplies and adds ``plane j``, every token's ``j``-th held pick, in
float32 - a token's held picks are moved to the front of its row first, so a
block looks at as many planes as its fullest token holds picks, not at ``k``.

Off the TPU both are their ``jnp`` forms (``kernel=False``): a ``take``
that fills, a masked ``take`` and a sum.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GATHER_BLOCK = 256      # slots a grid step of ``moe_rows_gather``
SUM_BLOCK = 16          # tokens a grid step of ``moe_rows_sum`` (``k`` candidate rows each)
PACK_BLOCK = 512        # rows a grid step of ``moe_rows_pack``
_UNROLL = 8             # row copies started an iteration of the loop
_VMEM_LIMIT = 64 << 20


def _pack(dtype):
    """Elements of ``dtype`` a 32-bit word holds."""
    return 4 // jnp.dtype(dtype).itemsize


def rows_kernel_supported(width, dtype):
    """Can the kernels copy rows of ``width`` elements of ``dtype``? 2 or 4
    bytes an element, whole 128-lane vregs of 32-bit words a row."""
    return jnp.dtype(dtype).itemsize in (2, 4) and width % (128 * _pack(dtype)) == 0


def _words(x):
    """A dense block ``[R, D]`` → its rows as 32-bit words ``[R, W]``: a
    16-bit row's element ``m`` in the low half of word ``m``, element ``W + m``
    in the high half (float32 widens a 16-bit float to its bits and 16 zeros)."""
    if x.dtype.itemsize == 4:
        return pltpu.bitcast(x, jnp.uint32)
    W = x.shape[1] // 2
    bits = lambda a: pltpu.bitcast(a.astype(jnp.float32), jnp.uint32)
    return (bits(x[:, :W]) >> 16) | (bits(x[:, W:]) & jnp.uint32(0xFFFF0000))


def _halves(words, pack):
    """Rows of words ``[R, W]`` → their elements as float32, ``[R, W]`` a
    half: (low, high) of a 16-bit type, the one of a 32-bit type."""
    f32 = lambda a: pltpu.bitcast(a, jnp.float32)
    if pack == 1:
        return (f32(words),)
    return f32(words << 16), f32(words & jnp.uint32(0xFFFF0000))


def _pack_kernel(x_ref, out_ref):
    out_ref[:, 0, :] = _words(x_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _as_rows(x, interpret):
    """``[N, D]`` as the tiled layout holds it → ``[N, 1, W]`` uint32, a row
    ``W`` contiguous words (a DMA can address it alone; of a tiled ``[N, D]``
    it can take eight rows or none). ``pallas_call`` ``moe_rows_pack``: XLA's
    own change of layout its compiler costs at ten times a plain pass."""
    N, D = x.shape
    W = D // _pack(x.dtype)
    block = min(PACK_BLOCK, -(-N // 16) * 16)
    return pl.pallas_call(
        _pack_kernel, grid=(-(-N // block),),
        in_specs=[pl.BlockSpec((block, D), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, 1, W), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1, W), jnp.uint32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="moe_rows_pack")(x)


def _start_rows(count, from_ref, to_ref, src_ref, buf, sem):
    """One DMA a listed row: entry ``i < count`` of the SMEM blocks copies
    ``src_ref[from_ref[i]]`` to ``buf[to_ref[i]]``. The list holds live rows
    alone, so the loop has no branch: :data:`_UNROLL` starts an iteration,
    then the rest."""

    def start(i):
        pltpu.make_async_copy(src_ref.at[from_ref[0, 0, i]], buf.at[to_ref[0, 0, i]], sem).start()

    def several(g, carry):
        for u in range(_UNROLL):
            start(g * _UNROLL + u)
        return carry

    def one(i, carry):
        start(i)
        return carry

    whole = count // _UNROLL
    jax.lax.fori_loop(0, whole, several, 0)
    jax.lax.fori_loop(whole * _UNROLL, count, one, 0)


def _wait_rows(count, buf, sem):
    """Wait for ``count`` row copies into ``buf`` on ``sem``. A DMA semaphore
    counts bytes, so one wait a set bit of ``count`` for that many rows at once
    (a descriptor of the buffer's first ``2**bit`` rows) does: nine waits for
    256 rows, not 256."""
    for bit in range(buf.shape[0].bit_length()):
        rows = min(1 << bit, buf.shape[0])

        @pl.when((count >> bit) & 1 == 1)
        def _():
            pltpu.make_async_copy(buf.at[pl.ds(0, rows)], buf.at[pl.ds(0, rows)], sem).wait()


def _pipelined(counts_ref, lists, src_ref, buf, sems):
    """Block ``i``'s rows waited for in ``buf[i % 2]``, block ``i + 1``'s
    started behind them (block 0's by step 0 itself) → the buffer's side.
    ``lists``: this block's (from, to) SMEM blocks, then the next block's."""
    i, n = pl.program_id(0), pl.num_programs(0)
    side = i & 1

    @pl.when(i == 0)
    def _():
        _start_rows(counts_ref[0], *lists[:2], src_ref, buf.at[0], sems.at[0])

    @pl.when(i + 1 < n)
    def _():
        _start_rows(counts_ref[i + 1], *lists[2:], src_ref, buf.at[1 - side], sems.at[1 - side])

    _wait_rows(counts_ref[i], buf.at[side], sems.at[side])
    return side


def _gather_kernel(counts_ref, f0, t0, f1, t1, idx_ref, src_ref, out_ref, buf, sems, *, n_src,
                   pack):
    side = _pipelined(counts_ref, (f0, t0, f1, t1), src_ref, buf, sems)
    live = idx_ref[...] < n_src
    W = buf.shape[-1]
    # a sentinel's buffer row holds whatever it held: selected away, NaN or not
    for h, half in enumerate(_halves(buf[side, :, 0, :], pack)):
        out_ref[:, h * W:(h + 1) * W] = jnp.where(live, half, 0.0).astype(out_ref.dtype)


def _sum_kernel(counts_ref, planes_ref, first_ref, f0, t0, f1, t1, slots_ref, *rest, n_src, block,
                k, pack, weighted):
    w_ref = rest[0] if weighted else None
    src_ref, acc_ref, out_ref, buf, sems = rest[-5:]
    side = _pipelined(counts_ref, (f0, t0, f1, t1), src_ref, buf, sems)
    W = buf.shape[-1]

    @pl.when(first_ref[0] != 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    @pl.when(first_ref[0] == 0)
    def _():
        out_ref[...] = acc_ref[...]

    for j in range(k):      # plane j: every token's j-th held pick; the block's fullest token has
        @pl.when(j < planes_ref[pl.program_id(0)])                          # planes_ref[i] of them
        def _():
            live = slots_ref[:, j:j + 1] < n_src
            for h, half in enumerate(_halves(buf[side, pl.ds(j * block, block), 0, :], pack)):
                if weighted:
                    half = half * w_ref[:, j:j + 1]
                out_ref[:, h * W:(h + 1) * W] += jnp.where(live, half, 0.0)


def _held_first(slots, w, n_src):
    """A token's held picks moved to the front of its row, in their order
    (the sum's order is theirs), the sentinels behind → (slots, w, how many
    each token holds): a block's planes past its fullest token's are not
    looked at. Comparisons and sums over ``[T, k, k]``, no sort."""
    k = slots.shape[1]
    live = slots < n_src
    rank = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
    moved = live[:, :, None] & (rank[:, :, None] == jnp.arange(k, dtype=jnp.int32))   # [T, from, to]
    held = jnp.sum(live, axis=1, dtype=jnp.int32)
    first = jnp.where(jnp.arange(k, dtype=jnp.int32) < held[:, None],
                      jnp.sum(jnp.where(moved, slots[:, :, None], 0), axis=1), n_src)
    if w is not None:
        w = jnp.sum(jnp.where(moved, w[:, :, None], 0), axis=1)
    return first, w, held


def _copy_lists(live, rows_from, rows_to, size):
    """The copies of every block of ``size`` entries, the live ones first:
    ``live`` [n] bool, ``rows_from`` [n] (a source row an entry), ``rows_to``
    [size] (the buffer row of a block's entry) → (live entries a block
    ``[blocks]``, the kernel's SMEM operands - the lists ``[blocks, 1, size]``
    of this grid step and of the next - and their specs)."""
    n_blocks = -(-live.shape[0] // size)
    pad = n_blocks * size - live.shape[0]
    live = jnp.pad(live, (0, pad)).reshape(n_blocks, size)
    at = jnp.arange(size, dtype=jnp.int32)
    _, rows_from, rows_to = jax.lax.sort(
        (jnp.where(live, at, size + at), jnp.pad(rows_from, (0, pad)).reshape(n_blocks, size),
         jnp.broadcast_to(rows_to.astype(jnp.int32), (n_blocks, size))), dimension=1, num_keys=1)
    here = pl.BlockSpec((1, 1, size), lambda i, *_: (i, 0, 0), memory_space=pltpu.SMEM)
    ahead = pl.BlockSpec((1, 1, size), lambda i, *_: (jnp.minimum(i + 1, n_blocks - 1), 0, 0),
                         memory_space=pltpu.SMEM)
    lists = (rows_from[:, None], rows_to[:, None]) * 2
    return jnp.sum(live, axis=1, dtype=jnp.int32), lists, [here, here, ahead, ahead]


def _params():
    # blocks in order on one core: a block starts the next one's copies
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("dtype", "block", "interpret"))
def _gather_packed(rows, idx, dtype, block, interpret):
    """``moe_rows_gather`` over a source already a row at a time (:func:`_as_rows`
    of ``[N, D]`` ``dtype``) → ``[S, D]`` ``dtype``."""
    (N, _, W), S = rows.shape, idx.shape[0]
    pack = _pack(dtype)
    idx = idx.astype(jnp.int32)
    counts, lists, specs = _copy_lists(idx < N, idx, jnp.arange(block), block)
    return pl.pallas_call(
        functools.partial(_gather_kernel, n_src=N, pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(counts.shape[0],),
            in_specs=specs + [pl.BlockSpec((block, 1), lambda i, counts: (i, 0)),
                              pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, W * pack), lambda i, counts: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, block, 1, W), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((S, W * pack), dtype),
        compiler_params=_params(), interpret=interpret, name="moe_rows_gather",
    )(counts, *lists, idx[:, None], rows)


@functools.partial(jax.jit, static_argnames=("dtype", "block", "interpret"))
def _sum_packed(acc, first, rows, slots, w, dtype, block, interpret):
    """``moe_rows_sum`` over a source already a row at a time, onto ``acc``
    (:func:`gather_sum_rows_onto`) → ``[T, D]`` float32 in ``acc``'s place."""
    (S, _, W), (T, k) = rows.shape, slots.shape
    pack = _pack(dtype)
    slots, w, held = _held_first(slots.astype(jnp.int32),
                                 None if w is None else w.astype(jnp.float32), S)
    entry = jnp.arange(block * k)                       # t k + j of a block -> plane j, row t
    counts, lists, specs = _copy_lists(slots.reshape(-1) < S, slots.reshape(-1),
                                       entry % k * block + entry // k, block * k)
    planes = jnp.max(jnp.pad(held, (0, counts.shape[0] * block - T)).reshape(-1, block), axis=1)
    by_token = pl.BlockSpec((block, k), lambda i, *_: (i, 0))
    weights = () if w is None else (w,)
    first = jnp.asarray(first, jnp.int32).reshape(1)
    # the first pass asks for the accumulator's block 0 all through: fetched once, read never
    onto = pl.BlockSpec((block, W * pack), lambda i, counts, planes, first: (
        jnp.where(first[0] != 0, 0, i), 0))
    operands = (counts, planes, first, *lists, slots, *weights, rows, acc)
    return pl.pallas_call(
        functools.partial(_sum_kernel, n_src=S, block=block, k=k, pack=pack,
                          weighted=w is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(counts.shape[0],),
            in_specs=specs + [by_token] * (1 + len(weights))
            + [pl.BlockSpec(memory_space=pl.ANY), onto],
            out_specs=pl.BlockSpec((block, W * pack), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k * block, 1, W), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T, W * pack), jnp.float32),
        input_output_aliases={len(operands) - 1: 0},
        compiler_params=_params(), interpret=interpret, name="moe_rows_sum",
    )(*operands)


def _gather(src, idx, kernel, interpret):
    if kernel:
        return _gather_packed(_as_rows(src, interpret), idx, src.dtype, GATHER_BLOCK, interpret)
    return jnp.take(src, idx, axis=0, mode="fill", fill_value=0)


def _sum_jnp(src, slots, w):
    rows = jnp.take(src, slots, axis=0, mode="fill", fill_value=0).astype(jnp.float32)
    if w is not None:
        rows = rows * w.astype(jnp.float32)[..., None]
    return jnp.sum(jnp.where((slots < src.shape[0])[..., None], rows, 0.0), axis=1)


def gather_sum_rows_onto(acc, first, src, slots, w=None, kernel=False, interpret=False):
    """:func:`gather_sum_rows` written **onto** ``acc`` [T, D] float32, which
    the result takes the place of: ``first`` (a traced bool) - the sum alone,
    and ``acc`` is not read (it may hold anything: ``jax.lax.empty``);
    otherwise ``acc +`` the sum, added in the kernel's own output block, so
    one pass of many costs no pass over ``[T, D]`` of its own and the first
    writes no zeros. Not differentiable: the exchange's backward is written
    out (``ops/grouped_gemm._share_passes_bwd``)."""
    if kernel:
        return _sum_packed(acc, first, _as_rows(src, interpret), slots, w, src.dtype, SUM_BLOCK,
                           interpret)
    out = _sum_jnp(src, slots, w)
    return jnp.where(first, out, acc + out)


def _gather_sum(src, slots, w, kernel, interpret):
    if kernel:
        acc = jax.lax.empty((slots.shape[0], src.shape[1]), jnp.float32)
        return gather_sum_rows_onto(acc, True, src, slots, w, kernel, interpret)
    return _sum_jnp(src, slots, w)


def _no_cotangent(index):
    return None if index is None else np.zeros(index.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gather_rows(src, idx, slots=None, kernel=False, interpret=False):
    """``src[idx]`` with zeros at the sentinel (module docstring).
    ``slots`` [N, k] (only where the call is differentiated): the slots that
    name each source row, sentinel ``>= len(idx)`` elsewhere. ``kernel``:
    the Pallas kernel (``interpret``: interpreted) and not the ``jnp`` form."""
    return _gather(src, idx, kernel, interpret)


def _gather_rows_fwd(src, idx, slots, kernel, interpret):
    return _gather(src, idx, kernel, interpret), (idx, slots, jnp.zeros((0,), src.dtype))


def _gather_rows_bwd(kernel, interpret, res, d_out):
    idx, slots, proto = res
    if slots is None:
        raise ValueError("gather_rows is differentiated: it needs the slots of every source row")
    d_src = gather_sum_rows(d_out, slots, None, idx, kernel, interpret).astype(proto.dtype)
    return d_src, _no_cotangent(idx), _no_cotangent(slots)


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gather_sum_rows(src, slots, w=None, idx=None, kernel=False, interpret=False):
    """``sum_j w[t, j] src[slots[t, j]]`` in float32, sentinels skipped
    (module docstring). ``idx`` [S] (only where the call is differentiated):
    the token of every slot, sentinel ``>= T`` elsewhere."""
    return _gather_sum(src, slots, w, kernel, interpret)


def _gather_sum_rows_fwd(src, slots, w, idx, kernel, interpret):
    return _gather_sum(src, slots, w, kernel, interpret), (src, slots, w, idx)


def _gather_sum_rows_bwd(kernel, interpret, res, d_out):
    src, slots, w, idx = res
    if idx is None:
        raise ValueError("gather_sum_rows is differentiated: it needs the token of every slot")
    # a slot's cotangent is its token's row (zeros at the sentinel), in the rows' own type
    rows = gather_rows(d_out.astype(src.dtype), idx, slots, kernel, interpret)
    if w is None:
        return rows, _no_cotangent(slots), None, _no_cotangent(idx)
    f32 = jnp.float32
    by_slot = jnp.zeros(src.shape[:1], f32).at[slots.reshape(-1)].set(
        w.reshape(-1).astype(f32), mode="drop")
    d_src = (rows.astype(f32) * by_slot[:, None]).astype(src.dtype)
    # a pick's weight: its slot's cotangent row against its slot's row, in layout order
    dots = jnp.sum(rows.astype(f32) * src.astype(f32), axis=-1)
    d_w = jnp.take(dots, slots, mode="fill", fill_value=0).astype(w.dtype)
    return d_src, _no_cotangent(slots), d_w, _no_cotangent(idx)


gather_sum_rows.defvjp(_gather_sum_rows_fwd, _gather_sum_rows_bwd)
