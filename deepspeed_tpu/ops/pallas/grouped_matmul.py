"""Pallas grouped (per-expert) matmul — the MoE expert GEMM.

Capability match for the reference's CUTLASS grouped GEMM
(``deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm/`` — MoE expert
dispatch as one kernel over per-expert row groups). TPU redesign,
megablocks-style: the caller pads each expert's row group to a multiple
of the row-tile ``tm`` (zeros), so every (tm × K) row tile belongs to
exactly ONE expert and the kernel needs no in-tile masking at all — a
scalar-prefetched ``tile_experts`` array steers each row tile's weight
DMA (``PrefetchScalarGridSpec``: the index map picks ``w[e]`` before the
tile runs).

The row tile is fitted to the rows a group (:func:`row_tile`): ~12 rows
a group (64 experts, 128 sequences x top-6) take a 32-row tile and pad
to at most ``groups x (tm - 1)`` rows more, a training batch takes 256.
The weights may be a table of more groups than the call's
(``first_group``, a traced scalar the weight index map adds): every
layer's experts ``[L*E, K, N]`` are read where they lie, never cut out.

Grid order puts the row-tile sweep innermost so each expert's weight
block stays resident in VMEM across its whole row range (weights re-DMA
only on a group boundary): the expert weights stream through once a
call, which with few rows a group is all the call costs (HBM-bound).
The block is ``[K, tn]`` with ``tn`` the widest 128-multiple divisor of
N whose block fits :data:`_WEIGHT_BLOCK_BYTES` (:func:`col_tile`; the
whole N for narrow experts), so a weight DMA moves long rows, not
128-lane slivers. Row tiles past the last group's (the static layout
holds the worst case) are skipped: they store zeros and fetch nothing.

The backward splits per operand: dx is the same kernel against
``w.swapaxes(1, 2)``; dw accumulates ``x_tileᵀ @ dy_tile`` into a
revisited output block, initialized on each group's first row tile.

:func:`gmm_quant` is the mixed-precision variant (the reference's
``mixed_gemm`` next to ``moe_gemm``): the expert stack arrives as
grouped-layout quantized carriers and each slab is dequantized in VMEM
inside the K-loop, with the same scalar-prefetched ``tile_experts``
steering both the carrier and the scale DMA — quantized MoE serving
pays quantized HBM bandwidth, never a dequantized expert stack.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(te_ref, meta_ref, x_ref, w_ref, o_ref):
    real = pl.program_id(1) < meta_ref[1]

    @pl.when(real)
    def _tile():
        o_ref[:] = jnp.dot(x_ref[:], w_ref[0], preferred_element_type=jnp.float32
                           ).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(real))
    def _past_the_last_group():
        o_ref[:] = jnp.zeros_like(o_ref)


def _gmm_dw_kernel(te_ref, x_ref, dy_ref, o_ref):
    m = pl.program_id(2)
    upd = jax.lax.dot_general(
        x_ref[:], dy_ref[:], dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((m == 0) | (te_ref[m] != te_ref[jnp.maximum(m - 1, 0)]))
    def _init():
        o_ref[0] = upd

    @pl.when((m != 0) & (te_ref[m] == te_ref[jnp.maximum(m - 1, 0)]))
    def _acc():
        o_ref[0] += upd


def _gmm_dw_onto_kernel(te_ref, fresh_ref, tiles_ref, x_ref, dy_ref, acc_ref, o_ref):
    m = pl.program_id(2)

    @pl.when(m < tiles_ref[0])
    def _tile():
        upd = jax.lax.dot_general(
            x_ref[:], dy_ref[:], dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        new = (m == 0) | (te_ref[m] != te_ref[jnp.maximum(m - 1, 0)])
        fresh = fresh_ref[te_ref[m]] != 0

        @pl.when(new & fresh)
        def _init():
            o_ref[0] = upd

        @pl.when(new & jnp.logical_not(fresh))
        def _onto():
            o_ref[0] = acc_ref[0] + upd

        @pl.when(jnp.logical_not(new))
        def _acc():
            o_ref[0] += upd


def _fit_tile(t, dim):
    """Largest divisor of ``dim`` that is ≤ t and a multiple of 128 (the
    lane width) when possible — tiles MUST divide the dim exactly or the
    grid silently drops the remainder.

    When nothing on the search ladder (multiples of 128 below ``t``,
    then multiples of 8 below 128) divides ``dim``, raise instead of
    quietly shipping a degenerate tile: an 8-row (or worse, 1-row) tile
    turns one matmul into hundreds of grid steps, and past callers only
    discovered the cliff in profiles.
    """
    t = min(t, dim)
    start = t
    while dim % t:
        t -= 128 if t > 128 else 8
        if t <= 8:
            raise ValueError(
                f"_fit_tile: no legal kernel tile for dim {dim}: nothing "
                f"on the search ladder below {start} (multiples of 128, "
                f"then of 8, down to the tile floor of 8) divides it. "
                "Pad the dim to a multiple of 8 or dispatch this shape "
                "to the non-Pallas fallback.")
    return t


# One weight block [K, tn] of the pipeline's two. 28 MiB holds a whole
# [2048, 1408] expert matrix and keeps a 4096-deep block 3584 columns wide, a
# 14336-deep one 1024 (rows of 2-7 KiB a DMA): on v5e the widest blocks that
# fit read fastest at every shape the census times, and a 256-column block
# a fifth to a third slower (PERF.md, PR 31: tools/kernel_census.py --gmm-sweep).
_WEIGHT_BLOCK_BYTES = 28 * 1024 * 1024
_VMEM_SLACK_BYTES = 8 * 1024 * 1024


def row_tile(rows, groups, dtype):
    """The row tile for ``rows`` rows over ``groups`` groups: twice the
    rows a group holds on average, rounded up to the dtype's sublane
    multiple (8 rows of 4 bytes, 16 of 2), at most 256. Routed rows
    scatter about their mean, and a group that overflows its tile costs
    a second pass of its weights through the MXU: twice the mean holds
    nearly every group in one tile, and read 0.5-10 % faster on v5e than
    the mean itself at all eight shapes timed, four times the mean
    3-12 % slower (PERF.md, PR 31). Pad rows are at most
    ``groups x (tile - 1)``."""
    sub = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    twice = max(1, 2 * rows // max(groups, 1))
    return min(256, -(-twice // sub) * sub)


def col_tile(K, N, itemsize):
    """Columns of a weight block ``[K, tn]``: the whole N if the block
    fits :data:`_WEIGHT_BLOCK_BYTES`, else the widest divisor of N that
    is a multiple of 128 (the lane width) and fits. None where not even
    128 columns fit or N has no such divisor: the caller dispatches
    elsewhere."""
    if K * N * itemsize <= _WEIGHT_BLOCK_BYTES or N <= 128:
        return N
    fits = [t for t in range(128, N, 128)
            if N % t == 0 and K * t * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits) if fits else None


# jitted: the gate and up matmuls of a layer (the same shapes) then share one
# trace and one Mosaic lowering, and programs with the same rows one trace
@functools.partial(jax.jit, static_argnames=("tm", "interpret", "tn"))
def _gmm_raw(x, w, tile_experts, meta, tm, interpret=False, tn=None):
    """x [Mp, K] (rows tile-aligned by group), w [G, K, N],
    tile_experts [Mp/tm], meta int32 [2] = (the call's first group in
    ``w``, its row tiles: those past them are skipped) → y [Mp, N]
    (x.dtype). ``tn`` overrides :func:`col_tile` (the census's sweep)."""
    Mp, K = x.shape
    _, _, N = w.shape
    tn = tn or col_tile(K, N, w.dtype.itemsize)
    if tn is None or N % tn:
        raise ValueError(f"gmm: no column tile for a [{K}, {N}] expert matrix")
    grid = (N // tn, Mp // tm)  # row sweep innermost: w block stays in VMEM
    # two buffers each of the weight block, the row tile and the output tile, the f32 product
    vmem = (2 * K * tn * w.dtype.itemsize + 2 * tm * (K + tn) * x.dtype.itemsize
            + tm * tn * 4 + _VMEM_SLACK_BYTES)
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                # a skipped tile asks for the last real tile's rows again: no DMA
                pl.BlockSpec((tm, K), lambda j, i, te, meta: (
                    jnp.minimum(i, jnp.maximum(meta[1] - 1, 0)), 0)),
                pl.BlockSpec((1, K, tn), lambda j, i, te, meta: (meta[0] + te[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, te, meta: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=vmem),
        interpret=interpret,
        # the trace names the custom call after this: the benchmark's per-layer
        # metrics find the expert matmuls by "ragged_dot" and the Mosaic calls by "^gmm"
        name="gmm_ragged_dot",
    )(tile_experts, meta, x, w)


def _gmm_dw_raw(x, dy, tile_experts, num_experts, tk, tn, interpret=False):
    """dw [E, K, N] fp32 = Σ_{rows of e} x_rowᵀ dy_row (groups tile-aligned;
    pad rows are zero in BOTH x and dy so they contribute nothing)."""
    Mp, K = x.shape
    _, N = dy.shape
    tm = Mp // tile_experts.shape[0]
    tk = _fit_tile(tk, K)
    tn = _fit_tile(tn, N)
    grid = (K // tk, N // tn, Mp // tm)  # row sweep innermost: revisited
    # output block accumulates in VMEM, written back on group change
    out = pl.pallas_call(
        _gmm_dw_kernel,
        name="gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda kt, j, i, te: (i, kt)),
                pl.BlockSpec((tm, tn), lambda kt, j, i, te: (i, j)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn), lambda kt, j, i, te: (te[i], kt, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((num_experts, K, N), jnp.float32),
        interpret=interpret,
    )(tile_experts, x, dy)
    # experts that own zero row tiles never get their block written —
    # mask them to zero (uninitialized output memory otherwise)
    present = jax.ops.segment_sum(jnp.ones_like(tile_experts), tile_experts,
                                  num_segments=num_experts) > 0
    return jnp.where(present[:, None, None], out, 0.0)


def gmm_dw_onto(acc, fresh, x, dy, tile_experts, num_tiles, tk, tn, interpret=False):
    """:func:`_gmm_dw_raw` written **onto** ``acc`` [E, K, N] float32, which
    the result takes the place of (aliased) → (that, ``named`` [E] bool: the
    experts whose blocks it wrote). ``fresh`` [E] bool: the experts whose
    block of ``acc`` holds nothing yet - theirs becomes ``dw`` alone and is
    not read (it may hold anything: ``jax.lax.empty``); another's becomes
    ``acc + dw``, summed in float32 in the kernel's own block. An expert that
    is not ``named`` keeps what ``acc`` held. ``num_tiles`` (traced): the row
    tiles the groups fill (:func:`tile_layout`); the layout's tiles past them
    - rows of zeros - are neither fetched nor multiplied (the first always
    is: a layout that holds nothing writes its one named expert zeros)."""
    Mp, K = x.shape
    _, N = dy.shape
    n_tiles = tile_experts.shape[0]
    tm = Mp // n_tiles
    tk = _fit_tile(tk, K)
    tn = _fit_tile(tn, N)
    tiles = jnp.maximum(jnp.asarray(num_tiles, jnp.int32), 1).reshape(1)

    def rows(i, tiles):         # a skipped tile asks for the last real tile's rows again: no DMA
        return jnp.minimum(i, tiles[0] - 1)

    def block(kt, j, i, te, fresh, tiles):
        return te[rows(i, tiles)], kt, j

    def acc_block(kt, j, i, te, fresh, tiles):
        # a fresh expert asks for block 0, which nobody reads: where every expert is fresh
        # (a first pass) it is fetched once
        e, kt, j = block(kt, j, i, te, fresh, tiles)
        return tuple(jnp.where(fresh[e] != 0, 0, b) for b in (e, kt, j))

    out = pl.pallas_call(
        _gmm_dw_onto_kernel,
        name="gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(K // tk, N // tn, n_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda kt, j, i, te, fresh, tiles: (rows(i, tiles), kt)),
                pl.BlockSpec((tm, tn), lambda kt, j, i, te, fresh, tiles: (rows(i, tiles), j)),
                pl.BlockSpec((1, tk, tn), acc_block),
            ],
            out_specs=pl.BlockSpec((1, tk, tn), block),
        ),
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={5: 0},
        # two buffers each of the output's block and the accumulator's, and of the row tiles
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=(
            4 * tk * tn * 4 + 2 * tm * (tk + tn) * x.dtype.itemsize + _VMEM_SLACK_BYTES)),
        interpret=interpret,
    )(tile_experts, fresh.astype(jnp.int32), tiles, x, dy, acc)
    real = jnp.arange(n_tiles, dtype=jnp.int32) < tiles[0]
    named = jnp.any(real[:, None] & (tile_experts[:, None] == jnp.arange(
        acc.shape[0], dtype=tile_experts.dtype)[None, :]), axis=0)
    return out, named


def dw_tiles(K, N):
    """(tk, tn) of ``gmm_dw``'s output block for a ``[K, N]`` expert matrix:
    one full [K, N] fp32 accumulator block per expert when it fits the 4MB
    VMEM budget (next to the double-buffered input streams) — x and dy then
    stream exactly once; otherwise halve the block until it fits, re-reading
    x per n-tile and dy per k-tile."""
    tk, tn = K, N
    while tk * tn * 4 > 4 * 1024 * 1024:  # fit VMEM next to the streams
        if tn >= tk and tn % 256 == 0:
            tn //= 2
        elif tk % 256 == 0:
            tk //= 2
        else:
            return 256, 512
    return tk, tn


def gmm(x, w, tile_experts, tm=256, interpret=False, first_group=None, num_tiles=None):
    """Grouped matmul on a tile-aligned row layout.

    ``x`` [Mp, K] with rows grouped by expert and each group padded
    (with zero rows) to a multiple of ``tm``; ``w`` [G, K, N];
    ``tile_experts`` [Mp/tm] int32 — owning expert of each row tile.
    → [Mp, N] in ``x.dtype``: f32 accumulation, one rounding.
    Differentiable in x and w.

    ``first_group`` (a traced scalar; None = 0): ``w`` is a table of
    groups of which ``tile_experts`` counts from there — every layer's
    experts, read where they lie. ``num_tiles`` (traced; None = all):
    the row tiles the groups fill; the layout's tiles past them are
    skipped and give zero rows. Use :func:`pad_groups_to_tiles` or
    :func:`tile_layout` to build the layout, :func:`row_tile` for ``tm``.
    """
    meta = jnp.stack([jnp.asarray(0 if first_group is None else first_group, jnp.int32),
                      jnp.asarray(tile_experts.shape[0] if num_tiles is None else num_tiles,
                                  jnp.int32)])
    return _gmm(x, w, tile_experts, meta, tm, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm(x, w, tile_experts, meta, tm, interpret):
    return _gmm_raw(x, w, tile_experts, meta, tm, interpret)


def _gmm_fwd(x, w, tile_experts, meta, tm, interpret):
    return _gmm_raw(x, w, tile_experts, meta, tm, interpret), (x, w, tile_experts, meta)


def _gmm_bwd(tm, interpret, res, dy):
    x, w, tile_experts, meta = res
    dy = dy.astype(x.dtype)
    # dx: the same grouped matmul against the transposed expert weights
    dx = _gmm_raw(dy, w.swapaxes(1, 2), tile_experts, meta, tm, interpret)
    # over the whole table: a group no tile names gets zeros
    dw = _gmm_dw_raw(x, dy, tile_experts + meta[0], w.shape[0], *dw_tiles(*w.shape[1:]),
                     interpret).astype(w.dtype)
    return dx, dw, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# quantized-carrier variant: dequantize each expert slab in VMEM, in the
# K-loop (the grouped analogue of ops/pallas/fused_quant_matmul.py)
# ---------------------------------------------------------------------------

def _fit_group_tile(t, dim, group):
    """Largest multiple of ``group`` ≤ max(t, group) that divides
    ``dim`` — quantized column tiles must cover whole scale groups so
    the scale BlockSpec stays aligned with the carrier BlockSpec."""
    ng = dim // group
    best = group
    for c in range(1, ng + 1):
        if ng % c == 0 and c * group <= max(t, group):
            best = c * group
    return best


def _gmm_quant_kernel(te_ref, x_ref, v_ref, s_ref, o_ref, acc_ref, *,
                      scheme, group, n_k, dequant_dtype):
    """One (row tile i, col tile j, K step) cell: the owning expert's
    quantized weight tile streams in (``te_ref`` steered both the
    carrier and the scale DMA), is decoded + scaled in registers, and
    accumulates into the fp32 VMEM scratch — the full-precision expert
    matrix never exists beyond one [tk, tn] tile."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = v_ref[0]
    if scheme == "fp6":
        from deepspeed_tpu.ops.fp_quantizer.quantize import _decode_e3m2
        from deepspeed_tpu.ops.pallas.fused_quant_matmul import _unpack_fp6_tile
        w = _decode_e3m2(_unpack_fp6_tile(v))
    else:
        w = v.astype(jnp.float32)
    tk, tn = w.shape
    s = s_ref[0]
    w = (w.reshape(tk, tn // group, group) * s[:, :, None]).reshape(tk, tn)
    ct = jnp.result_type(x_ref.dtype, dequant_dtype)
    acc_ref[...] += jnp.dot(x_ref[...].astype(ct),
                            w.astype(dequant_dtype).astype(ct),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_quant_raw(x, values, scales, tile_experts, scheme, dequant_dtype,
                   tm, tn, tk, interpret=False):
    """x [Mp, K] (rows tile-aligned by group), grouped-layout carriers
    ``values`` [E, K, N] (fp6: [E, K, N*3//4] packed uint8) and
    ``scales`` [E, K, ng] → y [Mp, N] (x.dtype). K-innermost grid with
    an fp32 VMEM accumulator per (row, col) tile."""
    Mp, K = x.shape
    ng = scales.shape[-1]
    N = values.shape[-1] * 4 // 3 if scheme == "fp6" else values.shape[-1]
    g = N // ng
    tn = _fit_group_tile(tn, N, g)
    tk = _fit_tile(tk, K)
    vtn = tn * 3 // 4 if scheme == "fp6" else tn
    n_k = K // tk
    grid = (Mp // tm, N // tn, n_k)
    return pl.pallas_call(
        functools.partial(_gmm_quant_kernel, scheme=scheme, group=g, n_k=n_k,
                          dequant_dtype=dequant_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, k, te: (i, k)),
                pl.BlockSpec((1, tk, vtn), lambda i, j, k, te: (te[i], k, j)),
                pl.BlockSpec((1, tk, tn // g), lambda i, j, k, te: (te[i], k, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, k, te: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        interpret=interpret,
    )(tile_experts, x, values, scales)


def _gmm_quant_dx_kernel(te_ref, dy_ref, v_ref, s_ref, o_ref, acc_ref, *,
                         scheme, group, n_n, dequant_dtype):
    """Backward-input cell: decode the same carrier tile and contract on
    its N axis (``dy_tile @ w_tileᵀ``) into a [tm, tk] accumulator — the
    backward pass stays carrier-resident too (no transient dequantized
    stack even for training)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = v_ref[0]
    if scheme == "fp6":
        from deepspeed_tpu.ops.fp_quantizer.quantize import _decode_e3m2
        from deepspeed_tpu.ops.pallas.fused_quant_matmul import _unpack_fp6_tile
        w = _decode_e3m2(_unpack_fp6_tile(v))
    else:
        w = v.astype(jnp.float32)
    tk, tn = w.shape
    s = s_ref[0]
    w = (w.reshape(tk, tn // group, group) * s[:, :, None]).reshape(tk, tn)
    ct = jnp.result_type(dy_ref.dtype, dequant_dtype)
    acc_ref[...] += jax.lax.dot_general(
        dy_ref[...].astype(ct), w.astype(dequant_dtype).astype(ct),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_n - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_quant_dx_raw(dy, values, scales, tile_experts, scheme, dequant_dtype,
                      tm, tn, tk, interpret=False):
    """dx [Mp, K] = dy [Mp, N] @ dequant(w)ᵀ, carriers streamed per
    (row tile, K tile, N step) with the N sweep innermost."""
    Mp, N = dy.shape
    K = values.shape[-2]
    ng = scales.shape[-1]
    g = N // ng
    tn = _fit_group_tile(tn, N, g)
    tk = _fit_tile(tk, K)
    vtn = tn * 3 // 4 if scheme == "fp6" else tn
    n_n = N // tn
    grid = (Mp // tm, K // tk, n_n)
    return pl.pallas_call(
        functools.partial(_gmm_quant_dx_kernel, scheme=scheme, group=g,
                          n_n=n_n, dequant_dtype=dequant_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, tn), lambda i, j, n, te: (i, n)),
                pl.BlockSpec((1, tk, vtn), lambda i, j, n, te: (te[i], j, n)),
                pl.BlockSpec((1, tk, tn // g), lambda i, j, n, te: (te[i], j, n)),
            ],
            out_specs=pl.BlockSpec((tm, tk), lambda i, j, n, te: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, K), dy.dtype),
        interpret=interpret,
    )(tile_experts, dy, values, scales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def gmm_quant(x, values, scales, tile_experts, scheme,
              dequant_dtype=jnp.bfloat16, tm=256, tn=512, tk=256,
              interpret=False):
    """Grouped matmul over quantized expert carriers (fused dequant).

    Same tile-aligned row layout as :func:`gmm`; the [E, K, N] expert
    stack is consumed as grouped-layout carriers (``values`` int8/fp8,
    or packed fp6 uint8 [E, K, N*3//4]; ``scales`` fp32 [E, K, ng]) and
    each expert slab is dequantized one [tk, tn] tile at a time inside
    the K-loop — the full-precision expert stack never materializes in
    HBM, forward or backward. Differentiable in x only (frozen
    quantized base, the ``OptimizedLinear`` training contract):
    integer carriers get float0 cotangents.
    """
    return _gmm_quant_raw(x, values, scales, tile_experts, scheme,
                          dequant_dtype, tm, tn, tk, interpret)


def _gmm_quant_fwd(x, values, scales, tile_experts, scheme, dequant_dtype,
                   tm, tn, tk, interpret):
    y = _gmm_quant_raw(x, values, scales, tile_experts, scheme, dequant_dtype,
                       tm, tn, tk, interpret)
    # residuals must be JAX types: carry x's dtype as a 0-size array
    return y, (values, scales, tile_experts, jnp.zeros((0,), x.dtype))


def _gmm_quant_bwd(scheme, dequant_dtype, tm, tn, tk, interpret, res, dy):
    values, scales, tile_experts, x_proto = res
    from deepspeed_tpu.ops.pallas.fused_quant_matmul import \
        _zero_carrier_cotangent
    dx = _gmm_quant_dx_raw(dy.astype(x_proto.dtype), values, scales,
                           tile_experts, scheme, dequant_dtype, tm, tn, tk,
                           interpret)
    return (dx, _zero_carrier_cotangent(values), jnp.zeros_like(scales), None)


gmm_quant.defvjp(_gmm_quant_fwd, _gmm_quant_bwd)


def gmm_quant_supported(values, scales, scheme):
    """Static legality check for :func:`gmm_quant` carriers — callers
    dispatch to the ragged/jnp fallback when False."""
    if values.ndim != 3 or scales.ndim != 3:
        return False
    ng = scales.shape[-1]
    N = values.shape[-1] * 4 // 3 if scheme == "fp6" else values.shape[-1]
    if ng == 0 or N % ng:
        return False
    g = N // ng
    if scheme == "fp6" and (g % 4 or values.shape[-1] * 4 != N * 3):
        return False
    try:
        _fit_tile(256, values.shape[-2])
    except ValueError:
        return False
    return True


def tile_layout(sizes, num_rows, tm):
    """Shared tile-aligned layout math for :func:`gmm` callers.

    ``sizes`` [E] (true per-group row counts, Σ = ``num_rows``) →
    ``(padded_starts [E], tile_experts [Mp/tm], Mp, num_tiles)``: each
    group's first padded row, the owning expert per row tile, the static
    padded row count (every group padded up to a tile multiple, worst
    case ``num_rows + E*tm``) and the traced count of tiles the groups
    fill. The tiles past them name the last filled tile's expert — their
    rows are zero by construction, so they contribute nothing, and a
    kernel that is given ``num_tiles`` skips them without a weight DMA.
    Comparisons and sums only (no gather, no ``repeat``): every serving
    program traces and lowers this once a layer body."""
    E = sizes.shape[0]
    Mp = ((num_rows + tm - 1) // tm) * tm + E * tm
    tiles = ((sizes + tm - 1) // tm).astype(jnp.int32)
    ends = jnp.cumsum(tiles)
    padded_starts = (ends - tiles) * tm
    num_tiles = ends[-1]
    tile = jnp.minimum(jnp.arange(Mp // tm, dtype=jnp.int32), jnp.maximum(num_tiles - 1, 0))
    # a tile's expert: as many groups end at or before it
    tile_experts = jnp.minimum(jnp.sum(ends[None, :] <= tile[:, None], axis=1), E - 1)
    return padded_starts, tile_experts.astype(jnp.int32), Mp, num_tiles


def pad_groups_to_tiles(sizes, num_rows, tm):
    """Layout metadata for group-SORTED rows: ``(dst, tile_experts, Mp)``
    where ``dst`` [num_rows] maps the j-th sorted row to its padded
    position. (The training dispatch in ``ops/grouped_gemm.py`` computes
    per-row slots rank-based without sorting; both share
    :func:`tile_layout`.)"""
    padded_starts, tile_experts, Mp, _ = tile_layout(sizes, num_rows, tm)
    starts = jnp.cumsum(sizes) - sizes
    row = jnp.arange(num_rows, dtype=jnp.int32)
    expert_of_row = jnp.searchsorted(jnp.cumsum(sizes), row, side="right").astype(jnp.int32)
    dst = (padded_starts[expert_of_row] + (row - starts[expert_of_row])).astype(jnp.int32)
    return dst, tile_experts, Mp
