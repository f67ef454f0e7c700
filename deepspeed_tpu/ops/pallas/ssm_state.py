"""The Mamba-2 state step: one visit of each sequence's slot of the pool.

A step of the ragged engine names some sequence rows; each owns a slot of
the float32 pool ``[Lm, NS, H, P, N]`` (``model_runner.NemotronHKind``:
``H`` heads of a ``P x N`` matrix, ``G`` groups of ``H / G`` heads that
share their ``B`` and ``C`` rows). For every sequence's first row of the
step - its only row, in a decode step - the carried state is read, used
and rewritten:

    seen[s, h, p] = sum_n S[h, p, n] * c[s, g(h), n]
    S[h, p, n]   <- S[h, p, n] * decay[s, h] + left[s, h, p] * b[s, g(h), n]

with ``S = pool[layer, slot[s]]``, taken as zero where ``fresh[s]`` (the
sequence starts here: what its slot held is a former owner's). A sequence
row with no token in the step (``here[s]`` false; the engine points all of
them at padding's slot 0) reads and writes nothing, its ``seen`` is zero,
and **a slot no live row names keeps what it held**, bit for bit. Live
rows name distinct slots.

:func:`ssm_state_step` is the Pallas kernel. The pool is **aliased in and
out** and stays in HBM; the layer (traced inside the layer scan), the live
rows, their slots, whether they are fresh and the decays ride in SMEM
(scalar prefetch). The grid runs over the **live** sequence rows alone, a
dynamic bound as the paged kernels' ``live_rows`` is, so what a call moves
is ``live rows x 2 x H x P x N x 4`` bytes and not the layer's. A grid
step takes one slot a tile at a time: a tile is ``[Ht, P, N]``, ``Ht`` heads
**inside one group** (:func:`tile_heads`: a whole group's ``H / G`` where
four such tiles fit ``TILE_VMEM_BYTES`` - 512 KB at 128 x 64 x 128 in 8
groups - and otherwise the largest divisor of ``H / G`` whose tile is no
larger than that 512 KB, the size the pipeline was fitted at: 16 heads of
the 128 of a model with **one group**, whose ``b`` / ``c`` row every tile
then shares; tile ``t`` reads group ``t * Ht // (H / G)``'s). It is copied
into one of two VMEM buffers while the tile before it is worked on - the
next sequence's first tile is started from the last tile of this one, as
the paged kernels start the next token's - and the result leaves from one
of two others, so the reads, the arithmetic and the writes of neighbouring
tiles overlap. A fresh sequence's tiles are not fetched.

**Arithmetic.** The state is float32 and stays so: the update multiplies
and adds float32 on the VPU. What the vector unit does badly is the two
things the layout forces - a sum over the 128 lanes of ``N`` for every one
of a tile's 1024 ``(h, p)`` rows, and ``left`` laid along the sublanes to
be multiplied into ``b``'s lanes - so both go through the MXU, written as
sums of bfloat16 products accumulated in float32 (a bfloat16 x bfloat16
product is exact there). ``left (x) b``: both split in three pieces, all
nine products in one pass of depth 16, which is the float32 product to its
last place or two. ``S c``: ``c`` in three pieces, the tile in
``READ_PIECES`` (2: what is read is the state to 16 bits of mantissa, an
error of 2**-17 a term, 64 times under bfloat16's; the state itself is
never rounded). ``unit="vpu"`` keeps the plain float32 product and lane
sum, and ``unit="none"`` neither (the copies and the decay alone: what
the pipeline moves when nothing else is in its way), for
``tools/kernel_census.py --ssm`` to time beside it.

:func:`xla_ssm_state_step` is the same mathematics as XLA sees it: the
pool's layer read where it lies and written back whole - three passes over
all ``NS`` slots - the reference the tests compare against and the path
where the kernel does not run (:func:`state_step_impl`: not a TPU, a mesh,
or a shape :func:`kernel_supported` refuses).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "pallas_ssm_state"
XLA = "xla"
# the four tile buffers (two in, two out) may take this much VMEM
TILE_VMEM_BYTES = 8 << 20
# scalar prefetch holds the [S, H] decays beside the rows' indices
SMEM_BYTES = 512 << 10
READ_PIECES = 2
# a tile that is not a whole group is at most this large: the tile the pipeline was fitted at
FITTED_TILE_BYTES = 512 << 10


def xla_ssm_state_step(pool, layer, slot, fresh, here, c, b, decay, left):
    """Reference math, by slot. pool [Lm, NS, H, P, N] float32; ``layer``
    int32 scalar; per sequence row of the step (``S`` of them): ``slot``
    int32, ``fresh`` / ``here`` bool, ``c`` / ``b`` [S, G, N], ``decay``
    [S, H], ``left`` [S, H, P], float32. → (pool, seen [S, H, P]); the
    module docstring has the equations. What is a sequence row's is laid
    out a slot first and the layer updated whole, every slot no live row
    names decayed by 1 with nothing added."""
    NS, H, P, N = pool.shape[1:]
    G = c.shape[1]
    per = H // G
    at = jnp.where(here, slot, NS)               # a row with no token names no slot

    def by_slot(of_row, fill):
        return jnp.full((NS,) + of_row.shape[1:], fill, of_row.dtype).at[at].set(
            of_row, mode="drop")

    carried = jnp.where(by_slot(fresh, False)[:, None, None, None], 0.0, pool[layer])
    carried = carried.reshape(NS, G, per, P, N)
    # a product and a sum over N, in float32 as the state is: a dot would have the
    # states copied in its operand's type first
    seen = jnp.sum(carried * by_slot(c, 0.0)[:, :, None, None, :], axis=-1)
    seen = jnp.where(here[:, None, None], seen.reshape(NS, H, P)[slot], 0.0)
    state = (carried * by_slot(decay, 1.0).reshape(NS, G, per, 1, 1)
             + by_slot(left, 0.0).reshape(NS, G, per, P, 1) * by_slot(b, 0.0)[:, :, None, None, :])
    return pool.at[layer].set(state.reshape(NS, H, P, N)), seen


def tile_heads(pool_shape, n_groups):
    """→ ``Ht``, the heads of one tile ``[Ht, P, N]``: a whole group's where
    four such tiles fit ``TILE_VMEM_BYTES`` (every shape the kernel took
    before it had tiles inside a group: their programs are the ones they
    were), else the largest divisor of ``H / G`` whose tile is within
    ``FITTED_TILE_BYTES``; 0 where even one head's is not."""
    _, _, H, P, N = pool_shape
    per, head = H // n_groups, P * N * 4
    if 4 * per * head <= TILE_VMEM_BYTES:
        return per
    return max((d for d in range(1, per + 1) if per % d == 0 and d * head <= FITTED_TILE_BYTES),
               default=0)


def kernel_supported(pool_shape, n_groups, n_rows):
    """Can Mosaic tile it? A tile is ``[Ht, P, N]`` float32 rows of the
    pool (:func:`tile_heads`): ``N`` whole 128-lane vregs, ``P`` whole
    8-sublane tiles (the tile is worked on as ``[Ht * P, N]``), four of it
    within ``TILE_VMEM_BYTES``, and the ``n_rows`` sequence rows' decays
    within the SMEM budget."""
    _, _, H, P, N = pool_shape
    if n_groups < 1 or H % n_groups or N % 128 or P % 8:
        return False
    return tile_heads(pool_shape, n_groups) > 0 and n_rows * (H + 3) * 4 + 4 <= SMEM_BYTES


def state_step_impl(pool_shape, n_groups, n_rows):
    """→ ``KERNEL`` or ``XLA``: which of the two a program traced here gets
    for these shapes. The kernel where kernels run at all
    (``ops.pallas.use_pallas``: a TPU and no mesh) and Mosaic can tile the
    shapes; interpreted (``DS_PALLAS=1`` off the chip, how the CPU tests
    reach it) any shape runs."""
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    if not use_pallas():
        return XLA
    if default_interpret() or kernel_supported(pool_shape, n_groups, n_rows):
        return KERNEL
    return XLA


def _pieces(x, n):
    """float32 ``x`` → ``n`` float32 arrays of bfloat16 values that sum to
    it (to ``8 n`` bits of mantissa)."""
    out = []
    for _ in range(n):
        top = x.astype(jnp.bfloat16).astype(jnp.float32)
        out.append(top)
        x = x - top
    return out


def _rows_of(pieces, order):
    """[16, W] bfloat16 whose row ``k`` is ``pieces[order[k]]`` ([1, W]
    each) and zero past ``order``: the operand of a depth-16 product."""
    shape = (16, pieces[0].shape[1])
    k = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    rows = jnp.zeros(shape, jnp.float32)
    for at, i in enumerate(order):
        rows = jnp.where(k == at, pieces[i], rows)
    return rows.astype(jnp.bfloat16)


def _kernel(meta_ref, row_ref, slot_ref, fresh_ref, decay_ref,
            c_ref, b_ref, left_ref, pool_ref, out_ref, seen_ref,
            in_buf, out_buf, sems, *, G, per, group_tiles, P, N, unit):
    """One live sequence row: its slot's ``G`` tiles of ``per`` heads in
    turn (the caller's ``tiles`` and ``Ht``: a group is ``group_tiles`` of
    them). c/b blocks [1, groups, N], left/seen [1, G, per * P] of the row (VMEM); pool/out: the
    whole pool, one buffer under two names (HBM); the rest in SMEM, meta
    the layer and the number of live rows (the grid's bound, but 1 where
    there is none: an empty grid is not asked of Mosaic, and that step
    does nothing)."""
    i, n = pl.program_id(0), meta_ref[1]
    layer, row, fresh = meta_ref[0], row_ref[i], fresh_ref[i] != 0
    f32 = jnp.float32
    R = per * P

    def tile_of(ref, ii, g):
        return ref.at[layer, slot_ref[ii], pl.ds(pl.multiple_of(g * per, per), per)]

    def fetch(ii, g, buf):
        return pltpu.make_async_copy(tile_of(pool_ref, ii, g), in_buf.at[buf], sems.at[0, buf])

    def store(ii, g, buf):
        return pltpu.make_async_copy(out_buf.at[buf], tile_of(out_ref, ii, g), sems.at[1, buf])

    @pl.when(n > 0)
    def _():
        @pl.when((i == 0) & jnp.logical_not(fresh))
        def _():
            fetch(0, 0, 0).start()

        def tile_step(g, carry):
            t = i * G + g                            # tiles in order; buffers take turns
            buf = t & 1
            last = g + 1 == G
            ahead = jnp.where(last, jnp.minimum(i + 1, n - 1), i)

            # the next tile in order - this slot's, or the next sequence's first - flies
            # during this tile's arithmetic
            @pl.when((jnp.logical_not(last) | (i + 1 < n)) & (fresh_ref[ahead] == 0))
            def _():
                fetch(ahead, jnp.where(last, 0, g + 1), 1 - buf).start()

            @pl.when(jnp.logical_not(fresh))
            def _():
                fetch(i, g, buf).wait()

            # what left this buffer two tiles ago must be gone before it is filled again
            @pl.when(t >= 2)
            def _():
                store(i, g, buf).wait()

            # a fresh sequence's buffer holds whatever it held: selected away, NaN or not
            tile = jnp.where(fresh, 0.0, in_buf[buf]).reshape(R, N)
            group = g if group_tiles == 1 else g // group_tiles
            c = c_ref[0, pl.ds(group, 1), :]         # [1, N]
            b = b_ref[0, pl.ds(group, 1), :]
            left = left_ref[0, pl.ds(g, 1), :]       # [1, R]
            if unit == "mxu":
                cm = _rows_of(_pieces(c, 3), (0, 1, 2))
                seen = jnp.zeros((16, R), f32)
                for piece in _pieces(tile, READ_PIECES):
                    seen = seen + jax.lax.dot_general(
                        cm, piece.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)
                seen_ref[0, pl.ds(g, 1), :] = jnp.sum(seen, axis=0, keepdims=True)
                # left (x) b: piece i of left against piece j of b, all nine at once
                lk = _rows_of(_pieces(left, 3), [k // 3 for k in range(9)])
                bk = _rows_of(_pieces(b, 3), [k % 3 for k in range(9)])
                added = jax.lax.dot_general(lk, bk, (((0,), (0,)), ((), ())),
                                            preferred_element_type=f32)       # [R, N]
            elif unit == "vpu":
                seen_ref[0, pl.ds(g, 1), :] = jnp.sum(tile * c, axis=-1).reshape(1, R)
                added = left.reshape(R, 1) * b
            else:                                    # "none": the copies and the decay alone
                seen_ref[0, pl.ds(g, 1), :] = jnp.zeros((1, R), f32)
                added = jnp.zeros((R, N), f32)
            for a in range(per):
                rows = slice(a * P, (a + 1) * P)
                out_buf[buf, a] = tile[rows] * decay_ref[row, g * per + a] + added[rows]
            store(i, g, buf).start()
            return carry

        jax.lax.fori_loop(0, G, tile_step, 0)

        # the last two tiles' results are still on their way
        @pl.when(i + 1 == n)
        def _():
            for back in (1, 2):
                @pl.when(n * G >= back)
                def _():
                    store(i, 0, (n * G - back) & 1).wait()


@functools.partial(jax.jit, static_argnames=("unit", "interpret"))
def _state_call(pool, layer, slot, fresh, here, c, b, decay, left, unit, interpret):
    """The kernel over the live rows (jitted so that a cell's programs
    share one trace of it)."""
    H, P, N = pool.shape[2:]
    S, groups = c.shape[:2]
    # ``per`` heads a tile, ``G`` tiles a slot, ``group_tiles`` of them a group (1: a tile is
    # a group, as it was before tiles lay inside one)
    per = tile_heads(pool.shape, groups) or H // groups     # (interpreted: any shape runs)
    G, group_tiles = H // per, H // groups // per
    f32 = jnp.float32
    order = jnp.argsort(jnp.logical_not(here), stable=True).astype(jnp.int32)   # live rows first
    n_live = jnp.sum(here.astype(jnp.int32))

    def row_block(width, rows=G):
        return pl.BlockSpec((1, rows, width), lambda i, meta, row, *_: (row[i], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # layer and live rows; the rows, their slots, fresh; decays
        grid=(jnp.maximum(n_live, 1),),
        in_specs=[row_block(N, groups), row_block(N, groups), row_block(per * P),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), row_block(per * P)],
        scratch_shapes=[pltpu.VMEM((2, per, P, N), f32), pltpu.VMEM((2, per, P, N), f32),
                        pltpu.SemaphoreType.DMA((2, 2))],       # [in | out, buffer]
    )
    new, seen = pl.pallas_call(
        functools.partial(_kernel, G=G, per=per, group_tiles=group_tiles, P=P, N=N, unit=unit),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((S, G, per * P), f32)],
        input_output_aliases={8: 0},            # the pool, after the five scalars and c, b, left
        # rows in order on one core: a row starts the next one's first tile
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=32 << 20),
        interpret=interpret,
        name="ssm_state_step",
    )(jnp.stack([jnp.asarray(layer, jnp.int32), n_live]), order, slot[order].astype(jnp.int32),
      fresh[order].astype(jnp.int32), decay.astype(f32), c.astype(f32), b.astype(f32),
      left.astype(f32).reshape(S, G, per * P), pool)
    # a row the grid did not reach has whatever its block of ``seen`` held
    return new, jnp.where(here[:, None, None], seen.reshape(S, H, P), 0.0)


def ssm_state_step(pool, layer, slot, fresh, here, c, b, decay, left, unit="mxu",
                   interpret=None):
    """Pallas path of :func:`xla_ssm_state_step` (same contract). ``unit``:
    what sums over ``N`` and lays ``left`` against ``b`` - ``"mxu"``, or
    ``"vpu"`` for the census. Raises where Mosaic cannot tile the shapes;
    interpreted, any shape runs."""
    if interpret is None:
        from deepspeed_tpu.ops.pallas import default_interpret
        interpret = default_interpret()
    if not interpret and not kernel_supported(pool.shape, c.shape[1], c.shape[0]):
        raise ValueError(
            f"the state step kernel needs N % 128 == 0, P % 8 == 0, a head's [P, N] within "
            f"{FITTED_TILE_BYTES >> 10} KB and the rows' decays in SMEM; got a "
            f"pool {pool.shape} in {c.shape[1]} groups under {c.shape[0]} sequence rows")
    return _state_call(pool, layer, slot, fresh, here, c, b, decay, left, unit, interpret)
