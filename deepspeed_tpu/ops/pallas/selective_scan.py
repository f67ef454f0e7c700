"""The Mamba-1 selective scan over a ragged step: every row of a sequence
through its slot's state, in order, one visit of the slot.

A step of the ragged engine names some sequence rows; each owns a slot of
the float32 pool ``[Lm, NS, N, C]`` (``model_runner.JambaKind``: ``C``
channels along a vector's lanes, ``N`` state columns a channel). A
sequence's rows of the step are one run of the flat batch, ``first_row ..
first_row + length - 1``, and each passes through the state::

    S[n, c] <- exp(delta[t, c] * a[n, c]) * S[n, c] + delta[t, c] * x[t, c] * b[t, n]
    y[t, c]  = sum_n S[n, c] * c[t, n]

with ``S = pool[layer, slot[s]]``, taken as zero before the sequence's
first row where ``fresh[s]`` (the sequence starts here: what its slot held
is a former owner's). The decay is **an element's own** - another for
every channel, state column and row - so, unlike Mamba-2's scalar a head
(``ssm_state.py``, a chunk through a decay mask), no mask over a chunk's
rows expresses it: the rows are scanned. A sequence row with no token in
the step (``length[s]`` 0; the engine points all of them at padding's slot
0) reads and writes nothing, **a slot no live row names keeps what it
held**, bit for bit, and a row of the batch that is no sequence's gives
``y`` zero. Live rows name distinct slots.

:func:`selective_scan` is the Pallas kernel. The pool is **aliased in and
out** and stays in HBM; the layer (traced inside the layer scan), each
row's sequence and the sequences' slots, first and last rows and ``fresh``
ride in SMEM (scalar prefetch). The grid runs over **blocks of ``ROWS``
rows** up to the last live one, a dynamic bound as the paged kernels'
``live_rows`` is, with the rows' ``x``, ``delta`` and ``y`` blocks piped by
Pallas; the state is the kernel's own to move: at a sequence's first row
its slot ``[N, C]`` (320 KB at 16 x 5120) is waited for - it was asked
for when the sequence before it began, so the fetch flies during that one's
arithmetic; a fresh sequence's is not fetched - and laid in one of two
VMEM buffers; the sequence's rows update it there, whichever blocks they
fall in; at its last row it leaves for the slot it came from, and is
waited for two sequences later, when its buffer is wanted again. So **each
live slot of a layer is read once and written once a call**, one row in a
decode step or a chunk's hundreds, several sequences' runs side by side in
one call; no ``[T, C, N]`` tensor exists anywhere. ``b`` and ``c`` arrive
laid ``[T, N, 128]`` - a row's column a sublane, the same value along the
lanes (XLA's broadcast: 8 KB a row beside the 60 KB of ``x``, ``delta`` and
``y``) - because a row's ``N`` values otherwise lie along lanes and a lane
cannot be read at a dynamic index. A row's arithmetic runs a ``PIECE`` of
512 channels at a time: 8 vregs of state, of decay, of ``b`` and ``c``. **What
is traced and lowered is one row's body**, the pieces side by side in it: a
block's rows are a ``fori_loop`` (a row read and written at a dynamic sublane:
4.5 % more for a prompt row, 0.5 % for a decode row), because every copy of
the body is traced and lowered again in each layer body of each step program,
which no compile cache saves. The pieces stay written out: a loop over them (a
dynamic lane offset, which Mosaic takes) leaves the scheduler one piece at a
time, and a prompt row 70 % slower (PERF.md, PR 45).

:func:`xla_selective_scan` is the same mathematics as XLA sees it - the
sequences' states gathered ``[S, N, C]``, one ``lax.scan`` over the rows,
the states scattered back - the reference the tests compare against and the
path where the kernel does not run (:func:`scan_impl`: not a TPU, a mesh,
or a shape :func:`kernel_supported` refuses).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "pallas_selective_scan"
XLA = "xla"
ROWS = 8            # rows of the batch a grid step: a float32 tile's sublanes
LANES = 128
PIECE = 512         # channels a row's arithmetic takes at a time: 8 vregs of state
SMEM_BYTES = 256 << 10


def xla_selective_scan(pool, layer, seq, slot, first_row, length, fresh, x, delta, b, c, a):
    """Reference math. pool [Lm, NS, N, C] float32; ``layer`` int32 scalar;
    ``seq`` [T] int32: each row's sequence row (of ``S``); per sequence row:
    ``slot`` int32, ``first_row`` int32 (its first row of the batch),
    ``length`` int32 (its rows in this step, 0: none), ``fresh`` bool; a row
    of the batch: ``x`` / ``delta`` [T, C], ``b`` / ``c`` [T, N]; ``a`` [N,
    C], all float32. → (pool, y [T, C]); the module docstring has the
    equations. A row belongs to its sequence where it lies in the
    sequence's run; every other row gives zero and moves nothing."""
    NS = pool.shape[1]
    T = x.shape[0]
    here = length > 0
    carried = jnp.where(fresh[:, None, None], 0.0, pool[layer, slot])        # [S, N, C]
    rows = jnp.arange(T, dtype=jnp.int32)
    mine = (rows >= first_row[seq]) & (rows < first_row[seq] + length[seq])

    def one(states, row):
        s, live, d_t, x_t, b_t, c_t = row
        old = states[s]
        new = jnp.exp(d_t[None, :] * a) * old + (d_t * x_t)[None, :] * b_t[:, None]
        y_t = jnp.sum(new * c_t[:, None], axis=0)
        return states.at[s].set(jnp.where(live, new, old)), jnp.where(live, y_t, 0.0)

    states, y = jax.lax.scan(one, carried, (seq, mine, delta, x, b, c))
    at = jnp.where(here, slot, NS)                  # a row with no token names no slot
    return pool.at[layer, at].set(states, mode="drop"), y


def kernel_supported(pool_shape, n_tokens, n_rows):
    """Can Mosaic tile it? The state ``[N, C]`` is whole float32 tiles (``N
    % 8``, ``C % 128``), the batch whole blocks of ``ROWS`` rows, and the
    rows' and sequences' scalars fit the SMEM budget."""
    _, _, N, C = pool_shape
    if N % 8 or C % LANES or n_tokens % ROWS:
        return False
    return (n_tokens + 4 * n_rows + 3) * 4 <= SMEM_BYTES


def scan_impl(pool_shape, n_tokens, n_rows):
    """→ ``KERNEL`` or ``XLA``: which of the two a program traced here gets
    for these shapes. The kernel where kernels run at all
    (``ops.pallas.use_pallas``: a TPU and no mesh) and Mosaic can tile the
    shapes; interpreted (``DS_PALLAS=1`` off the chip, how the CPU tests
    reach it) any shape runs."""
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    if not use_pallas():
        return XLA
    if default_interpret() or kernel_supported(pool_shape, n_tokens, n_rows):
        return KERNEL
    return XLA


def _kernel(meta_ref, row_ref, slot_ref, start_ref, end_ref, fresh_ref,
            x_ref, delta_ref, b_ref, c_ref, a_ref, pool_ref, out_ref, y_ref,
            in_buf, state, sems, *, piece):
    """One block of ``rows`` rows. x/delta/y blocks [rows, C], b/c blocks
    [rows, N, lanes] (VMEM, piped); a [N, C] whole; pool/out: the whole
    pool, one buffer under two names (HBM); the rest in SMEM: meta the layer,
    the live sequences ``n`` and one past the last live row; ``row_ref`` each
    row's sequence in row order (-1: none), and the sequences in that order:
    their slots, first and last rows, fresh."""
    i = pl.program_id(0)
    rows, C = x_ref.shape
    layer, n, n_rows = meta_ref[0], meta_ref[1], meta_ref[2]

    def fetch(k):
        return pltpu.make_async_copy(pool_ref.at[layer, slot_ref[k]], in_buf.at[k & 1],
                                     sems.at[0, k & 1])

    def store(k):
        return pltpu.make_async_copy(state.at[k & 1], out_ref.at[layer, slot_ref[k]],
                                     sems.at[1, k & 1])

    def row_step(j, carry):
        """Row ``j`` of the block: a loop's index, so that one row's body is
        traced and lowered and not a block's eight."""
        row = pl.ds(j, 1)
        r = i * rows + j
        k = row_ref[r]

        @pl.when((r < n_rows) & (k >= 0))
        def _():
            buf = k & 1

            @pl.when(r == start_ref[k])
            def _():
                fresh = fresh_ref[k] != 0

                @pl.when((k == 0) & jnp.logical_not(fresh))
                def _():
                    fetch(0).start()

                # the next sequence's slot flies during this one's rows
                nxt = jnp.minimum(k + 1, n - 1)

                @pl.when((k + 1 < n) & (fresh_ref[nxt] == 0))
                def _():
                    fetch(nxt).start()

                @pl.when(jnp.logical_not(fresh))
                def _():
                    fetch(k).wait()

                # what left this buffer two sequences ago must be gone before it is filled
                @pl.when(k >= 2)
                def _():
                    store(k - 2).wait()

                # a fresh sequence's buffer holds whatever it held: selected away, NaN or not
                state[buf] = jnp.where(fresh, 0.0, in_buf[buf])

            # a row's columns, laid a lane tile wide, side by side to the piece's width
            b = jnp.concatenate([b_ref[j]] * (piece // b_ref.shape[2]), axis=1)   # [N, piece]
            c = jnp.concatenate([c_ref[j]] * (piece // c_ref.shape[2]), axis=1)
            for q in range(C // piece):
                lanes = slice(q * piece, (q + 1) * piece)
                d = delta_ref[row, lanes]                       # [1, piece]
                s = jnp.exp(d * a_ref[:, lanes]) * state[buf, :, lanes] \
                    + (d * x_ref[row, lanes]) * b
                state[buf, :, lanes] = s
                y_ref[row, lanes] = jnp.sum(s * c, axis=0, keepdims=True)

            @pl.when(r == end_ref[k])
            def _():
                store(k).start()

        return carry

    jax.lax.fori_loop(0, rows, row_step, 0)

    # the last two sequences' states are still on their way
    @pl.when(i + 1 == pl.num_programs(0))
    def _():
        for back in (1, 2):
            @pl.when(n >= back)
            def _():
                store(n - back).wait()


def live_runs(seq, first_row, length, n_tokens):
    """What a kernel that runs a step's rows through their slots in row order
    prefetches of the step (this one and ``kda.kda_delta_rule``): → (``order``
    [S]: the sequence rows, the live ones first, in the order of their rows;
    the number of live ones; one past the last live row; ``mine`` [T]: whether
    a row lies in its sequence's run; ``row_seq`` [T]: a row's sequence's place
    in ``order``, -1 where it is no sequence's; ``start`` [S]: the first row of
    each of ``order``)."""
    i32 = jnp.int32
    S = first_row.shape[0]
    here = length > 0
    order = jnp.argsort(jnp.where(here, first_row, n_tokens), stable=True).astype(i32)
    rank = jnp.zeros((S,), i32).at[order].set(jnp.arange(S, dtype=i32))
    n_live = jnp.sum(here.astype(i32))
    n_rows = jnp.max(jnp.where(here, first_row + length, 0)).astype(i32)
    at = jnp.arange(n_tokens, dtype=i32)
    mine = (at >= first_row[seq]) & (at < first_row[seq] + length[seq])
    row_seq = jnp.where(mine, rank[seq], -1).astype(i32)
    return order, n_live, n_rows, mine, row_seq, first_row[order].astype(i32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(pool, layer, seq, slot, first_row, length, fresh, x, delta, b, c, a, interpret):
    """The kernel over the live rows (jitted so that a cell's programs
    share one trace of it)."""
    N, C = pool.shape[2:]
    T = x.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    rows = ROWS if T % ROWS == 0 else T
    lanes = LANES if C % LANES == 0 else C         # b and c are laid this wide
    piece = next((w for w in (PIECE, PIECE // 2) if C % w == 0), lanes)
    order, n_live, n_rows, mine, row_seq, start = live_runs(seq, first_row, length, T)

    def columns_of(v):  # [T, N] → [T, N, lanes]: a column a sublane, the same along the lanes
        return jnp.broadcast_to(v.astype(f32)[:, :, None], (T, N, lanes))

    def block(width):
        return pl.BlockSpec((rows, width), lambda i, *_: (i, 0))

    def columns():
        return pl.BlockSpec((rows, N, lanes), lambda i, *_: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,      # layer, sequences, rows; a row's sequence; the sequences'
        grid=(jnp.maximum((n_rows + rows - 1) // rows, 1),),
        in_specs=[block(C), block(C), columns(), columns(),
                  pl.BlockSpec((N, C), lambda i, *_: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), block(C)],
        scratch_shapes=[pltpu.VMEM((2, N, C), f32), pltpu.VMEM((2, N, C), f32),
                        pltpu.SemaphoreType.DMA((2, 2))],       # [in | out, buffer]
    )
    new, y = pl.pallas_call(
        functools.partial(_kernel, piece=piece),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((T, C), f32)],
        input_output_aliases={11: 0},           # the pool, after the six scalars and x .. a
        # rows in order on one core: a sequence starts the next one's fetch
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="selective_scan",
    )(jnp.stack([jnp.asarray(layer, i32), n_live, n_rows]), row_seq, slot[order].astype(i32),
      start, start + length[order].astype(i32) - 1, fresh[order].astype(i32),
      x.astype(f32), delta.astype(f32), columns_of(b), columns_of(c), a.astype(f32), pool)
    # a row the grid did not reach, or no sequence's, has whatever its block of ``y`` held
    return new, jnp.where(mine[:, None], y, 0.0)


def selective_scan(pool, layer, seq, slot, first_row, length, fresh, x, delta, b, c, a,
                   interpret=None):
    """Pallas path of :func:`xla_selective_scan` (same contract). Raises
    where Mosaic cannot tile the shapes; interpreted, any shape runs."""
    if interpret is None:
        from deepspeed_tpu.ops.pallas import default_interpret
        interpret = default_interpret()
    if not interpret and not kernel_supported(pool.shape, x.shape[0], slot.shape[0]):
        raise ValueError(
            f"the selective scan kernel needs N % 8 == 0, C % {LANES} == 0, whole blocks of "
            f"{ROWS} rows and the rows' scalars in SMEM; got a pool {pool.shape} under "
            f"{x.shape[0]} rows of {slot.shape[0]} sequence rows")
    return _scan_call(pool, layer, seq, slot, first_row, length, fresh, x, delta, b, c, a,
                      interpret)
