"""The Kimi-delta-attention recurrence over a ragged step: every row of a
sequence through its slot's state, in order, one visit of the slot.

A step of the ragged engine names some sequence rows; each owns a slot of
the float32 pool ``[Lk, NS, H, d, d]`` (``model_runner.SolarOpen2Kind``: a
``d x d`` matrix a head, **key rows, value columns** - the values lie along
a vector's lanes). A sequence's rows of the step are one run of the flat
batch, ``first_row .. first_row + length - 1``, and each passes through the
state, a head at a time::

    S' = Diag(exp(log_alpha[t])) S             (a key row's own decay, <= 1)
    S  <- S' + beta[t] * k[t] (v[t] - S'^T k[t])^T
    o[t] = S^T q[t]

with ``S = pool[layer, slot[s]]``, taken as zero before the sequence's
first row where ``fresh[s]`` (the sequence starts here: what its slot held
is a former owner's). The transition ``(I - beta k k^T) Diag(alpha)`` is
**not diagonal**: a row rotates the state toward its key before it writes,
so neither a decay mask over a chunk's rows (``ssm_state.py``) nor an
element-wise scan (``selective_scan.py``) expresses it. A sequence row with
no token in the step (``length[s]`` 0; the engine points all of them at
padding's slot 0) reads and writes nothing, **a slot no live row names
keeps what it held**, bit for bit, and a row of the batch that is no
sequence's gives ``o`` zero. Live rows name distinct slots.

:func:`kda_delta_rule` is the Pallas kernel: the recurrence itself, exact,
a row at a time - no chunked transform, nothing dropped, no exponential in
the kernel at all (``alpha = exp(log_alpha)`` is taken outside, of a number
that is never positive). The pool is **aliased in and out** and stays in
HBM; the layer (traced inside the layer scan), each row's sequence and the
sequences' slots, first and last rows and ``fresh`` ride in SMEM (scalar
prefetch), as ``selective_scan``'s do, whose pipeline this is: the grid
runs over **blocks of ``ROWS`` rows** up to the last live one, the rows'
``q, k, alpha, v, beta`` and ``o`` blocks ``[ROWS, H, d]`` piped by Pallas;
the state is the kernel's own to move: at a sequence's first row its slot
``[H, d, d]`` (4.19 MB at 64 x 128 x 128) is waited for - it was asked for
when the sequence before it began, so the fetch flies during that one's
arithmetic; a fresh sequence's is not fetched - into one of two VMEM
buffers; the first row reads it there and leaves the new state in one of
two others, which the sequence's later rows update in place, whichever
blocks they fall in; at its last row the state leaves for the slot it came
from, and is waited for two sequences later, when its buffer is wanted
again. So **each live slot of a layer is read once and written once a
call**, one row in a decode step or a chunk's hundreds, several sequences'
runs side by side in one call; no ``[T, H, d, d]`` tensor exists anywhere.

**Arithmetic**: float32 on the vector unit, a head's ``[d, d]`` tile (16
vregs) at a time. ``S'^T k`` and ``S^T q`` are sums over a tile's sublanes
(vreg adds, one shuffle), the update an outer product of a column and a
row; what the layout forces is ``k``, ``q`` and ``alpha`` **as columns**
where a row of the batch has them along lanes: ``HEADS`` heads' rows ``[8,
d]`` are transposed at once (``[d, 8]``: the XLU) and a head takes its
column of that. **What is traced and lowered is one row's body for
``HEADS`` heads**: the rows of a block and the groups of heads are
``fori_loop``s.

:func:`xla_kda_delta_rule` is the same mathematics as XLA sees it - the
sequences' states gathered ``[S, H, d, d]``, one ``lax.scan`` over the
rows, the states scattered back - the reference the tests compare against
and the path where the kernel does not run (:func:`delta_rule_impl`: not a
TPU, a mesh, or a shape :func:`kernel_supported` refuses).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.selective_scan import live_runs

KERNEL = "pallas_kda"
XLA = "xla"
ROWS = 8            # rows of the batch a grid step: a float32 tile's sublanes
HEADS = 8           # heads whose rows are turned into columns at once, and a loop body holds
LANES = 128
SMEM_BYTES = 256 << 10
STATE_VMEM_BYTES = 24 << 20     # the four state buffers may take this much VMEM
VMEM_LIMIT_BYTES = 48 << 20


def xla_kda_delta_rule(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha,
                       beta):
    """Reference math. pool [Lk, NS, H, d, d] float32; ``layer`` int32
    scalar; ``seq`` [T] int32: each row's sequence row (of ``S``); per
    sequence row: ``slot`` int32, ``first_row`` int32 (its first row of the
    batch), ``length`` int32 (its rows in this step, 0: none), ``fresh``
    bool; a row of the batch: ``q`` / ``k`` / ``v`` / ``log_alpha`` [T, H,
    d], ``beta`` [T, H], all float32. → (pool, o [T, H, d]); the module
    docstring has the equations. A row belongs to its sequence where it
    lies in the sequence's run; every other row gives zero and moves
    nothing."""
    NS = pool.shape[1]
    T = q.shape[0]
    here = length > 0
    carried = jnp.where(fresh[:, None, None, None], 0.0, pool[layer, slot])     # [S, H, d, d]
    rows = jnp.arange(T, dtype=jnp.int32)
    mine = (rows >= first_row[seq]) & (rows < first_row[seq] + length[seq])

    def one(states, row):
        s, live, q_t, k_t, v_t, g_t, b_t = row
        old = states[s]
        new = jnp.exp(g_t)[:, :, None] * old
        seen = jnp.sum(new * k_t[:, :, None], axis=1)                           # [H, d]
        new = new + (b_t[:, None, None] * k_t[:, :, None]) * (v_t - seen)[:, None, :]
        o_t = jnp.sum(new * q_t[:, :, None], axis=1)
        return states.at[s].set(jnp.where(live, new, old)), jnp.where(live, o_t, 0.0)

    f32 = jnp.float32
    states, o = jax.lax.scan(one, carried, (seq, mine, q.astype(f32), k.astype(f32),
                                            v.astype(f32), log_alpha.astype(f32),
                                            beta.astype(f32)))
    at = jnp.where(here, slot, NS)                  # a row with no token names no slot
    return pool.at[layer, at].set(states, mode="drop"), o


def kernel_supported(pool_shape, n_tokens, n_rows):
    """Can Mosaic tile it? A head's state ``[d, d]`` is whole float32 tiles
    with the values a whole lane tile (``d % 128``), the heads whole groups
    of ``HEADS``, the batch whole blocks of ``ROWS`` rows, four states
    within ``STATE_VMEM_BYTES`` and the rows' and sequences' scalars within
    the SMEM budget."""
    _, _, H, d, dv = pool_shape
    if d != dv or d % LANES or H % HEADS or n_tokens % ROWS:
        return False
    return (4 * H * d * d * 4 <= STATE_VMEM_BYTES
            and (n_tokens + 4 * n_rows + 3) * 4 <= SMEM_BYTES)


def delta_rule_impl(pool_shape, n_tokens, n_rows):
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
            q_ref, k_ref, a_ref, v_ref, b_ref, pool_ref, out_ref, o_ref,
            bufs, sems, *, heads):
    """One block of ``rows`` rows. q/k/a/v/b/o blocks [rows, H, d] (VMEM,
    piped; ``a`` the decays, ``b`` beta along the lanes); pool/out: the whole
    pool, one buffer under two names (HBM); ``bufs`` [4, H, d, d]: 0 and 1
    take a fetched slot, 2 and 3 hold the state a sequence's rows update;
    the rest in SMEM: meta the layer, the live sequences ``n`` and one past
    the last live row; ``row_ref`` each row's sequence in row order (-1:
    none), and the sequences in that order: their slots, first and last
    rows, fresh."""
    i = pl.program_id(0)
    rows, H, d = q_ref.shape
    layer, n, n_rows = meta_ref[0], meta_ref[1], meta_ref[2]

    def fetch(s):
        return pltpu.make_async_copy(pool_ref.at[layer, slot_ref[s]], bufs.at[s & 1],
                                     sems.at[0, s & 1])

    def store(s):
        return pltpu.make_async_copy(bufs.at[2 + (s & 1)], out_ref.at[layer, slot_ref[s]],
                                     sems.at[1, s & 1])

    def row_step(j, carry):
        """Row ``j`` of the block: a loop's index, so that one row's body is
        traced and lowered and not a block's eight."""
        r = i * rows + j
        s = row_ref[r]

        @pl.when((r < n_rows) & (s >= 0))
        def _():
            buf = s & 1
            first = r == start_ref[s]
            fresh = fresh_ref[s] != 0

            @pl.when(first)
            def _():
                @pl.when((s == 0) & jnp.logical_not(fresh))
                def _():
                    fetch(0).start()

                # the next sequence's slot flies during this one's rows
                nxt = jnp.minimum(s + 1, n - 1)

                @pl.when((s + 1 < n) & (fresh_ref[nxt] == 0))
                def _():
                    fetch(nxt).start()

                @pl.when(jnp.logical_not(fresh))
                def _():
                    fetch(s).wait()

                # what left this buffer two sequences ago must be gone before it is filled
                @pl.when(s >= 2)
                def _():
                    store(s - 2).wait()

            # a sequence's first row reads the slot as fetched (a fresh sequence's buffer
            # holds whatever it held: selected away, NaN or not), its later rows the state
            src = jnp.where(first, buf, 2 + buf)
            empty = first & fresh

            def group(g, carry):
                h0 = pl.multiple_of(g * heads, heads)
                these = pl.ds(h0, heads)
                q_cols, k_cols, a_cols = (ref[j, these, :].T for ref in (q_ref, k_ref, a_ref))
                v_rows, b_rows = v_ref[j, these, :], b_ref[j, these, :]        # [heads, d]
                out = []
                for c in range(heads):
                    col = slice(c, c + 1)
                    state = jnp.where(empty, 0.0, bufs[src, h0 + c]) * a_cols[:, col]
                    seen = jnp.sum(state * k_cols[:, col], axis=0, keepdims=True)   # [1, d]
                    state = state + k_cols[:, col] * (b_rows[col] * (v_rows[col] - seen))
                    bufs[2 + buf, h0 + c] = state
                    out.append(jnp.sum(state * q_cols[:, col], axis=0, keepdims=True))
                o_ref[j, these, :] = jnp.concatenate(out, axis=0)
                return carry

            jax.lax.fori_loop(0, H // heads, group, 0)

            @pl.when(r == end_ref[s])
            def _():
                store(s).start()

        return carry

    jax.lax.fori_loop(0, rows, row_step, 0)

    # the last two sequences' states are still on their way
    @pl.when(i + 1 == pl.num_programs(0))
    def _():
        for back in (1, 2):
            @pl.when(n >= back)
            def _():
                store(n - back).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _delta_call(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha, beta,
                interpret):
    """The kernel over the live rows (jitted so that a cell's programs
    share one trace of it)."""
    H, d = pool.shape[2:4]
    T = q.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    rows = ROWS if T % ROWS == 0 else T
    heads = HEADS if H % HEADS == 0 else H
    order, n_live, n_rows, mine, row_seq, start = live_runs(seq, first_row, length, T)

    def block():
        return pl.BlockSpec((rows, H, d), lambda i, *_: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,      # layer, sequences, rows; a row's sequence; the sequences'
        grid=(jnp.maximum((n_rows + rows - 1) // rows, 1),),
        in_specs=[block(), block(), block(), block(), block(),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), block()],
        scratch_shapes=[pltpu.VMEM((4, H, d, d), f32),
                        pltpu.SemaphoreType.DMA((2, 2))],       # [in | out, buffer]
    )
    new, o = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((T, H, d), f32)],
        input_output_aliases={11: 0},           # the pool, after the six scalars and q .. beta
        # rows in order on one core: a sequence starts the next one's fetch
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="kda_delta_rule",
    )(jnp.stack([jnp.asarray(layer, i32), n_live, n_rows]), row_seq, slot[order].astype(i32),
      start, start + length[order].astype(i32) - 1, fresh[order].astype(i32),
      q.astype(f32), k.astype(f32), jnp.exp(log_alpha.astype(f32)), v.astype(f32),
      jnp.broadcast_to(beta.astype(f32)[:, :, None], (T, H, d)), pool)
    # a row the grid did not reach, or no sequence's, has whatever its block of ``o`` held
    return new, jnp.where(mine[:, None, None], o, 0.0)


def kda_delta_rule(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha, beta,
                   interpret=None):
    """Pallas path of :func:`xla_kda_delta_rule` (same contract). Raises
    where Mosaic cannot tile the shapes; interpreted, any shape runs."""
    if interpret is None:
        from deepspeed_tpu.ops.pallas import default_interpret
        interpret = default_interpret()
    if not interpret and not kernel_supported(pool.shape, q.shape[0], slot.shape[0]):
        raise ValueError(
            f"the KDA kernel needs d % {LANES} == 0, H % {HEADS} == 0, whole blocks of {ROWS} "
            f"rows, four states in {STATE_VMEM_BYTES >> 20} MB of VMEM and the rows' scalars in "
            f"SMEM; got a pool {pool.shape} under {q.shape[0]} rows of {slot.shape[0]} "
            f"sequence rows")
    return _delta_call(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha,
                       beta, interpret)
