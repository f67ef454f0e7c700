"""The Kimi-delta-attention recurrence over a ragged step: every row of a
sequence through its slot's state, in order, one visit of the slot - a row
at a time, or where a sequence has many rows in the step a block at a time.

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

:func:`kda_delta_rule` is the Pallas kernel, **one recurrence in two
arithmetic forms, chosen by what it can observe: a run's length in the
step** (``length[s]``). A run of fewer than ``MIN_CHUNK_RUN`` rows - every
decode row - goes **a row at a time on the vector unit** (below); a longer
one, a prompt chunk, **a block of ``CHUNK`` rows at a time on the matrix
unit**, in the rule's chunked (WY / UT-transform) form (further below). Two
``pallas_call``s over disjoint slots, the row form's first, each under a name
that starts with ``kda_delta_rule``; a slot belongs to one of them. Both are
exact in float32 (no state, operand or product in fewer bits: the block
form's products are float32 operands at the highest, multi-pass precision),
drop nothing, and share the pipeline:

The pool is **aliased in and out** and stays in HBM; the layer (traced
inside the layer scan), each row's sequence and the sequences' slots, first
and last rows and ``fresh`` ride in SMEM (scalar prefetch), as
``selective_scan``'s do, whose pipeline this is. **The row form**: no
exponential in the kernel at all (``alpha = exp(log_alpha)`` is taken
outside, of a number that is never positive); the grid
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

**Arithmetic of the row form**: float32 on the vector unit, a head's ``[d,
d]`` tile (16 vregs) at a time. ``S'^T k`` and ``S^T q`` are sums over a
tile's sublanes (vreg adds, one shuffle), the update an outer product of a
column and a row; what the layout forces is ``k``, ``q`` and ``alpha`` **as
columns** where a row of the batch has them along lanes: ``HEADS`` heads'
rows ``[8, d]`` are transposed at once (``[d, 8]``: the XLU) and a head takes
its column of that. **What is traced and lowered is one row's body for
``HEADS`` heads**: the rows of a block and the groups of heads are
``fori_loop``s. A row costs ~140 vreg operations a head whatever it moves
(10.2 us a row of 64 heads: PERF.md, PR 48), which is why a run of many rows
takes the other form.

**The block form** (PR 49). With ``G_t`` the running sum of a block's
log-decays (a key row's own, ``[C, d]``, never positive), ``S_0`` the state
before the block and ``u_t = beta_t (v_t - S'_t^T k_t)`` what row ``t``
writes, unrolling the recurrence gives, all in the block's ``C`` rows::

    (I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S_0)
                A[t, i] = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}    (i < t)
    O = (Q e^G) S_0 + P U,      P[t, i] = the same of q_t, k_i       (i <= t)
    S <- Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

a unit lower-triangular system - the recurrence's own, solved in float32 -
and matrix products with a ``d`` or ``C`` contraction. **A decay is only
ever exponentiated as a difference that is <= 0**: ``e^{-G}`` alone
overflows under a strong decay, so the block is cut into sub-blocks of
``SUB`` rows; the products between a sub-block and the ones before it are
factored about **its first row** ``r`` (``k_t e^{G_t - r}`` and ``k_i e^{r -
G_i}``, both exponents <= 0: two matrix products a sub-block, ``[2 SUB, d] x
[d, C]`` and ``[2 SUB, C] x [C, d]``), the pairs inside a sub-block are
taken pairwise, a column ``i`` at a time (``e^{G_t - G_i}`` itself, a lane
sum), and the same loop solves the sub-block's triangle by columns (``U_i``
is final once the columns before it are subtracted). What stays on the
vector unit is that loop, the exponentials and the masks. The running sum
``G`` is a product with a triangle of ones. **A row of a block that is not
the run's is the identity** (``beta = 0``, ``log_alpha = 0``; its ``o`` is
not written): a run's rows fall in **aligned blocks of ``CHUNK`` rows of the
batch**, so a ragged start or end needs no second path, and a block two runs
share is visited once by each (an *item*). The grid is ``(groups of HEADS
heads, items)``: a group's items run in order, so that the ``o`` block two
runs share stays in VMEM between them, and a run's state - **its ``HEADS``
heads ``[8, d, d]``, 512 KB** - stays in VMEM across its blocks while their
operands ``[CHUNK, HEADS, d]`` stream; the state moves as the row form's
does, a (group, run) at a time: fetched while the one before computes,
written back while the next two do, so each live slot is still read once
and written once a call, by parts. **What is traced and lowered is one
head's block**: the heads of a group and the sub-blocks are ``fori_loop``s
(a sub-block's ``SUB`` columns are written out). Against
``xla_kda_delta_rule`` it reads ~4.5e-6 of the largest magnitude on the chip
(the row form 1.3e-7; ``tests/unit/ops/test_kda.py`` holds it to 2e-5 and
shows that one bfloat16 pass fails that); a 512-row chunk takes 1.4 ms a
layer at the cell's shape where the row form takes 5.2 (PERF.md, PR 49).

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
ROWS = 8            # rows of the batch a grid step of the row form: a float32 tile's sublanes
CHUNK = 64          # rows of the batch a block of the block form
SUB = 16            # rows of a sub-block: pairwise inside it, factored about its first row before
MIN_CHUNK_RUN = 16  # a run of fewer rows keeps the row form: 8 rows cost it 96 us, a block 103
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


def _row_call(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha, beta,
              interpret):
    """The row form over the runs ``length`` names → (pool, o, the rows that
    were its)."""
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
      q, k, jnp.exp(log_alpha), v, beta, pool)
    return new, o, mine


def _mxu(a, b, contract, one_pass):
    """A float32 product on the matrix unit at the highest (multi-pass)
    precision. ``one_pass``: the control of the tests and of nothing else -
    the operands rounded to bfloat16 first, what a single pass would see."""
    if one_pass:
        return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                                   (contract, ((), ())), preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
# the planes of the block kernel's scratch ``[7, CHUNK, d]``, a head's block at a time
_G, _K, _Q, _B, _U, _RHS, _O = range(7)


def _chunk_kernel(meta_ref, item_ref, block_ref, slot_ref, start_ref, end_ref, fresh_ref,
                  q_ref, k_ref, g_ref, v_ref, b_ref, pool_ref, out_ref, o_ref,
                  bufs, sems, work, *, one_pass):
    """One run's rows inside one aligned block of ``CHUNK`` rows, for one
    group of ``heads`` heads: grid step ``(group, item)``. q/k/g/v/b/o blocks
    [CHUNK, heads, d] (VMEM, piped; ``g`` the log-decays, ``b`` beta along the
    lanes); pool/out as the row kernel's; ``bufs`` [4, heads, d, d]: 0 and 1
    take a fetched group of a slot, 2 and 3 hold the state the run's blocks
    update; ``work`` [7, CHUNK, d]: a head's block (the planes above). SMEM:
    meta the layer and the runs ``n``; an item's run (-1: none) and block;
    the runs in row order: their slots, first and last rows, fresh. A (group,
    run) is one visit ``v`` of a slot's ``heads`` heads, in grid order:
    fetched while the visit before it computes, written back while the
    next two do."""
    grp, i = pl.program_id(0), pl.program_id(1)
    C, heads, d = q_ref.shape
    layer, n = meta_ref[0], meta_ref[1]
    s = item_ref[i]
    visits = n * pl.num_programs(0)
    last_step = (grp + 1 == pl.num_programs(0)) & (i + 1 == pl.num_programs(1))
    f32, i32 = jnp.float32, jnp.int32
    dot = functools.partial(_mxu, one_pass=one_pass)

    def part(ref, v):   # the visit's heads of its run's slot
        h0 = pl.multiple_of(jax.lax.div(v, n) * heads, heads)
        return ref.at[layer, slot_ref[jax.lax.rem(v, n)], pl.ds(h0, heads)]

    def fetch(v):
        return pltpu.make_async_copy(part(pool_ref, v), bufs.at[v & 1], sems.at[0, v & 1])

    def store(v):
        return pltpu.make_async_copy(bufs.at[2 + (v & 1)], part(out_ref, v), sems.at[1, v & 1])

    @pl.when(s >= 0)
    def _():
        v = grp * n + s
        buf = v & 1
        base = block_ref[i] * C
        lo = jnp.maximum(start_ref[s] - base, 0)            # the run's rows of this block
        hi = jnp.minimum(end_ref[s] + 1 - base, C)
        first = start_ref[s] >= base
        fresh = fresh_ref[s] != 0

        @pl.when(first)
        def _():
            @pl.when((v == 0) & jnp.logical_not(fresh))
            def _():
                fetch(0).start()

            # the next visit's state flies during this one's blocks
            nxt = jnp.minimum(v + 1, visits - 1)

            @pl.when((v + 1 < visits) & (fresh_ref[jax.lax.rem(nxt, n)] == 0))
            def _():
                fetch(nxt).start()

            @pl.when(jnp.logical_not(fresh))
            def _():
                fetch(v).wait()

            # what left this buffer two visits ago must be gone before it is filled
            @pl.when(v >= 2)
            def _():
                store(v - 2).wait()

        src = jnp.where(first, buf, 2 + buf)
        empty = first & fresh
        rows = jax.lax.broadcasted_iota(i32, (C, 1), 0)
        sub_rows = jax.lax.broadcasted_iota(i32, (SUB, 1), 0)
        valid = (rows >= lo) & (rows < hi)
        # a row that is not the run's is the identity: no decay, nothing written
        lower = (jax.lax.broadcasted_iota(i32, (C, C), 0)
                 >= jax.lax.broadcasted_iota(i32, (C, C), 1)).astype(f32)

        def state(c):
            return jnp.where(empty, 0.0, bufs[src, c])

        def enter(c):
            """Head ``c``'s block into ``work``: what the state it meets gives every
            row, and what every row would write were it alone."""
            q, k, val, g, b = (jnp.where(valid, ref[:, c, :], 0.0)
                               for ref in (q_ref, k_ref, v_ref, g_ref, b_ref))
            G = dot(lower, g, _NN)                      # the running sum of the log-decays
            decayed = jnp.exp(G)
            seen = dot(jnp.concatenate([k * decayed, q * decayed], axis=0), state(c), _NN)
            work[_G], work[_K], work[_Q], work[_B] = G, k, q, b
            work[_U] = jnp.zeros((C, d), f32)
            work[_RHS] = b * (val - seen[:C])
            work[_O] = seen[C:]

        def sub(I, carry):
            """Sub-block ``I``: the sub-blocks before it through products factored about
            its first row (both factors' exponents <= 0), its own rows pairwise, a column
            of the unit triangular system at a time."""
            r0 = pl.multiple_of(I * SUB, SUB)
            at = pl.ds(r0, SUB)
            GI, KI, QI = work[_G, at], work[_K, at], work[_Q, at]
            BI = work[_B, at][:, :1]
            about = work[_G, pl.ds(r0, 1)]
            since = jnp.exp(GI - about)
            until = jnp.where(rows < r0, work[_K]
                              * jnp.exp(jnp.minimum(about - work[_G], 0.0)), 0.0)
            ap = dot(jnp.concatenate([KI * since, QI * since], axis=0), until, _NT)
            moved = dot(jnp.concatenate([BI * ap[:SUB], ap[SUB:]], axis=0), work[_U], _NN)
            rhs = work[_RHS, at] - moved[:SUB]
            o = work[_O, at] + moved[SUB:]
            # a column of the sub-block's own pairs at a time: row j of U is final once the
            # columns before it are subtracted, so it leaves the rows after it and enters o
            # from its own row on. Written out: a column's lane sums wait ~300 cycles, which
            # sixteen independent ones hide and a loop does not (7.0 ms a 512-row chunk for 1.4)
            for j in range(SUB):
                y = KI[j:j + 1] * jnp.exp(jnp.minimum(GI - GI[j:j + 1], 0.0))
                a = jnp.sum(KI * y, axis=-1, keepdims=True)
                p = jnp.sum(QI * y, axis=-1, keepdims=True)
                u = rhs[j:j + 1]
                rhs = rhs - jnp.where(sub_rows > j, BI * a, 0.0) * u
                o = o + jnp.where(sub_rows >= j, p, 0.0) * u
            work[_U, at] = rhs
            work[_O, at] = o
            return carry

        def leave(c):
            whole = work[_G, C - 1:C]                # the block's decay, a key row's own
            rest = work[_K] * jnp.exp(whole - work[_G])
            column = jnp.broadcast_to(jnp.exp(whole), (ROWS, d)).T[:, :1]
            bufs[2 + buf, c] = column * state(c) + dot(rest, work[_U], _TN)
            o_ref[:, c, :] = jnp.where(valid, work[_O], o_ref[:, c, :])

        def head(c, carry):
            enter(c)
            jax.lax.fori_loop(lo // SUB, (hi + SUB - 1) // SUB, sub, 0)
            leave(c)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)

        @pl.when(end_ref[s] < base + C)
        def _():
            store(v).start()

    # the last two visits' states are still on their way
    @pl.when(last_step)
    def _():
        for back in (1, 2):
            @pl.when(visits >= back)
            def _():
                store(visits - back).wait()


def _chunk_call(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha, beta,
                interpret, min_run, one_pass):
    """The block form over the runs ``length`` names → (pool, o, the rows
    that were its). A run's rows fall in aligned blocks of ``CHUNK`` rows of
    the batch; an **item** is one run's rows of one block, and a block that
    two runs share is two items."""
    H, d = pool.shape[2:4]
    T, S = q.shape[0], slot.shape[0]
    f32, i32 = jnp.float32, jnp.int32
    heads = HEADS if H % HEADS == 0 else H
    order, n_runs, _, mine, _, start = live_runs(seq, first_row, length, T)
    end = start + length[order].astype(i32) - 1
    blocks = jnp.where(length[order] > 0, end // CHUNK - start // CHUNK + 1, 0)
    upto = jnp.cumsum(blocks)
    n_items = upto[-1]
    items = T // CHUNK + min(S, T // max(min_run, 1))         # a run adds at most one item
    at = jnp.arange(items, dtype=i32)
    run = jnp.minimum(jnp.searchsorted(upto, at, side="right"), S - 1).astype(i32)
    block = jnp.where(at < n_items, start[run] // CHUNK + at - (upto - blocks)[run], 0)
    run = jnp.where(at < n_items, run, -1)

    def rows():
        return pl.BlockSpec((CHUNK, heads, d), lambda g, i, meta, item_run, item_block, *_:
                            (item_block[i], g, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,      # layer, runs; an item's run and block; the runs'
        grid=(jnp.where(n_items > 0, H // heads, 1), jnp.maximum(n_items, 1)),
        in_specs=[rows(), rows(), rows(), rows(), rows(), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), rows()],
        scratch_shapes=[pltpu.VMEM((4, heads, d, d), f32),
                        pltpu.SemaphoreType.DMA((2, 2)),        # [in | out, buffer]
                        pltpu.VMEM((7, CHUNK, d), f32)],
    )
    new, o = pl.pallas_call(
        functools.partial(_chunk_kernel, one_pass=one_pass),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((T, H, d), f32)],
        input_output_aliases={12: 0},           # the pool, after the seven scalars and q .. beta
        # a group's items in order on one core: a block two runs share keeps its ``o``
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="kda_delta_rule_blocks",
    )(jnp.stack([jnp.asarray(layer, i32), n_runs]), run, block.astype(i32),
      slot[order].astype(i32), start, end, fresh[order].astype(i32),
      q, k, log_alpha, v, beta, pool)
    return new, o, mine


def chunk_rows(length, n_tokens, min_run=None):
    """→ [S] bool: the sequence rows whose run of this step takes the block
    form in a program of ``n_tokens`` rows - a run of ``MIN_CHUNK_RUN`` rows
    or more, where the batch is whole blocks of ``CHUNK``."""
    min_run = MIN_CHUNK_RUN if min_run is None else min_run
    if n_tokens % CHUNK:
        return jnp.zeros(length.shape, bool)
    return length >= max(min_run, 1)


@functools.partial(jax.jit, static_argnames=("interpret", "one_row_runs", "min_run", "one_pass"))
def _delta_call(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha, beta,
                interpret, one_row_runs=False, min_run=None, one_pass=False):
    """Both forms over the live rows, each over the runs that are its (jitted
    so that a cell's programs share one trace of it); the row form alone
    where no run can take the other. ``min_run`` and ``one_pass`` are the
    census' and the tests': the crossover swept, the control of the
    precision."""
    T, H, d = q.shape
    f32 = jnp.float32
    q, k, v, log_alpha = (x.astype(f32) for x in (q, k, v, log_alpha))
    beta = jnp.broadcast_to(beta.astype(f32)[:, :, None], (T, H, d))
    min_run = MIN_CHUNK_RUN if min_run is None else min_run
    blocks = chunk_rows(length, T, min_run)
    pool, o, mine = _row_call(pool, layer, seq, slot, first_row, jnp.where(blocks, 0, length),
                              fresh, q, k, v, log_alpha, beta, interpret)
    # a row the grid did not reach, or no sequence's, has whatever its block of ``o`` held
    o = jnp.where(mine[:, None, None], o, 0.0)
    if T % CHUNK or one_row_runs:
        return pool, o
    pool, o_blocks, theirs = _chunk_call(pool, layer, seq, slot, first_row,
                                         jnp.where(blocks, length, 0), fresh, q, k, v,
                                         log_alpha, beta, interpret, min_run, one_pass)
    return pool, jnp.where(theirs[:, None, None], o_blocks, o)


def kda_delta_rule(pool, layer, seq, slot, first_row, length, fresh, q, k, v, log_alpha, beta,
                   interpret=None, one_row_runs=False):
    """Pallas path of :func:`xla_kda_delta_rule` (same contract). Raises
    where Mosaic cannot tile the shapes; interpreted, any shape runs.
    ``one_row_runs``: the program holds one row a sequence by construction (a
    burst's step, which says so as it does to the paged kernel:
    ``model_runner.ragged_forward``), so the block form is not lowered into it."""
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
                       beta, interpret, one_row_runs)
