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

The kernel has ``paged_decode_attention``'s pipeline (one grid step a
token in order on one core, tables and positions in SMEM, block DMA out of
the whole pool by layer index, two slots, running max and sum;
``paged_attention``'s docstring has the reasoning, which is not repeated
here), fitted to a row that is both key and value and that every head
shares: a fetched block is fetched **once** and all ``H`` query rows ride
one matmul against it.

**A tile** is ``n`` consecutive table blocks of one token's context laid
one under the other in a slot ``[n * bs, rank]`` of ``c`` and one ``[n *
bs, lanes]`` of ``r`` (:func:`mla_tile`: 8 blocks of 256 rows under 16
heads, 4 under 64). Every copy of a tile is started before any is waited
for. **A unit** is the rows a tile's pair of matmuls grows by: the pair
runs once a tile over the ``[H, w]`` scores of the first ``w`` rows of the
slot, ``w`` the whole units the context reaches (one branch a width), so a
context of 300 rows in a tile of 1024 multiplies 512. A copy is a whole
block: copied in halves or quarters, so that rows past the context were
not fetched, a block changed no time on the chip (what a token waits for
at 64 heads is its arithmetic; PERF.md, PR 35) and that part was left out.

**In flight**: while tile ``i`` is multiplied, tile ``i + 1``'s copies fly
into the other slot, and after a token's last tile the **next token's
first tile**. **Reuse**: where the token before had one tile and every
block the two first tiles share is the same block (:func:`fetch_plan`: a
prompt chunk's consecutive tokens), the next token's first tile **stays**
in that slot and only the blocks it lacks are fetched - started after this
token's wait (a slot has one semaphore a pool) into rows this token's
scores mask. A chunk's rows are in the pool before the call, so a held
block is the block. **Live rows**: the grid ends where the call's padding
rows start (``live_rows``), and those rows are zeros:
``paged_attention``'s paragraph of that name.

**Stale rows**: ``c`` is the value operand too, so its slots are zeroed in
the first grid step and scores past the position are replaced;
``paged_attention``'s paragraph of that name applies as written, and a
block no context names is never read. The matmuls take the
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
                                                      SMEM_TABLE_BYTES, live_grid,
                                                      smem_table_fits, zeros_past)


def xla_paged_mla_attention(q, c_pool, r_pool, block_tables, token_pos, layer, live_rows=None):
    """Reference math by gather. q [T, H, rank + lanes], scaled;
    c_pool [L, NB, bs, rank]; r_pool [L, NB, bs, lanes]; block_tables
    [T, MB] (per token); token_pos [T]; layer int32 scalar →
    [T, H, rank]; attends to positions <= token_pos. ``live_rows`` is the
    kernel's: the gather computes every row."""
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


# A tile's context rows at the most, the VMEM its four slots (c and r, two
# each) and its float32 score tile may take, the rows its pair of matmuls
# grows by, and the widths it is compiled at at the most: mla_tile().
MLA_TILE_ROWS = 2048
MLA_VMEM_BYTES = 6 << 20
MLA_SCORE_BYTES = 256 << 10
MLA_UNIT_ROWS = 256
MLA_WIDTHS = 8


def mla_tile(block_size, row_bytes, itemsize, max_blocks, heads):
    """(``n``, ``unit``) from the shapes alone: the table blocks of a tile
    and the rows its pair of matmuls grows by. ``n`` is
    :func:`paged_attention.tile_blocks`' rule at the latent row's sizes:
    ``MLA_TILE_ROWS`` rows a tile, halved until the two slots of ``c`` and
    ``r`` fit ``MLA_VMEM_BYTES`` and the ``[heads, n * block_size]``
    float32 score tile ``MLA_SCORE_BYTES``, never more than the table has
    blocks, 1 for a block that is no whole number of sublane tiles. The
    cells' shapes (256-row bf16 blocks of 1280 bytes) get 8 blocks under
    Moonlight's 16 heads and 4 under LongCat's 64: on the chip
    (``tools/kernel_census.py --mla``; PERF.md, PR 35) 1, 2, 4 and 8
    blocks read 56, 73, 77 and 79 % of the HBM roofline at 16 heads and
    contexts of 1024-4096, and 1, 2, 4 and 6 blocks 45, 51, 51 and 51 % at
    64 heads and 128-1536 (38, 42, 41 and 40 % beside a prompt chunk),
    where a turn's arithmetic and not its copies is what is
    waited for. ``unit`` is ``MLA_UNIT_ROWS`` where the tile is a whole
    number of them and no more than ``MLA_WIDTHS`` (Mosaic's layout
    inference fails on a deeper chain of branches), else the tile."""
    if block_size % (32 // itemsize):
        return 1, block_size
    n = max(1, MLA_TILE_ROWS // block_size)
    while n > 1 and (2 * n * block_size * row_bytes > MLA_VMEM_BYTES
                     or heads * n * block_size * 4 > MLA_SCORE_BYTES):
        n //= 2
    n = min(n, max_blocks)
    rows = n * block_size
    fits = rows % MLA_UNIT_ROWS == 0 and rows // MLA_UNIT_ROWS <= MLA_WIDTHS
    return n, MLA_UNIT_ROWS if fits else rows


def fetch_plan(block_tables, token_pos, block_size, n, live_rows=None):
    """The kernel's fetch rule as one pure function of the call's tables
    and positions (``_kernel``'s ``plan`` states it a token at a time on
    the same integers). A token's first tile **stays** in the slot of the
    token before it where that token had one tile and every block the two
    first tiles share is the same block; it is then fetched from the block
    that token held up to (nothing, where it reaches no further), else
    whole into the other slot. Every later tile is fetched whole. A row
    from ``live_rows`` on (None: no such row) is beyond the grid: it names
    its one block and nothing is fetched for it. → per token, int32 [T]:
    the blocks its context names (``pos // block_size + 1``, never more
    than the table has) and those of them a copy is started for."""
    T, MB = block_tables.shape
    block_tables, token_pos = block_tables.astype(jnp.int32), token_pos.astype(jnp.int32)
    named = jnp.minimum(token_pos // block_size + 1, MB)
    held = jnp.minimum(named, n)                    # blocks of the first tile
    before_held = jnp.roll(held, 1)
    cols = min(n, MB)
    same = ((block_tables[:, :cols] == jnp.roll(block_tables, 1, axis=0)[:, :cols])
            | (jnp.arange(cols)[None, :] >= jnp.minimum(held, before_held)[:, None]))
    stays = (jnp.arange(T) > 0) & (jnp.roll(named, 1) <= n) & jnp.all(same, axis=1)
    fetched = named - jnp.minimum(jnp.where(stays, before_held, 0), held)
    if live_rows is not None:
        fetched = jnp.where(jnp.arange(T) < jnp.maximum(live_rows, 1), fetched, 0)
    return named, fetched


def fetch_counts(block_tables, token_pos, block_size, n, live_rows=None):
    """:func:`fetch_plan` summed over the call's tokens → int32 (blocks
    named, blocks fetched): what a step record's ``n_blocks_named`` and
    ``n_blocks_fetched`` are of."""
    named, fetched = fetch_plan(block_tables, token_pos, block_size, n, live_rows)
    return jnp.sum(named).astype(jnp.int32), jnp.sum(fetched).astype(jnp.int32)


def _kernel(tab_ref, pos_ref, layer_ref, q_ref, c_hbm, r_hbm, o_ref,
            c_buf, r_buf, sems, nxt_ref, *, bs, n, unit, max_blocks, rank, ahead, reuse):
    """One token: q_ref [1, H, rank + lanes] (VMEM); both pools stay in
    HBM; tables, positions and layer in SMEM. The module docstring says
    what a tile and a unit are and what is in flight when. ``ahead`` (the
    next token's first tile early) and ``reuse`` are on but in the
    census."""
    t = pl.program_id(0)
    T = pl.num_programs(0)
    layer = layer_ref[0]
    H = q_ref.shape[1]
    rows = n * bs
    contract_last = (((1,), (1,)), ((), ()))

    # positions and counts are never negative: lax.div / & 1 (paged_attention._kernel)
    def n_blocks(tok):
        return jnp.minimum(jax.lax.div(pos_ref[tok], bs) + 1, max_blocks)

    def blocks_of(tok, i):
        """The blocks of ``tok``'s tile ``i`` that its context reaches."""
        return jnp.clip(n_blocks(tok) - i * n, 0, n)

    def plan(tok, before):
        """→ (``tok``'s first tile stays in the slot of ``before``'s, the
        block it is fetched from): :func:`fetch_plan`'s rule a token at a
        time."""
        held = blocks_of(before, 0)
        share = jnp.minimum(blocks_of(tok, 0), held)
        stays = n_blocks(before) <= n
        for j in range(min(n, max_blocks)):
            stays &= (tab_ref[tok, j] == tab_ref[before, j]) | (j >= share)
        return stays, jnp.where(stays, held, 0)

    def each_block(tok, i, slot, first, act):
        """``act`` on the copies of blocks ``first``.. of ``tok``'s tile ``i``."""
        def one(j, carry):
            blk = tab_ref[tok, i * n + j]
            at = pl.ds(pl.multiple_of(j * bs, bs), bs)
            act(pltpu.make_async_copy(c_hbm.at[layer, blk], c_buf.at[slot, at], sems.at[0, slot]))
            act(pltpu.make_async_copy(r_hbm.at[layer, blk], r_buf.at[slot, at], sems.at[1, slot]))
            return carry
        jax.lax.fori_loop(first, blocks_of(tok, i), one, 0)

    def start(tok, i, slot, first):
        each_block(tok, i, slot, first, lambda copy: copy.start())

    @pl.when(t == 0)
    def _():
        nxt_ref[0] = 0
        nxt_ref[1] = 0
        c_buf[...] = jnp.zeros(c_buf.shape, c_buf.dtype)  # see "stale rows"

    slot0, first0 = nxt_ref[0], nxt_ref[1]

    @pl.when((t == 0) | (not ahead))
    def _():
        start(t, 0, slot0, first0)

    pos = pos_ref[t]
    n_tiles = jax.lax.div(n_blocks(t) + n - 1, n)
    nxt = jnp.minimum(t + 1, T - 1)
    has_next = t + 1 < T
    if reuse:
        stays, nxt_first = plan(nxt, t)
    else:
        stays, nxt_first = False, 0
    q = q_ref[0]
    q_lat, q_rope = q[:, :rank], q[:, rank:]

    def attend(slot, width, first_pos, carry):
        """The first ``width`` rows of a slot, whose first is context
        position ``first_pos``: one pair of matmuls for all H heads."""
        m, l, acc = carry  # [H, 1], [H, 1], [H, rank]
        c = c_buf[slot, pl.ds(0, width)]     # [width, rank]: the keys' latent part AND the values
        kr = r_buf[slot, pl.ds(0, width)]    # [width, lanes]
        s = (jax.lax.dot_general(q_lat, c, contract_last, preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_rope, kr, contract_last,
                                   preferred_element_type=jnp.float32))     # [H, width]
        kv_pos = first_pos + jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        s = jnp.where(kv_pos <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)        # [H, rank]
        return m_new, l_new, acc * alpha + pv

    def tile_step(i, carry):
        slot = (slot0 + i) & 1
        last = i + 1 == n_tiles
        to_other = has_next & jnp.logical_not(stays) if ahead else False

        # the next tile in order - this token's, or the next token's first -
        # flies into the other slot during this tile's arithmetic
        @pl.when(jnp.logical_not(last) | to_other)
        def _():
            start(jnp.where(last, nxt, t), jnp.where(last, 0, i + 1), 1 - slot, 0)

        each_block(t, i, slot, jnp.where(i == 0, first0, 0), lambda copy: copy.wait())

        # ... or, where it stays, the blocks this slot lacks: after the wait
        # (one semaphore a slot), into rows this token's scores mask
        if ahead and reuse:
            @pl.when(last & has_next & stays)
            def _():
                start(nxt, 0, slot, nxt_first)

        # one pair of matmuls a tile, as wide as the units the context reaches
        widths = range(unit, rows + 1, unit)
        if len(widths) == 1:
            return attend(slot, rows, i * rows, carry)
        live = jnp.minimum(pos + 1 - i * rows, blocks_of(t, i) * bs)
        units = jax.lax.div(live + unit - 1, unit)
        return jax.lax.switch(units - 1, [functools.partial(attend, slot, w, i * rows)
                                          for w in widths], carry)

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    a0 = jnp.zeros((H, rank), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_tiles, tile_step, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # the next token's first tile: in this token's last slot, or in the other
    last_slot = (slot0 + n_tiles - 1) & 1
    nxt_ref[0] = jnp.where(stays, last_slot, 1 - last_slot)
    nxt_ref[1] = nxt_first


@functools.partial(jax.jit, static_argnames=("n", "unit", "interpret", "ahead", "reuse"))
def _mla_call(q, c_pool, r_pool, block_tables, token_pos, layer, n, unit, interpret,
              ahead=True, reuse=True, live_rows=None):
    """The kernel at ``n`` blocks a tile whose pair of matmuls grows by
    ``unit`` rows (``tools/kernel_census.py --mla`` sweeps them and
    switches ``ahead`` and ``reuse`` off; everything else gets
    :func:`mla_tile`'s). Jitted so that the serving programs of one
    shape share one trace of it. ``live_rows`` (None: every row) is where
    the grid ends."""
    T, H, width = q.shape
    live_rows, grid = live_grid(T, live_rows)
    bs, rank, lanes = c_pool.shape[2], c_pool.shape[3], r_pool.shape[3]
    MB = block_tables.shape[1]
    if n * bs // unit > MLA_WIDTHS:
        raise ValueError(f"a tile of {n * bs} rows in units of {unit} is more than {MLA_WIDTHS} "
                         f"widths: Mosaic's layout inference fails on so deep a chain of branches")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tables, positions, layer
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H, width), lambda t, tab, pos, layer: (t, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda t, tab, pos, layer: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n * bs, rank), c_pool.dtype),
            pltpu.VMEM((2, n * bs, lanes), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # [c | r, slot]
            pltpu.SMEM((2,), jnp.int32),      # this token's first tile: its slot, its first block
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, n=n, unit=unit, max_blocks=MB, rank=rank,
                               ahead=ahead, reuse=reuse)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, rank), q.dtype),
        # tokens in order on one core: a token starts the next one's first tile
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_mla_decode_attention",
    )(block_tables.astype(jnp.int32), token_pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, c_pool, r_pool)
    return zeros_past(out, live_rows)


def paged_mla_decode_attention(q, c_pool, r_pool, block_tables, token_pos, layer,
                               live_rows=None, interpret=None):
    """Pallas path of :func:`xla_paged_mla_attention` (same contract on
    the rows before ``live_rows``, zeros from there on; None: every row)."""
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
    n, unit = mla_tile(bs, width * c_pool.dtype.itemsize, c_pool.dtype.itemsize, MB, H)
    return _mla_call(q, c_pool, r_pool, block_tables, token_pos, layer, n, unit, interpret,
                     live_rows=live_rows)
