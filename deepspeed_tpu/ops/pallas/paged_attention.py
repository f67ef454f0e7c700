"""Paged attention kernel: a step's query rows vs a block-tabled KV.

TPU-native counterpart of the reference's ragged decode kernels
(``deepspeed/inference/v2/kernels/ragged_ops/atom_builder`` +
``blocked_flash`` over the blocked KV cache,
``csrc/.../ragged_ops/``). Each token walks its own context - or, a
prompt chunk's rows, one walk together ("A query tile", below): its block
table rides in SMEM (scalar prefetch), KV blocks are dynamically
indexed out of the pool, and scores accumulate flash-style (running
max / sum) with positions beyond the token's context masked. GQA is
handled by viewing the query heads as [Hkv, G, Dh].

Both paths take the WHOLE pool ``[L, NB, bs, Hkv*Dh]`` — the layout
``BlockedKVCache`` stores — and the layer as an index, so the layer
scan never cuts a layer out of the pool and no program reshapes it:
the kernel's block DMA reads ``pool[layer, blk]``, the reference
gathers ``pool[layer, block_tables]``.

**A tile** is ``n`` consecutive table blocks of one token's context laid
one under the other in a VMEM slot ``[n*bs, Hkv*Dh]``, one slot for K and
one for V: block ``j`` of the tile lands at rows ``j*bs``. ``n`` follows
from the shapes (:func:`tile_blocks`: 16 blocks = 256 rows for 16-row
bf16 blocks of 8 KV heads, so that the ``[H, n*bs]`` score tile is whole
128-lane vregs and a KV head's two matmuls run once a tile; 1 where a
block is not a whole number of sublane tiles). All ``2n`` copies of a
tile are started before any is waited for, and there are **two slots**:
while tile ``i`` is multiplied, tile ``i+1``'s copies fly into the other
slot — and after a
token's last tile, the **next token's first tile** (grid steps run in
order on one core; every token's table and position are in SMEM from the
start), so that no token waits for a fetch it could have had. A token
whose whole context is the one block the token before it had as its own
(a prompt's first tokens; padding rows among the live ones) finds it in
that token's slot and fetches nothing.

**A query tile.** A row a grid step fetches a context once a row: a
512-row chunk of a prompt at context 2048 read its sequence's keys and
values ~480 times over (3.8 ms a call at ``lfm2-24b-rag``'s shape, 12.5 ms
at context 7680, where the chunk's bytes are microseconds of HBM; PERF.md,
PR 41 and 42). The batch says when that is waste: the engine lays a
sequence's rows of a step side by side at consecutive positions, so rows
``t .. t+r-1`` of one sequence have one table and need context ``0 ..
pos[t+r-1]``. :func:`query_tiles` - a pure function of the batch's
``token_seq`` and ``token_pos``, traced in the program; the host counts a
step record's ``n_chunk_rows`` / ``n_chunk_tiles`` in numpy by the step of
it that says which rows share a walk (:func:`chunk_counts`) - cuts the rows
into **items**: a tile is two
to ``QUERY_TILE`` such rows inside one block of ``QUERY_TILE`` rows, every
other row (a decode row among other sequences' rows, padding) an item of
its own. The grid is one step an
item, and the pipeline above is the items': a tile's step walks the
context's tiles **once**, up to its last row's position, multiplies a KV
head's slot rows by the tile's ``QUERY_TILE x G`` queries at a time, masks
each query at its own row's position and keeps a running max / sum /
accumulator a query. A query is a **column** there (scores ``[context
rows, queries]``): the running max and sum of a tile are one lane each of a
``[1, queries]`` row and not a sublane each of a ``[queries, 1]`` column,
which cost as many vector registers as the scores themselves; the
wrapper hands the kernel the block's queries a second time in that
layout (``[Hkv, blocks, Dh, QUERY_TILE x G]``, one pass of XLA over q) and
takes a tile's output back the same way. A row's arithmetic is the row
path's - the same context tiles in the same order, scores scaled in
float32, probabilities rounded to the pool's dtype, a tile wholly past a
row's position contributing exactly nothing - so the two agree to
rounding. A one-row item takes the one-row body and costs what it cost.
Who passes tiles: ``model_runner._paged_attend`` for the programs whose
rows may be neighbours of one sequence (``put``, verify). A program of one
row a sequence by construction (the burst family) and a selection's call
(``selected=True``: a table a (token, head), neighbours' tables differ)
pass none and lower the kernel a row a grid step - the program they lowered
before there were tiles, operation for operation (the Mosaic module differs
from PR 41's in its source locations alone); rows that are no whole blocks
of ``QUERY_TILE``, or whose items do not fit SMEM beside their table, take
none either.

**Live rows.** A program's width is static and its batch is not: the
engine packs the rows that hold a token first and pads the rest (table on
the null block, position 0). ``live_rows`` says where the padding starts,
and the grid ends there - a dynamic bound, so the rows from there on cost
no grid step, no copy and no arithmetic; they are written as zeros after
the call (their hidden state still goes through the layers behind
attention, and must be finite whatever the null block holds). A padding
row among the live ones is a row like any other. On the chip a padding
row costs 0.02 us here and 0.11 us in the latent kernel, the zeroing
alone, where it cost 0.58 and 0.85 us (``tools/kernel_census.py --live``;
PERF.md, PR 37); an early-out a grid step (``pl.when`` around the body)
read 0.22 and 0.42 and was not kept.

**Stale rows.** A tile's blocks past the context's last are not fetched,
so their rows are whatever the slot held. Keys: the scores at positions
past the token's are replaced (``where``), so nothing of them survives,
NaN or not. Values: a masked position's probability is exactly 0, and
0 x NaN is NaN, so V's slots are zeroed once, in the first grid step;
from then on a slot's rows are zeros or rows of blocks that some token's
context named — what the block-a-step loop also multiplied by 0 in a
context's last block. A block no context names is never read.

**Arithmetic** is the pool's: a 2-byte pool's rows (and queries of the
same dtype) go to the MXU as they lie, one pass with float32
accumulation — bf16 x bf16 products are exact in float32 — and the
probabilities are rounded to the pool's dtype for the second product, as
:func:`xla_paged_attention` and the latent kernel do; the scale is
applied to the float32 scores, and running max, sum and accumulator stay
float32. A float32 pool (what the CPU tests hold to 1e-5) keeps
``Precision.HIGHEST``.

**A table that is a selection.** Nothing in either path asks that a
token's table be its sequence's whole table: ``block_tables[t]`` is the
blocks token ``t`` reads, in the order they are laid under each other,
and ``token_pos[t]`` says where that context ends — ``pos // bs + 1``
blocks are read and the rows past ``pos % bs`` of the last are masked.
So a token that reads only some blocks of its context (block-sparse
attention: ``model_runner.SalaKind``) is given a table of those blocks,
ascending with the block that holds the query last, and the position
that its last row has **in that table**: :func:`selected_tables` says
how, per (token, key-value head), each a row of the call with the head's
query group as its heads and the head's own pool layer's blocks.

The XLA reference path (``xla_paged_attention``) is the same math via
gather. Which of the two a program runs is decided in ONE place, the
``inference/v2/modules/heuristics`` registry (``supports()`` there reads
:func:`kernel_supported` and :func:`smem_table_fits`), where the choice
has a name the engine reports; the kernel entry itself never hands a
refused shape to the reference — it raises.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)

# The block tables + positions ride in SMEM via scalar prefetch and v5e
# SMEM is ~1 MB: oversized state configs (e.g. the default
# max_tokens=768 x max_context/bs tables) overflow it at COMPILE time
# ("Ran out of memory in memory space smem").
SMEM_TABLE_BYTES = 768 * 1024
# The gather reference materializes a dense [T, MB*bs, Hkv, Dh] copy of
# K and of V per layer; past this it is an opaque allocator OOM.
GATHER_LIMIT_BYTES = 2 << 30
# A tile's context rows (the score tile's lanes) and the VMEM its four
# slots (K and V, two each) may take: tile_blocks().
TILE_ROWS = 256
TILE_VMEM_BYTES = 4 << 20
# What a slot holds at the least where the table is a selection
# (``selected=True``): tile_blocks().
SELECTED_SLOT_BYTES = 512 << 10
# The head size two of which make one 128-lane slice: kernel_supported(), _paired().
PAIRED_HEAD_DIM = 64
# A query tile (the module docstring): the rows it holds at the most, which is
# also the height of the kernel's query block.
QUERY_TILE = 32


def xla_paged_attention(q, kc, vc, block_tables, token_pos, layer, live_rows=None, tiles=None,
                        alibi_slopes=None, selected=False, window=None):
    """Reference math. q: [T, H, Dh]; kc/vc: the pool [L, NB, bs, Hkv*Dh];
    block_tables: [T, MB] (per TOKEN, already indexed by its sequence);
    token_pos: [T]; layer: int32 scalar, the layer of the pool to read.
    → [T, H, Dh]; attends to positions <= token_pos.
    ``alibi_slopes``: optional [H] — adds the Bloom-style linear
    relative-position penalty slope_h * (k_pos - q_pos) to the scores.
    ``live_rows``, ``tiles``, ``selected``: the kernel's (where its grid
    ends; its query tiles; its tile of the context); the gather computes
    every row and reads the same rows either way. ``window``: the kernel's
    (:func:`window_tables`' table and positions; a row attends to the last
    ``window`` positions up to its own)."""
    T, H, Dh = q.shape
    bs, Hkv = kc.shape[2], kc.shape[3] // Dh
    gather_bytes = 2 * T * block_tables.shape[1] * bs * Hkv * Dh * kc.dtype.itemsize
    if gather_bytes > GATHER_LIMIT_BYTES:
        raise ValueError(
            f"the XLA gather attention would materialize {gather_bytes / 1e9:.0f} GB of KV "
            f"for block table [{T}, {block_tables.shape[1]}] — shrink "
            f"max_ragged_batch_size / max_context, or raise kv_block_size")
    ks = kc[layer, block_tables].reshape(T, -1, Hkv, Dh).astype(q.dtype)
    vs = vc[layer, block_tables].reshape(T, -1, Hkv, Dh).astype(q.dtype)
    if Hkv != H:
        from deepspeed_tpu.models.llama import repeat_kv
        ks, vs = repeat_kv(ks, vs, H // Hkv)
    scale = 1.0 / np.sqrt(Dh)
    scores = jnp.einsum("thd,tchd->thc", q, ks).astype(jnp.float32) * scale
    k_idx = jnp.arange(ks.shape[1])
    if alibi_slopes is not None:
        rel = (k_idx[None, :] - token_pos[:, None]).astype(jnp.float32)  # [T, C]
        scores = scores + alibi_slopes[None, :, None] * rel[:, None, :]
    mask = k_idx[None, :] <= token_pos[:, None]
    if window is not None:
        mask &= k_idx[None, :] > token_pos[:, None] - window
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("thc,tchd->thd", probs, vs)


def selected_tables(tables, counts, token_pos, block_size):
    """The paged call's table and positions for rows that read a
    **selection** of their context. tables [T, Hkv, W]: per (token,
    key-value head) the pool blocks to read, the first ``counts [T, Hkv]``
    columns in ascending order of their place in the sequence, the block
    that holds the query among them and therefore last; ``token_pos`` [T]:
    the query's position in its sequence. → (table [T*Hkv, W] with the
    columns past a row's count on the null block, positions [T*Hkv]): a
    row reads ``count`` blocks, and in the last — the query's own — the
    rows up to the query's, which is what the causal mask comes to when
    every other block read lies wholly before the query."""
    T, Hkv, W = tables.shape
    tab = jnp.where(jnp.arange(W)[None, None, :] < counts[..., None], tables, 0)
    at = (counts - 1) * block_size + (token_pos % block_size)[:, None]
    return tab.reshape(T * Hkv, W).astype(jnp.int32), at.reshape(T * Hkv).astype(jnp.int32)


def window_columns(window, block_size, rows=1):
    """The columns of a windowed call's table: the blocks that the windows
    of ``rows`` rows of one sequence at consecutive positions can touch, the
    first row's window starting anywhere in its first block (9 for a window
    of 512 over 64-row blocks, 10 under a query tile of 32 rows)."""
    return (block_size + window + rows - 3) // block_size + 1


def window_tables(rings, token_pos, window, block_size, rows=1):
    """The paged call's table and positions for rows that attend to the
    last ``window`` positions of their context (``window=`` of both paths).
    rings [T, R]: per token its sequence's row of the window pool's table, a
    **ring**: the block that holds positions ``b * block_size ..`` stands in
    column ``b % R`` (``ragged_manager.WindowTable``), so the table is as
    short at position 200,000 as at 600; ``token_pos`` [T]: the query's
    position in its sequence. → (table [T, :func:`window_columns`], whose
    column 0 is the block that holds the row's **lower bound** ``max(0, pos -
    window + 1)`` and whose next columns the blocks after it, in order;
    positions [T] **in that table**, ``pos - (the first block's first
    position)``). The blocks before the lower bound's are in no column: a
    call never names, let alone fetches, a block that lies wholly outside
    its rows' windows. A query tile walks its first row's table up to its
    last row's position (``rows``: the rows a tile may hold), which the
    sequence holds at once (``WindowTable``'s bound)."""
    first = jnp.maximum(token_pos - (window - 1), 0) // block_size
    columns = first[:, None] + jnp.arange(window_columns(window, block_size, rows))[None, :]
    table = jnp.take_along_axis(rings, columns % rings.shape[1], axis=1)
    return table.astype(jnp.int32), (token_pos - first * block_size).astype(jnp.int32)


def kernel_supported(head_dim, block_size, n_kv_heads=None):
    """Mosaic constraint: a block DMA copies a 2-D ``[block_size,
    Hkv*Dh]`` slice (the pool stores its KV-head and head dims
    flattened), so the lane dim is ``Hkv * head_dim`` — a multiple of 128
    for any head count when head_dim % 128 == 0 — and the sublane dim is
    ``block_size`` (multiple of 8). ANY KV-head count is supported this
    way (round 4's Hkv % 8 restriction came from slicing the un-flattened
    [bs, Hkv, Dh] pool, whose second-minor dim had to tile the 8-sublane
    granule — 1/6/12/20-head pools crashed Mosaic; the flattened layout
    re-measured compiling and matching the XLA reference on a real v5e
    for all four counts, 2026-08-01). How many blocks make a tile is not a
    matter of support: :func:`tile_blocks` gives every shape this
    function admits some ``n``, 1 at the least.

    **A head of 64** is admitted where the pool's row is still whole lane
    tiles, ``n_kv_heads * 64 % 128 == 0``: the kernel then takes **a pair
    of key-value heads a 128-lane slice** (:func:`_paired`), each query
    head widened to the pair's 128 lanes with the other head's half zero,
    so every slice, query and accumulator is as lane-aligned as at a head
    of 128 - the same kernel body, told the true head size for its scale.
    An odd number of 64-wide heads, other head sizes and ALiBi models take
    the XLA gather path (see ``inference/v2/modules/heuristics.py``). A
    model whose heads are neither (Moonlight's 192-wide queries over a
    576-value latent row) is not this kernel's at all: its state kind is
    ``latent`` and its kernel
    ``paged_mla_attention.paged_mla_decode_attention``, with
    ``mla_kernel_supported`` as its own constraint."""
    if block_size % 8:
        return False
    if head_dim == PAIRED_HEAD_DIM:
        return n_kv_heads is not None and n_kv_heads % 2 == 0
    return head_dim % 128 == 0


def smem_table_fits(n_tokens, max_blocks, tiles=False):
    """Do the ``[n_tokens, max_blocks]`` int32 block table, the
    ``[n_tokens]`` positions and the layer index fit the kernel's SMEM
    budget - and beside them, for a call with query tiles (``tiles``), the
    items' rows and lengths, as many each as there are positions?"""
    return (n_tokens * max_blocks + (3 if tiles else 1) * n_tokens + 1) * 4 <= SMEM_TABLE_BYTES


def tile_blocks(block_size, row_bytes, itemsize, max_blocks, slot_bytes=0):
    """``n``: the table blocks of one tile, from the shapes alone. A tile
    wants ``TILE_ROWS`` context rows: the score tile is then whole
    128-lane vregs, a head's two matmuls run once a tile and not once a
    block, and the loop turns once for 2n copies. It is halved until K's
    and V's two slots fit ``TILE_VMEM_BYTES``, and is never more than the
    table has blocks. Block ``j`` lands at rows ``j * block_size`` of a
    slot, which Mosaic wants to be a whole number of the dtype's sublane
    tiles (8 rows of 4 bytes, 16 of 2, 32 of 1): a block that is not gets
    ``n`` = 1, a slot of its own. The cells' shape (16-row bf16 blocks of
    8 x 128) gets 16 blocks = 256 rows = 2 MiB of slots: on the chip
    (``tools/kernel_census.py --paged``; PERF.md, PR 33) 1, 2, 4, 8, 16
    and 32 blocks read 20, 32, 50, 67, 80 and 81 % of the HBM roofline at
    decode contexts of 128-1536, and all of 8-32 the same 27 % where three
    rows in four are padding. ``slot_bytes``: what a slot holds at the
    least, for the caller whose rows are so narrow that ``TILE_ROWS`` of
    them are a small copy; 0, what every call but a selection's passes,
    leaves the tile at ``TILE_ROWS`` rows."""
    if block_size % (32 // itemsize):
        return 1
    n = max(1, max(TILE_ROWS, slot_bytes // row_bytes) // block_size)
    while n > 1 and 4 * n * block_size * row_bytes > TILE_VMEM_BYTES:
        n //= 2
    return min(n, max_blocks)


def live_grid(T, live_rows):
    """→ (``live_rows`` as int32, ``T`` for None; the grid that ends
    there). A call with no live row still runs row 0: an empty grid is not
    asked of Mosaic."""
    live_rows = jnp.asarray(T if live_rows is None else live_rows, jnp.int32)
    return live_rows, (jnp.maximum(live_rows, 1),)


def zeros_past(out, live_rows):
    """``out`` [T, ...] with zeros in the rows from ``live_rows`` on, which
    the grid did not reach and nothing wrote."""
    return jnp.where(jnp.arange(out.shape[0])[:, None, None] < live_rows, out,
                     jnp.zeros((), out.dtype))


def query_tile_rows(n_rows, max_blocks):
    """The height of the query block of a call over ``n_rows`` rows of
    ``max_blocks`` table columns that is given tiles: ``QUERY_TILE`` where
    the rows are whole blocks of it and the items fit SMEM beside the table
    (:func:`smem_table_fits`), else 1, and a height of 1 is the call without
    tiles."""
    whole = n_rows % QUERY_TILE == 0 and smem_table_fits(n_rows, max_blocks, tiles=True)
    return QUERY_TILE if whole else 1


def _runs(token_seq, token_pos, n_seqs, xp):
    """The step of :func:`query_tiles` that says which rows share a walk, over
    rows that are whole blocks of ``QUERY_TILE`` (``xp``: ``jnp`` inside a
    program, ``numpy`` for the host's count): → (``t``, the rows' indices;
    ``run [T]``, the rows of a row's run - a row that joins no row before it
    and the rows that join it, inside one block of ``QUERY_TILE`` rows;
    ``shared [T]``, the rows of runs of two rows or more, which are tiles;
    ``own [T]``, the rows that start an item)."""
    T, tq = token_seq.shape[0], QUERY_TILE
    t = xp.arange(T, dtype=xp.int32)
    joins = xp.concatenate([
        xp.zeros(1, bool),
        (token_seq[1:] < n_seqs) & (token_seq[1:] == token_seq[:-1])
        & (token_pos[1:] == token_pos[:-1] + 1)]) & (t % tq != 0)
    # compares over [rows, tq], which fuse: no sort and no scan
    i = xp.arange(tq, dtype=xp.int32)
    heads = xp.logical_not(joins).reshape(-1, 1, tq)             # [block, 1, j]: j starts a run
    first = xp.max(xp.where(heads & (i[None, :] <= i[:, None]), i, 0), axis=2)
    after = xp.min(xp.where(heads & (i[None, :] > i[:, None]), i, tq), axis=2)
    run = (after - first).reshape(T)
    return t, run, run > 1, xp.logical_not(joins)


def query_tiles(token_seq, token_pos, n_seqs, live_rows, max_blocks):
    """A step's rows as the kernel's grid takes them, from what the batch
    says alone (traced: a program lays its own). A **tile** is two to
    ``QUERY_TILE`` adjacent rows of one sequence (``token_seq`` below
    ``n_seqs``, the padding's) at consecutive positions inside one block of
    ``QUERY_TILE`` rows - what ``RaggedBatchWrapper.insert_sequence`` lays for
    a prompt chunk or a verify program for its ``d + 1`` rows; every other row
    is an item of its own. → ``(item_row [T], item_len [T], n_items,
    shared [T])``: each item's first row and its rows (int32) in the order of
    the rows, how many items cover the rows before ``live_rows``, and which
    rows lie in a tile; the entries from ``n_items`` on are rows of their own.
    None where the rows, under a table of ``max_blocks`` columns, take no
    tiles at all (:func:`query_tile_rows`)."""
    T = token_seq.shape[0]
    if query_tile_rows(T, max_blocks) == 1:
        return None
    t, run, shared, own = _runs(token_seq, token_pos, n_seqs, jnp)
    # item k is the k-th row that starts one: a compare and sums over [rows, rows], fused too,
    # so that laying a step's tiles costs its program microseconds
    hit = own & (jnp.cumsum(own, dtype=jnp.int32) - 1 == t[:, None])   # [item, row]
    item_row = jnp.sum(jnp.where(hit, t, 0), axis=1, dtype=jnp.int32)
    item_len = jnp.sum(jnp.where(hit, run, 0), axis=1, dtype=jnp.int32)
    real = t < jnp.sum(own, dtype=jnp.int32)
    n_items = jnp.sum(own & (t < live_rows), dtype=jnp.int32)
    return (jnp.where(real, item_row, T - 1), jnp.maximum(item_len, 1), n_items, shared)


def chunk_counts(token_seq, token_pos, n_seqs, live_rows):
    """→ ``(n_chunk_rows, n_chunk_tiles)`` of a batch the host packed
    (numpy) for a program that lays tiles (so its rows are whole blocks of
    ``QUERY_TILE``): the rows before ``live_rows`` that :func:`query_tiles`
    lays in tiles, and those tiles - by the step of it that says so
    (:func:`_runs`), without the items' table, which only the kernel's grid
    wants."""
    t, _, shared, own = _runs(token_seq, token_pos, n_seqs, np)
    live = shared & (t < live_rows)
    return int(live.sum()), int((live & own).sum())


def _kernel(*refs, bs, n, max_blocks, groups, n_kv_heads, native, head_dim=None, tq=1,
            window=None):
    """One item of the grid: a row, or with ``tq`` above 1 a row or a tile.
    q_ref [tq, H, Dh] (VMEM), the item's block of rows; kc/vc, the whole
    pool [L, NB, bs, Hkv*Dh], stay in HBM (ANY); tab/pos/layer, and the
    items' first rows and lengths where there are tiles, in SMEM via scalar
    prefetch. With tiles the block's queries come a second time, as a tile
    multiplies them: qt_ref [Hkv, Dh, tq x G], a KV head's queries as columns
    (the block's row, then the head of its group), and a tile's output
    leaves the same way through ot_ref. The module docstring says what a
    tile of the context and a query tile are, what is in flight when, and
    what a slot's stale rows may hold. ``head_dim``: the head size the
    scores are scaled by where it is not the query's width (a pair of narrow
    heads a slice, :func:`_paired`); None: the width. ``window``: None, or
    the positions a row attends to, its own last (:func:`window_tables`'
    table and positions: a row's lower bound lies in its table's first
    block, so the walk is the unwindowed one over a short table and the
    mask gains its lower edge)."""
    if tq == 1:
        (tab_ref, pos_ref, layer_ref, q_ref, kc_ref, vc_ref, o_ref,
         k_buf, v_buf, sems, slot_ref) = refs
    else:
        (tab_ref, pos_ref, layer_ref, row_ref, len_ref, q_ref, qt_ref, kc_ref, vc_ref,
         o_ref, ot_ref, k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref) = refs
    t = pl.program_id(0)
    T = pl.num_programs(0)
    layer = layer_ref[0]
    H, Dh = q_ref.shape[1], q_ref.shape[2]
    rows = n * bs
    scale = 1.0 / np.sqrt(Dh if head_dim is None else head_dim)
    precision = None if native else jax.lax.Precision.HIGHEST

    # An item as the functions below take it. With a row an item (``tq`` 1) it is
    # the row's index and nothing else: its table and its blocks are read where
    # they are used, and the kernel lowers what it lowered before there were
    # tiles, operation for operation (a selection's call, a burst program).
    # With tiles it is (the row whose table is the item's, the blocks of its
    # context, which ends at its last row's position), read once a grid step.
    # Positions and counts are never negative: lax.div / & 1, not the floor
    # division and modulo whose sign handling Mosaic lowers at length.
    def item(k):
        if tq == 1:
            return k
        if window is None:
            end = pos_ref[row_ref[k] + len_ref[k] - 1]
        else:   # positions are each row's own table's: the last row's in the first row's table
            end = pos_ref[row_ref[k]] + len_ref[k] - 1
        return row_ref[k], jnp.minimum(jax.lax.div(end, bs) + 1, max_blocks)

    def table_row(it):
        return it if tq == 1 else it[0]

    def n_blocks(it):
        return jnp.minimum(jax.lax.div(pos_ref[it], bs) + 1, max_blocks) if tq == 1 else it[1]

    def same_block(it, before):
        """Is ``it``'s whole context the one block the item ``before`` it had as
        its own? Then it sits in that item's slot already: nothing to fetch."""
        return ((n_blocks(it) == 1) & (n_blocks(before) == 1)
                & (tab_ref[table_row(it), 0] == tab_ref[table_row(before), 0]))

    def each_block(it, i, slot, act):
        """``act`` on the K and V copies of the blocks of ``it``'s tile ``i``
        that lie inside its context."""
        def one(j, carry):
            blk = tab_ref[table_row(it), i * n + j]
            at = pl.ds(pl.multiple_of(j * bs, bs), bs)
            act(pltpu.make_async_copy(kc_ref.at[layer, blk], k_buf.at[slot, at], sems.at[0, slot]))
            act(pltpu.make_async_copy(vc_ref.at[layer, blk], v_buf.at[slot, at], sems.at[1, slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(n_blocks(it) - i * n, n), one, 0)

    def start(it, i, slot):
        each_block(it, i, slot, lambda copy: copy.start())

    def head(x, h):
        """KV head ``h`` of a slot: 128-aligned lanes of its rows."""
        x = jax.lax.slice(x, (0, h * Dh), (rows, (h + 1) * Dh))
        return x if native else x.astype(jnp.float32)

    @pl.when(t == 0)
    def _():
        slot_ref[0] = 0
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)  # see "stale rows"
        start(item(0), 0, 0)

    this = item(t)
    pos = pos_ref[table_row(this)]  # of the item's first row
    n_tiles = jax.lax.div(n_blocks(this) + n - 1, n)
    slot0 = slot_ref[0]
    reused = (t > 0) & same_block(this, item(jnp.maximum(t - 1, 0)))
    nxt = item(jnp.minimum(t + 1, T - 1))
    nxt_fetches = (t + 1 < T) & jnp.logical_not(same_block(nxt, this))

    def fetched(i):
        """→ the slot that holds this item's tile ``i``, its copies waited
        for; the next tile in order - this item's, or the next item's first -
        is started first and flies during this tile's arithmetic."""
        slot = (slot0 + i) & 1
        last = i + 1 == n_tiles

        @pl.when(jnp.logical_not(last) | nxt_fetches)
        def _():
            start(jax.tree.map(lambda a, b: jnp.where(last, a, b), nxt, this),
                  jnp.where(last, 0, i + 1), 1 - slot)

        @pl.when((i > 0) | jnp.logical_not(reused))
        def _():
            each_block(this, i, slot, lambda copy: copy.wait())
        return slot

    def attend_row(r):
        """Row ``r`` of the query block against its own context."""
        q = q_ref[r]  # [H, Dh], heads grouped [Hkv x G]; everything stays 2-D for Mosaic
        if not native:
            q = q.astype(jnp.float32)

        def tile_step(i, carry):
            m, l, acc = carry  # [H, 1], [H, 1], [H, Dh]
            slot = fetched(i)
            kbuf = k_buf[slot]  # one read; heads are lane slices of it
            vbuf = v_buf[slot]
            s = jnp.concatenate([
                jax.lax.dot_general(jax.lax.slice(q, (h * groups, 0), ((h + 1) * groups, Dh)),
                                    head(kbuf, h), (((1,), (1,)), ((), ())), precision=precision,
                                    preferred_element_type=jnp.float32)
                for h in range(n_kv_heads)], axis=0) * scale  # [H, rows]
            kv_pos = i * rows + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
            seen = kv_pos <= pos
            if window is not None:
                seen &= kv_pos > pos - window
            s = jnp.where(seen, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            p = p.astype(vbuf.dtype if native else jnp.float32)
            pv = jnp.concatenate([
                jax.lax.dot_general(jax.lax.slice(p, (h * groups, 0), ((h + 1) * groups, rows)),
                                    head(vbuf, h), (((1,), (0,)), ((), ())), precision=precision,
                                    preferred_element_type=jnp.float32)
                for h in range(n_kv_heads)], axis=0)  # [H, Dh]
            return m_new, l_new, acc * alpha + pv

        m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((H, 1), jnp.float32)
        a0 = jnp.zeros((H, Dh), jnp.float32)
        _, l, acc = jax.lax.fori_loop(0, n_tiles, tile_step, (m0, l0, a0))
        o_ref[r] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    def attend_tile():
        """The item's rows of the query block against one walk of their
        context. Everything is a query a **column**: a KV head's scores
        ``[rows, tq x G]`` are its slot's rows times qt_ref's columns, a
        column masked at its own row's position, and the running max and sum
        of a column are one lane of a ``[1, tq x G]`` row, the accumulator
        ``[Dh, tq x G]``. The columns of the block's rows that are not the
        item's are masked everywhere and never stored."""
        M = tq * groups
        at = table_row(this) & (tq - 1)
        col = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (1, M), 1), groups)
        mine = (col >= at) & (col < at + len_ref[t])
        q_pos = jnp.where(mine, pos + col - at, -1)  # [1, M]
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def tile_step(i, carry):
            slot = fetched(i)
            kbuf = k_buf[slot]
            vbuf = v_buf[slot]
            kv_pos = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            seen = kv_pos <= q_pos  # [rows, M]
            if window is not None:
                seen &= kv_pos > q_pos - window
            for h in range(n_kv_heads):
                q = qt_ref[h]  # [Dh, M]
                if not native:
                    q = q.astype(jnp.float32)
                s = jax.lax.dot_general(head(kbuf, h), q, (((1,), (0,)), ((), ())),
                                        precision=precision,
                                        preferred_element_type=jnp.float32) * scale
                s = jnp.where(seen, s, NEG_INF)
                m = m_ref[h]
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=0, keepdims=True)
                p = p.astype(vbuf.dtype if native else jnp.float32)
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    head(vbuf, h), p, (((0,), (0,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32)
                m_ref[h] = m_new
            return carry

        jax.lax.fori_loop(0, n_tiles, tile_step, 0)
        for h in range(n_kv_heads):
            out = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(ot_ref.dtype)
            ot_ref[h] = jnp.where(mine, out, ot_ref[h])

    if tq == 1:
        attend_row(0)
    else:
        pl.when(len_ref[t] == 1)(lambda: attend_row(table_row(this) & (tq - 1)))
        pl.when(len_ref[t] > 1)(attend_tile)
    # the next item's first tile is in the other slot, or, reused, in this one
    last_slot = (slot0 + n_tiles - 1) & 1
    slot_ref[0] = jnp.where(nxt_fetches, 1 - last_slot, last_slot)


@functools.partial(jax.jit, static_argnames=("n", "interpret", "head_dim", "window"))
def _paged_call(q, kc, vc, block_tables, token_pos, layer, n, interpret, live_rows=None,
                head_dim=None, tiles=None, window=None):
    """The kernel at ``n`` blocks a tile (``tools/kernel_census.py``
    sweeps it; everything else gets :func:`tile_blocks`'). Jitted so
    that the serving programs of one shape (23 a cell lower the kernel in
    their layer body) share one trace of it. ``live_rows`` (None: every
    row) is where the grid ends; the module docstring says why.
    ``head_dim``: :func:`_kernel`'s. ``tiles``: :func:`query_tiles`' of
    these rows, or None: every row an item, a grid step a row, the query
    block one row high. ``window``: :func:`_kernel`'s; such a call has a
    name of its own in the device trace, ``paged_window_attention``."""
    T, H, Dh = q.shape
    live_rows, grid = live_grid(T, live_rows)
    bs, Hkv = kc.shape[2], kc.shape[3] // Dh
    MB = block_tables.shape[1]
    groups = H // Hkv
    tq = 1 if tiles is None else QUERY_TILE
    prefetch = [block_tables.astype(jnp.int32), token_pos.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1)]
    operands = [q]
    scratch = [
        pltpu.VMEM((2, n * bs, Hkv * Dh), kc.dtype),
        pltpu.VMEM((2, n * bs, Hkv * Dh), vc.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),  # [K | V, slot]
        pltpu.SMEM((1,), jnp.int32),      # the slot of this item's first tile
    ]
    if tq == 1:
        def block(t, *refs):
            return t, 0, 0
        blocks = [pl.BlockSpec((1, H, Dh), block)]
    else:
        item_row, item_len, n_items, shared = tiles
        prefetch += [item_row.astype(jnp.int32), item_len.astype(jnp.int32)]
        grid = (jnp.maximum(n_items.astype(jnp.int32), 1),)
        # a KV head's queries as columns, (row, head of the group) in order: what a tile
        # multiplies a slot by, and how its output comes back (one pass of XLA each way)
        M = tq * groups
        operands.append(q.reshape(T // tq, tq, Hkv, groups, Dh).transpose(2, 0, 4, 1, 3)
                        .reshape(Hkv, T // tq, Dh, M))
        scratch += [
            pltpu.VMEM((Hkv, 1, M), jnp.float32),   # running max,
            pltpu.VMEM((Hkv, 1, M), jnp.float32),   # sum
            pltpu.VMEM((Hkv, Dh, M), jnp.float32),  # and accumulator a column
        ]

        def block(t, tab, pos, layer, item_row, item_len):
            return jax.lax.div(item_row[t], tq), 0, 0

        def columns(t, tab, pos, layer, item_row, item_len):
            return 0, jax.lax.div(item_row[t], tq), 0, 0
        blocks = [pl.BlockSpec((tq, H, Dh), block), pl.BlockSpec((Hkv, None, Dh, M), columns)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # tables, positions, layer; the items
        grid=grid,
        in_specs=blocks + [pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=blocks if tq > 1 else blocks[0],
        scratch_shapes=scratch,
    )
    # bf16 x bf16 products are exact in float32, so a 2-byte pool's rows go to
    # the MXU as they lie; a float32 pool (or query) keeps the six-pass product
    native = q.dtype == kc.dtype == vc.dtype and kc.dtype.itemsize == 2
    kernel = functools.partial(_kernel, bs=bs, n=n, max_blocks=MB, groups=groups,
                               n_kv_heads=Hkv, native=native, head_dim=head_dim, tq=tq,
                               **({} if window is None else {"window": window}))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, q.dtype) for x in operands] if tq > 1
        else jax.ShapeDtypeStruct((T, H, Dh), q.dtype),
        # items in order on one core: an item starts the next one's first tile
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention" if window is None else "paged_window_attention",
    )(*prefetch, *operands, kc, vc)
    if tq > 1:
        out, columns = out
        columns = (columns.reshape(Hkv, T // tq, Dh, tq, groups).transpose(1, 3, 0, 4, 2)
                   .reshape(T, H, Dh))
        out = jnp.where(shared[:, None, None], columns, out)
    return zeros_past(out, live_rows)


def _paired(q, n_kv_heads):
    """Queries of ``PAIRED_HEAD_DIM``-wide heads [T, H, 64], grouped
    ``[Hkv x G]`` → (the queries the kernel takes [T, H, 128], ``pick`` that
    brings its output back to [T, H, 64]). The pool's row holds key-value
    heads ``2p`` and ``2p + 1`` side by side in lanes ``128 p .. 128 p +
    127``, so the kernel is given **``Hkv / 2`` heads of 128**, each with
    the ``2 G`` query heads of its pair: a query of the even head lies in
    the slice's low half and a query of the odd head in its high half, the
    other half zero, so ``q . k`` over 128 lanes is the head's own 64
    products and 64 zeros. The second product gives each query head both
    heads' values side by side, of which ``pick`` keeps its own half. The
    kernel is bound by its fetches (one pass over the pool, as at a head
    of 128); the wider products ride the MXU's 128 lanes, which a 64-wide
    product would leave half empty."""
    T, H, d = q.shape
    odd = ((jnp.arange(H) // (H // n_kv_heads)) % 2 == 1)[None, :, None]
    zero = jnp.zeros_like(q)
    wide = jnp.concatenate([jnp.where(odd, zero, q), jnp.where(odd, q, zero)], axis=-1)
    return wide, lambda out: jnp.where(odd, out[..., d:], out[..., :d])


def paged_decode_attention(q, kc, vc, block_tables, token_pos, layer, live_rows=None, tiles=None,
                           interpret=None, selected=False, window=None):
    """Pallas path of :func:`xla_paged_attention` (same contract on the
    rows before ``live_rows``, zeros from there on; None: every row).
    ``tiles``: :func:`query_tiles`' of the batch these rows are, from the
    caller that knows that a row's table is its sequence's (the module
    docstring, "A query tile"); None - a selection's call, a program of one
    row a sequence - walks every row's context for that row alone.
    ``selected``: the table is a selection (:func:`selected_tables`) over
    a pool of one KV head a pool layer, whose 256 rows of 256 bytes are
    64 KB a slot: a turn's fixed cost (2n copies started and waited for,
    two 16-row matmuls, the masks) then outweighs its copies — 0.45 us a
    tile of 4 64-row blocks, 31 % of the HBM roofline, 43 % at 8 blocks
    and 57 % at 32 (chip, PR 34) — so such a call's slot holds
    ``SELECTED_SLOT_BYTES`` at the least, the 512 KB that 256 rows of 8
    heads are. Every other call's tile is what it was. A head of 64 goes
    to the same kernel a pair of key-value heads a slice (:func:`_paired`);
    a head of 128 takes the code it took. ``window``: None - today's
    program - or the positions a row attends to, its own last, with
    :func:`window_tables`' table and positions for ``block_tables`` and
    ``token_pos``: the walk starts at the block that holds a row's (a query
    tile's first row's) lower bound, because no block before it is in the
    table, and the mask gains its lower edge."""
    if interpret is None:
        from deepspeed_tpu.ops.pallas import default_interpret
        interpret = default_interpret()
    T, H, Dh = q.shape
    bs, Hkv = kc.shape[2], kc.shape[3] // Dh
    MB = block_tables.shape[1]
    if selected:
        tiles = None  # a row's table is its own there: nothing to share
    if not interpret:
        if not kernel_supported(Dh, bs, Hkv):
            raise ValueError(
                f"paged decode kernel needs head_dim % 128 == 0 (or 64, an even number of "
                f"key-value heads) and block_size % 8 == 0, got head_dim={Dh}, "
                f"n_kv_heads={Hkv}, block_size={bs}")
        if not smem_table_fits(T, MB, tiles=tiles is not None):
            raise ValueError(
                f"paged decode block table [{T}, {MB}] overflows the kernel's "
                f"{SMEM_TABLE_BYTES >> 10} KB SMEM budget — shrink max_ragged_batch_size / "
                f"max_context, or raise kv_block_size")
    n = tile_blocks(bs, kc.shape[3] * kc.dtype.itemsize, kc.dtype.itemsize, MB,
                    SELECTED_SLOT_BYTES if selected else 0)
    windowed = {} if window is None else {"window": window}   # no window: the call as it was
    if Dh == PAIRED_HEAD_DIM and Hkv % 2 == 0:
        wide, pick = _paired(q, Hkv)
        return pick(_paged_call(wide, kc, vc, block_tables, token_pos, layer, n, interpret,
                                live_rows, head_dim=Dh, tiles=tiles, **windowed))
    return _paged_call(q, kc, vc, block_tables, token_pos, layer, n, interpret, live_rows,
                       tiles=tiles, **windowed)
