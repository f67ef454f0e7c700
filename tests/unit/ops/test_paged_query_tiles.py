"""A query tile (``ops/pallas/paged_attention``, "A query tile"): adjacent
rows of one sequence share one walk of its context. The tile path against
``xla_paged_attention`` and against the row path (the same call with no
tiles) over the batches an engine packs, in interpret mode; the function
that lays the tiles, on the host and inside a program; the two counts a
``put``'s step record takes from it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import paged_attention as pa

BS, MB, LAYERS = 16, 48, 2           # a tile of the context: 16 blocks = 256 positions
TQ = pa.QUERY_TILE
# float32 pools keep Precision.HIGHEST; a bf16 pool's kernel rounds the probabilities and its
# output to bf16, against the float32 reference on the same rounded inputs
# (test_paged_attention.TOL); the two paths against each other differ by an output's rounding
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}
# (query heads, key-value heads, head size): a head of 128, and a head of 64 a pair a slice
HEADS = {"head128": (8, 2, 128), "head64-paired": (8, 4, 64)}
# as many key-value heads as query heads (a stack whose every head has keys of its own:
# ``OuroKind``): a key-value row is fetched for one query head, a product a head is one row
GROUP_OF_ONE = {"head128-group1": (4, 4, 128)}

# the rows of a batch in order: (rows, context before them) a sequence; None: a padding row
BATCHES = {
    # decode rows, then two chunks side by side, the second a new prompt
    "decode_rows_then_two_chunks": (4 * TQ, [(1, 37), (1, 5), (1, 300), (45, 19), (40, 0)]),
    # a chunk that starts inside a block of the context, one that walks three tiles of it
    "mid_block_and_across_context_tiles": (4 * TQ, [(TQ + 7, BS + 5), (2 * TQ, 2 * 256 + 9)]),
    "shorter_and_longer_than_a_tile": (4 * TQ, [(2, 70), (1, 9), (2 * TQ + 11, 130), (3, 300)]),
    "padding_among_live_rows": (2 * TQ, [(1, 40), None, (9, 270), None, None, (12, 3), (1, 0)]),
    # a verify program's d + 1 rows a sequence, a padding sequence's among them
    "verify_rows_a_sequence": (2 * TQ, [(5, 100), (5, 17), None, None, None, None, None, (5, 255),
                                        (5, 256), (5, 0), (5, 511)]),
    "all_rows_one_chunk": (TQ, [(TQ, 0)]),
}


def _batch(name, heads, dtype, seed=0):
    """→ (q, kc, vc, tables [S + 1, MB], token_seq, token_pos, live rows)"""
    T, layout = BATCHES[name]
    H, Hkv, Dh = {**HEADS, **GROUP_OF_ONE}[heads]
    rng = np.random.default_rng(seed)
    seqs = [r for r in layout if r is not None]
    n_seqs = len(seqs)
    NB = 1 + sum(-(-(before + rows) // BS) for rows, before in seqs)
    kc = rng.standard_normal((LAYERS, NB, BS, Hkv * Dh)).astype(np.float32)
    vc = rng.standard_normal((LAYERS, NB, BS, Hkv * Dh)).astype(np.float32)
    q = rng.standard_normal((T, H, Dh)).astype(np.float32)
    tables = np.zeros((n_seqs + 1, MB), np.int32)
    seq, pos = np.full(T, n_seqs, np.int32), np.zeros(T, np.int32)
    blocks = iter(rng.permutation(np.arange(1, NB)))
    t = s = 0
    for run in layout:
        if run is None:
            t += 1
            continue
        rows, before = run
        need = -(-(before + rows) // BS)
        tables[s, :need] = [next(blocks) for _ in range(need)]
        seq[t:t + rows], pos[t:t + rows] = s, before + np.arange(rows)
        t, s = t + rows, s + 1
    cast = lambda a: jnp.asarray(a, dtype)
    return cast(q), cast(kc), cast(vc), jnp.asarray(tables), jnp.asarray(seq), jnp.asarray(pos), t


def _three_ways(q, kc, vc, tables, seq, pos, live, layer=jnp.int32(1)):
    """→ (the tile path, the row path, the float32 gather) on the live rows,
    and the tile path's rows past them."""
    tab = tables[seq]
    tiles = pa.query_tiles(seq, pos, tables.shape[0] - 1, live, MB)
    tiled = jax.jit(lambda *a: pa.paged_decode_attention(*a, interpret=True))(
        q, kc, vc, tab, pos, layer, live, tiles)
    rows = pa.paged_decode_attention(q, kc, vc, tab, pos, layer, live, interpret=True)
    f32 = lambda a: a.astype(jnp.float32)
    want = pa.xla_paged_attention(f32(q), f32(kc), f32(vc), tab, pos, layer)
    assert tiled.dtype == q.dtype
    out = [np.asarray(a, np.float32) for a in (tiled, rows, want)]
    return [a[:live] for a in out], out[0][live:]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("name", list(BATCHES))
def test_tiles_match_the_gather_and_the_row_path(name, heads, dtype):
    case = _batch(name, heads, dtype, seed=len(name))
    (tiled, rows, want), past = _three_ways(*case)
    assert pa.chunk_counts(np.asarray(case[4]), np.asarray(case[5]), case[3].shape[0] - 1,
                           case[6])[1] > 0, "the batch lays no tile: the test compares nothing"
    np.testing.assert_allclose(tiled, want, rtol=TOL[dtype], atol=TOL[dtype])
    np.testing.assert_allclose(tiled, rows, rtol=TOL[dtype], atol=TOL[dtype] / 2)
    assert not past.any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_query_group_of_one_rows_alone_and_in_tiles(dtype):
    """``H = Hkv`` (beside the groups of 4 and 2 above): decode rows, padding
    among them and two chunks, a row a grid step and in query tiles, against
    the gather - the slices of ``groups`` query rows a key-value head are
    slices of one."""
    args = _batch("padding_among_live_rows", "head128-group1", dtype)
    assert args[0].shape[1] * 128 == args[1].shape[-1]              # H = Hkv
    (tiled, rows, want), dead = _three_ways(*args)
    np.testing.assert_allclose(tiled, want, rtol=TOL[dtype], atol=TOL[dtype])
    np.testing.assert_allclose(rows, want, rtol=TOL[dtype], atol=TOL[dtype])
    assert not np.isnan(dead).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", list(HEADS))
def test_a_chunk_cut_by_live_rows(heads, dtype):
    """The grid ends at the last live tile: a chunk whose later rows lie past
    ``live_rows`` is attended up to there, and the rows from there on are
    zeros, whatever their table says."""
    q, kc, vc, tables, seq, pos, _ = _batch("decode_rows_then_two_chunks", heads, dtype, seed=5)
    live = 3 + 45 + 13   # inside the second chunk, inside a block of TQ rows
    (tiled, rows, want), past = _three_ways(q, kc, vc, tables, seq, pos, live)
    np.testing.assert_allclose(tiled, want, rtol=TOL[dtype], atol=TOL[dtype])
    np.testing.assert_allclose(tiled, rows, rtol=TOL[dtype], atol=TOL[dtype] / 2)
    assert past.shape[0] == q.shape[0] - live and not past.any()


def test_float32_tiles_are_the_row_paths_arithmetic():
    """The same context tiles in the same order, a row's running max, sum and
    accumulator its own: in float32 a tile's rows are the row path's to the
    last bit but the products' order of summation."""
    (tiled, rows, _), _ = _three_ways(*_batch("mid_block_and_across_context_tiles", "head128",
                                              jnp.float32, seed=2))
    np.testing.assert_allclose(tiled, rows, rtol=0, atol=1e-6)


@pytest.mark.parametrize("heads", list(HEADS))
def test_a_selection_takes_no_tiles_and_gives_what_it_gave(heads):
    """``selected=True``: a row's table is its own, so the tiles a caller
    passes are not taken, the program is the one without them, and the
    output is that program's bit for bit."""
    q, kc, vc, tables, seq, pos, live = _batch("decode_rows_then_two_chunks", heads, jnp.bfloat16)
    tab, layer = tables[seq], jnp.int32(0)
    tiles = pa.query_tiles(seq, pos, tables.shape[0] - 1, live, MB)
    plain = lambda *a: pa.paged_decode_attention(*a, interpret=True, selected=True)
    given = lambda *a: pa.paged_decode_attention(*a, tiles, interpret=True, selected=True)
    args = (q, kc, vc, tab, pos, layer, live)
    assert np.array_equal(np.asarray(given(*args), np.float32), np.asarray(plain(*args), np.float32))
    assert jax.jit(given).lower(*args).as_text() == jax.jit(plain).lower(*args).as_text()
    # and a call with tiles is another program: the items ride beside the tables
    assert jax.jit(lambda *a: pa.paged_decode_attention(*a, tiles, interpret=True)).lower(
        *args).as_text() != jax.jit(plain).lower(*args).as_text()


# A call that is given no tiles - a selection's (``minicpm-sala-longdoc``: 512 tokens x 2 heads,
# 64 selected blocks a row), a burst program's at a head of 128 and of 64 - traces to the
# program PR 41's module traced, operation for operation: sha256 of ``jax.make_jaxpr``'s text,
# which carries no source locations, taken from that module (``git show
# ca8f96a:deepspeed_tpu/ops/pallas/paged_attention.py``) and from this one in PR 42, when the
# Mosaic modules of both, printed without locations, were the same text as well (PERF.md, PR 42).
# (query, pool, table columns, selected, the parent's hash)
ROW_A_STEP = {
    "selection": ((1024, 16, 128), (1, 8800, 64, 128), 64, True,
                  "6fa93ceeb6b9397dd124585bea66d4a884c1dc49dd03293df4e033024c937f8b"),
    "burst-head128": ((64, 32, 128), (4, 2560, 16, 1024), 360, False,
                      "c4bedb7da826a96381a2a2bd079548a3471002a63f268b1e6feb19c356380db9"),
    "burst-head64": ((64, 32, 64), (2, 8705, 64, 512), 136, False,
                     "7bd65fa05008081cacafe63e740fa9839c18c0859d0faa84c730a194a05f2521"),
}


@pytest.mark.parametrize("name", list(ROW_A_STEP))
def test_a_call_without_tiles_traces_what_it_traced_before_there_were_tiles(name):
    """The row path is the parent's program and not one like it: the scalar
    work of a grid step weighs most where a step is short (a selection's 64 KB
    slots), and ``sparse_attn_roofline.longdoc`` reads these calls. A change
    that is meant to touch the row a grid step - or another jax, which may
    print a jaxpr differently - takes a new hash, and a chip run of the
    selection's class (``tools/kernel_census.py --chunk``) with it."""
    import hashlib
    q, pool, MB, selected, parent = ROW_A_STEP[name]
    sds = jax.ShapeDtypeStruct
    args = (sds(q, jnp.bfloat16), sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds((q[0], MB), jnp.int32), sds((q[0],), jnp.int32), sds((), jnp.int32),
            sds((), jnp.int32))
    traced = jax.make_jaxpr(lambda *a: pa.paged_decode_attention(
        *a, interpret=False, selected=selected))(*args)
    assert hashlib.sha256(str(traced).encode()).hexdigest() == parent


def test_rows_that_are_no_whole_blocks_take_no_tiles():
    """A call whose rows are not whole blocks of ``QUERY_TILE``, or whose
    items do not fit SMEM beside its table, is the call without tiles:
    ``query_tiles`` says None and the kernel walks a row a grid step. Only
    a call with tiles is charged for them."""
    seq, pos = jnp.zeros(TQ + 8, jnp.int32), jnp.arange(TQ + 8, dtype=jnp.int32)
    assert pa.query_tile_rows(TQ + 8, MB) == 1 and pa.query_tile_rows(4 * TQ, MB) == TQ
    assert pa.query_tiles(seq, pos, 1, TQ + 8, MB) is None
    # 512 rows of 382 table columns: 2 KB under the budget, and the items are 4 KB more
    assert pa.smem_table_fits(512, 382) and not pa.smem_table_fits(512, 382, tiles=True)
    assert pa.query_tile_rows(512, 382) == 1 and pa.query_tile_rows(512, 380) == TQ
    seq, pos = jnp.zeros(512, jnp.int32), jnp.arange(512, dtype=jnp.int32)
    assert pa.query_tiles(seq, pos, 1, 512, 382) is None


@pytest.mark.parametrize("name", list(BATCHES))
def test_host_and_device_lay_the_same_tiles(name):
    """The items a program lays (``query_tiles``, traced) cover the live rows
    once each, in order, a tile never across a block of ``QUERY_TILE`` rows,
    two sequences or a gap in the positions; and the host's count of the
    same batch (``chunk_counts``, numpy) is what they hold: one function
    (``_runs``) says on both sides which rows share a walk."""
    _, _, _, tables, seq, pos, live = _batch(name, "head128", jnp.float32)
    n_seqs = tables.shape[0] - 1
    device = jax.jit(lambda s, p, n: pa.query_tiles(s, p, n_seqs, n, MB))(seq, pos, live)
    item_row, item_len, n_items, shared = (np.asarray(a) for a in device)
    seq, pos = np.asarray(seq), np.asarray(pos)
    assert np.array_equal(shared, pa._runs(seq, pos, n_seqs, np)[2])
    covered = np.concatenate([np.arange(r, r + n) for r, n in
                              zip(item_row[:n_items], item_len[:n_items])])
    assert np.array_equal(covered, np.arange(live))
    for r, n in zip(item_row[:n_items], item_len[:n_items]):
        if n > 1:
            assert n <= TQ and r // TQ == (r + n - 1) // TQ
            assert (seq[r:r + n] == seq[r]).all() and seq[r] < n_seqs
            assert np.array_equal(pos[r:r + n], pos[r] + np.arange(n))
    in_a_tile = np.zeros(len(seq), bool)
    for r, n in zip(item_row[:n_items], item_len[:n_items]):
        in_a_tile[r:r + n] = n > 1
    assert np.array_equal(shared[:live], in_a_tile[:live])
    rows, tiles = pa.chunk_counts(seq, pos, n_seqs, live)
    assert rows == in_a_tile[:live].sum() and tiles == (item_len[:n_items] > 1).sum()


def test_sharded_wrapper_passes_the_tiles(monkeypatch):
    """``pallas_paged_sharded``: the tiles ride replicated beside the tables,
    a shard attends its own heads through them."""
    from deepspeed_tpu.inference.v2.modules.heuristics import instantiate_attn
    from deepspeed_tpu.parallel.topology import make_mesh_topology
    monkeypatch.setenv("DS_PALLAS", "1")
    mesh = make_mesh_topology(data=1, tensor=2, devices=jax.devices()[:2])
    q, kc, vc, tables, seq, pos, live = _batch("decode_rows_then_two_chunks", "head128",
                                               jnp.float32, seed=7)
    tab, layer = tables[seq], jnp.int32(1)
    tiles = pa.query_tiles(seq, pos, tables.shape[0] - 1, live, MB)
    name, fn = instantiate_attn(mesh, 128, BS, q.shape, kc.shape, None, max_blocks=MB,
                                override="pallas_paged_sharded")
    assert name == "pallas_paged_sharded"
    live = jnp.int32(live)
    got = jax.jit(fn)(q, kc, vc, tab, pos, layer, live, tiles)
    rows = jax.jit(fn)(q, kc, vc, tab, pos, layer, live, None)
    want = pa.xla_paged_attention(q, kc, vc, tab, pos, layer)
    live = int(live)
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(rows), rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[live:].any()
