"""The paged latent decode kernel (``ops/pallas/paged_mla_attention``) in
interpret mode against its gather twin, and its fetch rule against the
copies it starts.

Every pool block that no table names is NaN, so a fetch outside the
tables, or a stale row of a slot that reaches a product, shows as a
result that is not finite. Interpret mode runs a copy where it is started
and waits for nothing: it proves the rule and the arithmetic, not the
overlap, which is the chip's (``tools/kernel_census.py --mla``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import paged_mla_attention as pm

BS, RANK, LANES, NB = 32, 128, 128, 40


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def rows_of(spec, max_blocks, rng):
    """spec: a list of ("seq", [positions]) — rows of one sequence, on one
    table — and ("pad", count) → (tables [T, MB], positions [T]); a
    sequence's table holds the blocks its last position needs, the rest of
    a row the null block, as the engine's does."""
    free = iter(rng.permutation(np.arange(1, NB)))
    tabs, pos = [], []
    for kind, what in spec:
        if kind == "pad":
            tabs += [np.zeros(max_blocks, np.int32)] * what
            pos += [0] * what
            continue
        row = np.zeros(max_blocks, np.int32)
        need = max(what) // BS + 1
        row[:need] = [next(free) for _ in range(need)]
        tabs += [row] * len(what)
        pos += list(what)
    return np.stack(tabs), np.asarray(pos, np.int32)


def pools(tabs, dtype, rng, layers=2):
    """Two pools whose blocks outside ``tabs`` are NaN."""
    c = rng.standard_normal((layers, NB, BS, RANK)).astype(np.float32)
    r = rng.standard_normal((layers, NB, BS, LANES)).astype(np.float32)
    unnamed = np.setdiff1d(np.arange(NB), np.unique(tabs))
    assert len(unnamed) > 0
    c[:, unnamed], r[:, unnamed] = np.nan, np.nan
    return jnp.asarray(c, dtype), jnp.asarray(r, dtype)


# contexts that end on a block's first row (32), in its middle (45), on its last row (63), on a
# unit's edge (16 rows a unit: 39 / 40 / 47 / 48), on a tile's edge (2 blocks a tile: 63 / 64 /
# 127 / 128), and a first row of all; a short context after a long one in the same slot
SEQS = [[0], [32], [45], [63], [39], [40], [47], [48], [64], [127], [128], [3], [150], [7]]
SCENES = {
    "edges": ([("seq", p) for p in SEQS], 5),
    # runs of padding before, between and after live rows
    "padding": ([("pad", 3), ("seq", [70]), ("seq", [5]), ("pad", 4), ("seq", [33]), ("pad", 2)], 5),
    # a chunk's consecutive tokens across a unit's, a block's and a tile's edge: reuse, then one
    # block, then one tile more; then decode rows and padding
    "chunk": ([("seq", [99]), ("seq", list(range(20, 70))), ("seq", [12]), ("pad", 3)], 5),
    # a table narrower than a tile
    "narrow": ([("seq", [40]), ("seq", list(range(28, 36))), ("pad", 2), ("seq", [63])], 2),
}
SHAPES = [(2, 16), (2, 64), (1, 32), (3, 32), (4, 128)]   # (n, unit)


# 64 heads ride the same body: two tile shapes of it
CASES = ([(16, jnp.bfloat16, 2e-2, *shape) for shape in SHAPES]
         + [(64, jnp.bfloat16, 2e-2, *shape) for shape in SHAPES[:2]]
         + [(16, jnp.float32, 1e-5, *shape) for shape in SHAPES[:3]])


@pytest.mark.parametrize("scene", sorted(SCENES))
@pytest.mark.parametrize("heads,dtype,tol,n,unit", CASES)
def test_the_kernel_matches_the_gather(scene, heads, dtype, tol, n, unit):
    rng = np.random.default_rng(5)
    spec, max_blocks = SCENES[scene]
    tabs, pos = rows_of(spec, max_blocks, rng)
    c, r = pools(tabs, dtype, rng)
    q = jnp.asarray(rng.standard_normal((len(pos), heads, RANK + LANES)) * 0.1, dtype)
    got = pm._mla_call(q, c, r, jnp.asarray(tabs), jnp.asarray(pos), 1, n, unit, True)
    want = pm.xla_paged_mla_attention(q, c, r, jnp.asarray(tabs), jnp.asarray(pos), jnp.int32(1))
    assert got.shape == (len(pos), heads, RANK) and got.dtype == dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert rel_err(got, want) < tol
    # each token alone: what a slot held before it is nothing to it
    worst = max(rel_err(g, w) for g, w in zip(np.asarray(got, np.float32),
                                              np.asarray(want, np.float32)))
    assert worst < 2 * tol


@pytest.mark.parametrize("ahead,reuse", [(False, False), (True, False), (False, True)])
def test_the_parts_the_census_switches_off_change_no_result(ahead, reuse):
    rng = np.random.default_rng(6)
    tabs, pos = rows_of(*SCENES["chunk"], rng)
    c, r = pools(tabs, jnp.float32, rng)
    q = jnp.asarray(rng.standard_normal((len(pos), 4, RANK + LANES)) * 0.1, jnp.float32)
    args = (q, c, r, jnp.asarray(tabs), jnp.asarray(pos), 0, 2, 16, True)
    assert rel_err(pm._mla_call(*args, ahead=ahead, reuse=reuse), pm._mla_call(*args)) < 1e-6


@pytest.mark.parametrize("block_size,itemsize,columns,heads,want", [
    (256, 2, 6, 64, (4, 256)), (256, 2, 18, 16, (8, 256)), (256, 2, 18, 128, (2, 256)),
    (256, 2, 2, 16, (2, 256)), (64, 2, 64, 16, (32, 256)), (64, 4, 64, 16, (16, 256)),
    (16, 4, 6, 4, (6, 96)), (24, 2, 6, 16, (1, 24))])
def test_tile_and_unit_follow_from_the_shapes(block_size, itemsize, columns, heads, want):
    """Moonlight's shape gets 8 blocks of 256 rows and LongCat's 4 (its 64
    heads' score tile), a table of two columns 2, a block that is no whole
    number of sublane tiles a slot of its own."""
    got = pm.mla_tile(block_size, 640 * itemsize, itemsize, columns, heads)
    assert got == want
    n, unit = got
    assert (n * block_size) % unit == 0 and n * block_size // unit <= pm.MLA_WIDTHS
    assert n <= columns and 2 * n * block_size * 640 * itemsize <= pm.MLA_VMEM_BYTES
    with pytest.raises(ValueError, match="widths"):
        pm._mla_call(jnp.zeros((1, 2, 256)), jnp.zeros((1, 2, 16, 128)), jnp.zeros((1, 2, 16, 128)),
                     jnp.zeros((1, 12), jnp.int32), jnp.zeros(1, jnp.int32), 0, 9, 16, True)


def started_copies(monkeypatch, tabs, pos, n, live_rows=None):
    """The copies the kernel starts for these rows, counted where each is
    started (a callback under the very ``start``)."""
    counted = []
    make = pm.pltpu.make_async_copy

    class Counting:
        def __init__(self, src, dst, sem):
            self.copy = make(src, dst, sem)

        def start(self):
            jax.debug.callback(lambda: counted.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(pm.pltpu, "make_async_copy", Counting)
    rng = np.random.default_rng(7)
    c, r = pools(tabs, jnp.float32, rng, layers=1)
    q = jnp.asarray(rng.standard_normal((len(pos), 2, RANK + LANES)) * 0.1, jnp.float32)
    pm._mla_call.clear_cache()
    got = pm._mla_call(q, c, r, jnp.asarray(tabs), jnp.asarray(pos), 0, n, n * BS, True,
                       live_rows=live_rows)
    jax.block_until_ready(got)
    jax.effects_barrier()
    pm._mla_call.clear_cache()      # no later call finds the counting trace
    return len(counted)


@pytest.mark.parametrize("scene", sorted(SCENES))
@pytest.mark.parametrize("n", [1, 2, 4])
def test_the_fetch_rule_is_the_copies_the_kernel_starts(monkeypatch, scene, n):
    rng = np.random.default_rng(8)
    spec, max_blocks = SCENES[scene]
    tabs, pos = rows_of(spec, max_blocks, rng)
    named, fetched = (np.asarray(x) for x in pm.fetch_plan(jnp.asarray(tabs), jnp.asarray(pos), BS, n))
    assert started_copies(monkeypatch, tabs, pos, n) == 2 * fetched.sum()    # of c and of r
    np.testing.assert_array_equal(named, np.minimum(pos // BS + 1, max_blocks))
    totals = pm.fetch_counts(jnp.asarray(tabs), jnp.asarray(pos), BS, n)
    assert tuple(int(x) for x in totals) == (named.sum(), fetched.sum())
    assert (fetched <= named).all()
    assert (fetched.sum() < named.sum()) == (scene != "edges")    # no two of its rows share a block


@pytest.mark.parametrize("scene,live_rows", [("padding", 10), ("padding", 0), ("chunk", 52),
                                             ("chunk", 30), ("narrow", 0)])
def test_the_fetch_rule_knows_where_the_grid_ends(monkeypatch, scene, live_rows):
    """Told the live rows, the kernel starts no copy for a row from there
    on (but for row 0, which an empty call still runs), and the rule says
    the same; padding rows before that are rows like any other."""
    rng = np.random.default_rng(10)
    spec, max_blocks = SCENES[scene]
    tabs, pos = rows_of(spec, max_blocks, rng)
    tabs_d, pos_d = jnp.asarray(tabs), jnp.asarray(pos)
    named, fetched = (np.asarray(x) for x in pm.fetch_plan(tabs_d, pos_d, BS, 2, jnp.int32(live_rows)))
    whole = np.asarray(pm.fetch_plan(tabs_d, pos_d, BS, 2)[1])
    assert started_copies(monkeypatch, tabs, pos, 2, jnp.int32(live_rows)) == 2 * fetched.sum()
    reached = max(live_rows, 1)
    np.testing.assert_array_equal(fetched[:reached], whole[:reached])
    assert fetched[reached:].sum() == 0 and named.sum() == np.minimum(pos // BS + 1, max_blocks).sum()


def test_what_the_rule_saves_and_what_it_does_not():
    """By hand: a run of padding rows fetches the null block once; a
    chunk's token fetches what its predecessor lacked; a decode row whose
    table differs fetches its tile whole; a token after one of two tiles
    fetches whole."""
    rng = np.random.default_rng(9)
    spec = [("pad", 3), ("seq", list(range(30, 34))), ("seq", [100]), ("seq", [10]), ("pad", 2)]
    tabs, pos = rows_of(spec, 5, rng)
    named, fetched = (np.asarray(x).tolist() for x in pm.fetch_plan(
        jnp.asarray(tabs), jnp.asarray(pos), BS, 2))
    #                  pad       30 31 32 33   100  10  pad
    assert named ==   [1, 1, 1,  1, 1, 2, 2,   4,   1,  1, 1]
    assert fetched == [1, 0, 0,  1, 0, 1, 0,   4,   1,  1, 0]
