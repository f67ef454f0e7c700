"""The paged attention kernel under a **window** (interpret mode on the CPU)
against a masked softmax over the whole sequence: the table is the window
pool's ring cut to the blocks a row's window touches
(``paged_attention.window_tables``), the walk starts at the block that holds
the row's lower bound, and the mask has a lower edge. Windows that start
mid-block, contexts shorter than the window, query tiles whose rows cross the
window's edge, query groups of 4, 6 and 8; ``window=None`` is today's call."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas.paged_attention import (paged_decode_attention, query_tiles,
                                                      window_columns, window_tables,
                                                      xla_paged_attention)


def _sequence(S, Hkv, Dh, bs, ring, seed, dtype=jnp.float32):
    """One sequence of ``S`` positions in a window pool: block ``b`` of the
    sequence lies in ring column ``b % ring``; a block that has left the ring
    is **overwritten** by the one that took its column (so a call that read a
    block outside the window would read another position's rows). → (keys,
    values [S, Hkv, Dh], the pools [1, NB, bs, Hkv * Dh] as they stand after
    position ``S - 1`` was written, the ring [ring])."""
    rng = np.random.RandomState(seed)
    k = rng.randn(S, Hkv, Dh).astype(np.float32)
    v = rng.randn(S, Hkv, Dh).astype(np.float32)
    perm = rng.permutation(ring) + 1                      # column c's physical block; 0 is null
    kc = np.full((1, ring + 1, bs, Hkv * Dh), 7.0, np.float32)
    vc = np.full((1, ring + 1, bs, Hkv * Dh), 7.0, np.float32)
    for p in range(S):
        kc[0, perm[(p // bs) % ring], p % bs] = k[p].reshape(-1)
        vc[0, perm[(p // bs) % ring], p % bs] = v[p].reshape(-1)
    return k, v, jnp.asarray(kc, dtype), jnp.asarray(vc, dtype), jnp.asarray(perm, jnp.int32)


def _masked_softmax(q, k, v, pos, window):
    """q [T, H, Dh] at positions ``pos`` over keys and values [S, Hkv, Dh]."""
    T, H, Dh = q.shape
    G = H // k.shape[1]
    kk, vv = np.repeat(k, G, axis=1), np.repeat(v, G, axis=1)
    s = np.einsum("thd,shd->ths", q, kk) / np.sqrt(Dh)
    j = np.arange(k.shape[0])[None, :]
    seen = (j <= pos[:, None]) & (j > pos[:, None] - window)
    s = np.where(seen[:, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("ths,shd->thd", p / p.sum(-1, keepdims=True), vv)


@pytest.mark.parametrize("G,bs,window", [(4, 8, 24), (6, 16, 40), (8, 16, 40), (6, 8, 24)])
def test_decode_rows_read_their_window_alone(G, bs, window):
    """One row a sequence position, the sequence far longer than the window:
    every block before the lower bound's has been overwritten in the ring."""
    Hkv, Dh = 2, 128
    ring = window_columns(window, bs) + 1
    S = 5 * window + 3
    k, v, kc, vc, perm = _sequence(S, Hkv, Dh, bs, ring, seed=G + bs)
    rng = np.random.RandomState(1)
    # the last position, one whose window starts mid-block, one at a block's first row
    pos = np.asarray([S - 1, S - 1 - bs // 2, (S - 1) // bs * bs])
    pos = pos[pos > S - 1 - bs]     # only positions whose window the ring still holds whole
    q = rng.randn(len(pos), Hkv * G, Dh).astype(np.float32)
    tab, rel = window_tables(jnp.tile(perm[None], (len(pos), 1)), jnp.asarray(pos, jnp.int32),
                             window, bs)
    assert tab.shape[1] == window_columns(window, bs)
    want = _masked_softmax(q, k, v, pos, window)
    got = paged_decode_attention(jnp.asarray(q), kc, vc, tab, rel, jnp.int32(0), interpret=True,
                                 window=window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    ref = xla_paged_attention(jnp.asarray(q), kc, vc, tab, rel, jnp.int32(0), window=window)
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-5, atol=2e-5)


def test_a_context_shorter_than_the_window_is_the_causal_call():
    Hkv, G, Dh, bs, window = 2, 6, 128, 16, 64
    ring = window_columns(window, bs) + 1
    S = 37
    k, v, kc, vc, perm = _sequence(S, Hkv, Dh, bs, ring, seed=3)
    pos = np.asarray([0, 5, 16, 36])
    q = np.random.RandomState(2).randn(len(pos), Hkv * G, Dh).astype(np.float32)
    tab, rel = window_tables(jnp.tile(perm[None], (len(pos), 1)), jnp.asarray(pos, jnp.int32),
                             window, bs)
    np.testing.assert_array_equal(np.asarray(rel), pos)      # the lower bound is position 0
    got = paged_decode_attention(jnp.asarray(q), kc, vc, tab, rel, jnp.int32(0), interpret=True,
                                 window=window)
    np.testing.assert_allclose(np.asarray(got), _masked_softmax(q, k, v, pos, window),
                               rtol=2e-5, atol=2e-5)
    plain = paged_decode_attention(jnp.asarray(q), kc, vc, tab, rel, jnp.int32(0), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("G,start", [(4, 0), (6, 0), (6, 50), (8, 131), (6, 131)])
def test_query_tiles_cross_the_windows_edge(G, start):
    """A prompt chunk of 64 rows (two query tiles) beside two decode rows of
    other sequences and padding: a tile's first row's lower bound starts the
    walk, every row masks at its own two edges. ``start`` 0: the chunk's first
    rows have less than a window behind them; 50, 131: the window's lower
    edge moves through a block inside the tile."""
    Hkv, Dh, bs, window, chunk = 2, 128, 16, 40, 64
    tq = pa.QUERY_TILE
    ring = window_columns(window, bs, chunk) + 1
    S = start + chunk
    k, v, kc, vc, perm = _sequence(S, Hkv, Dh, bs, ring, seed=start + G)
    T = 96
    seq = np.full(T, 3, np.int32)                     # padding's sequence row
    pos = np.zeros(T, np.int32)
    seq[:chunk], pos[:chunk] = 0, np.arange(start, S)
    seq[chunk], pos[chunk] = 1, S - 1                 # "other sequences": the same ring serves
    seq[chunk + 1], pos[chunk + 1] = 2, S - 7
    live = chunk + 2
    q = np.random.RandomState(5).randn(T, Hkv * G, Dh).astype(np.float32)
    rings = jnp.tile(perm[None], (T, 1))
    tiles = query_tiles(jnp.asarray(seq), jnp.asarray(pos), 3, jnp.int32(live), 8)
    assert tiles is not None
    tab, rel = window_tables(rings, jnp.asarray(pos), window, bs, rows=tq)
    got = paged_decode_attention(jnp.asarray(q), kc, vc, tab, rel, jnp.int32(0),
                                 live_rows=jnp.int32(live), tiles=tiles, interpret=True,
                                 window=window)
    want = _masked_softmax(q[:live], k, v, pos[:live], window)
    np.testing.assert_allclose(np.asarray(got[:live]), want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[live:]).any()
    rows = paged_decode_attention(jnp.asarray(q), kc, vc, tab, rel, jnp.int32(0),
                                  live_rows=jnp.int32(live), interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(rows[:live]), want, rtol=2e-5, atol=2e-5)


def test_bfloat16_pools_take_the_native_products():
    Hkv, G, Dh, bs, window = 2, 6, 128, 16, 48
    ring = window_columns(window, bs) + 1
    S = 200
    k, v, kc, vc, perm = _sequence(S, Hkv, Dh, bs, ring, seed=9, dtype=jnp.bfloat16)
    pos = np.asarray([S - 1, S - 5])
    q = np.random.RandomState(4).randn(2, Hkv * G, Dh).astype(np.float32)
    tab, rel = window_tables(jnp.tile(perm[None], (2, 1)), jnp.asarray(pos, jnp.int32), window, bs)
    got = paged_decode_attention(jnp.asarray(q, jnp.bfloat16), kc, vc, tab, rel, jnp.int32(0),
                                 interpret=True, window=window)
    def rounded(x):
        return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))

    want = _masked_softmax(rounded(q), rounded(k), rounded(v), pos, window)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=5e-2, atol=5e-2)


def test_no_window_is_todays_program():
    """``window=None`` lowers the call it lowered before the argument was
    there: the same jaxpr, so the same Mosaic module, under the same name."""
    rng = np.random.RandomState(0)
    T, Hkv, G, Dh, bs, MB = 32, 2, 4, 128, 16, 6
    q = jnp.asarray(rng.randn(T, Hkv * G, Dh), jnp.float32)
    kc = jnp.asarray(rng.randn(2, 9, bs, Hkv * Dh), jnp.float32)
    tab = jnp.asarray(rng.randint(1, 9, (T, MB)), jnp.int32)
    pos = jnp.asarray(rng.randint(0, MB * bs, (T,)), jnp.int32)

    def call(**kw):
        return str(jax.make_jaxpr(lambda *a: paged_decode_attention(*a, interpret=True, **kw))(
            q, kc, kc, tab, pos, jnp.int32(1)))

    plain, none, windowed = call(), call(window=None), call(window=20)
    assert plain == none
    assert "paged_decode_attention" in plain and "paged_window_attention" not in plain
    assert "paged_window_attention" in windowed


def test_the_table_is_as_short_at_any_position():
    assert window_columns(512, 64) == 9 and window_columns(512, 64, 32) == 10
    ring = jnp.arange(1, 18, dtype=jnp.int32)[None]          # 17 columns: block b in b % 17
    for pos in (600, 200_000):
        tab, rel = window_tables(ring, jnp.asarray([pos], jnp.int32), 512, 64)
        first = (pos - 511) // 64
        np.testing.assert_array_equal(np.asarray(tab[0]), (first + np.arange(9)) % 17 + 1)
        assert int(rel[0]) == pos - first * 64 and 511 <= int(rel[0]) < 511 + 64
