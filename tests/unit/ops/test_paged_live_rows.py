"""Both paged kernels told where a call's padding rows start
(``live_rows``): the grid ends there, so the live rows are what a call
over every row gives, bit for bit, and the rows from there on are zeros
whatever the null block holds (interpret mode on the CPU; what a padding
row costs is the chip's: ``tools/kernel_census.py --live``).

The pool's null block and every block no live row names are NaN, as the
engine packs them the live rows come first, and padding rows sit on the
null block at position 0.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas import paged_mla_attention as pm

NB = 96


def live_counts(T):
    return [0, 1, 3, T - 1, T]


def _tables(T, live, max_blocks, bs, rng):
    """→ (tables [T, MB], positions [T], the blocks named): rows from
    ``live`` on are padding."""
    tabs, pos = np.zeros((T, max_blocks), np.int32), np.zeros(T, np.int32)
    free = iter(rng.permutation(np.arange(1, NB)))
    for t in range(live):
        pos[t] = rng.integers(0, max_blocks * bs)
        need = pos[t] // bs + 1
        tabs[t, :need] = [next(free) for _ in range(need)]
    return tabs, pos


def _pool(shape, named, rng):
    """A float32 pool whose blocks outside ``named`` - the null block
    among them - are NaN."""
    x = rng.standard_normal(shape).astype(np.float32)
    x[:, np.setdiff1d(np.arange(shape[1]), named)] = np.nan
    return jnp.asarray(x)


def _check(got, whole, want, live):
    got, whole, want = (np.asarray(x, np.float32) for x in (got, whole, want))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[:live], whole[:live])     # the call over every row
    np.testing.assert_array_equal(got[live:], 0.0)
    np.testing.assert_allclose(got[:live], want[:live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,live", [(T, n) for T in (6, 20) for n in live_counts(T)])
def test_key_value_kernel_dense_table(T, live):
    H, Hkv, Dh, bs, MB = 4, 2, 128, 8, 3
    rng = np.random.default_rng(100 * T + live)
    tabs, pos = _tables(T, live, MB, bs, rng)
    named = np.unique(tabs[tabs > 0])
    kc, vc = (_pool((2, NB, bs, Hkv * Dh), named, rng) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((T, H, Dh)), jnp.float32)
    args = (q, kc, vc, jnp.asarray(tabs), jnp.asarray(pos), jnp.int32(1))
    got = pa.paged_decode_attention(*args, jnp.int32(live), interpret=True)
    # the references see a pool without NaN: the gather multiplies every block it names by 0
    clean = (q, jnp.nan_to_num(kc), jnp.nan_to_num(vc), *args[3:])
    _check(got, pa.paged_decode_attention(*clean, interpret=True), pa.xla_paged_attention(*clean),
           live)


@pytest.mark.parametrize("T,live", [(T, n) for T in (4, 10) for n in live_counts(T)])
def test_key_value_kernel_selection(T, live):
    """``selected_tables`` lays the (token, key-value head) rows
    token-major, so the call's live rows are ``live x Hkv``."""
    G, Hkv, Dh, bs, W = 2, 2, 128, 8, 4
    rng = np.random.default_rng(200 * T + live)
    counts = np.ones((T, Hkv), np.int32)
    counts[:live] = rng.integers(1, W + 1, (live, Hkv))
    token_pos = np.zeros(T, np.int32)
    token_pos[:live] = rng.integers(0, 64, live)
    free = iter(rng.permutation(np.arange(1, NB)))
    tables = np.zeros((T, Hkv, W), np.int32)
    for t in range(live):
        for h in range(Hkv):
            tables[t, h, :counts[t, h]] = [next(free) for _ in range(counts[t, h])]
    tab, at = pa.selected_tables(jnp.asarray(tables), jnp.asarray(counts), jnp.asarray(token_pos),
                                 bs)
    named = np.unique(tables[tables > 0])
    kc, vc = (_pool((1, NB, bs, Dh), named, rng) for _ in range(2))     # one head a pool layer
    q = jnp.asarray(rng.standard_normal((T * Hkv, G, Dh)), jnp.float32)
    got = pa.paged_decode_attention(q, kc, vc, tab, at, jnp.int32(0), jnp.int32(live * Hkv),
                                    interpret=True, selected=True)
    clean = (q, jnp.nan_to_num(kc), jnp.nan_to_num(vc), tab, at, jnp.int32(0))
    _check(got, pa.paged_decode_attention(*clean, interpret=True, selected=True),
           pa.xla_paged_attention(*clean), live * Hkv)


@pytest.mark.parametrize("T,live", [(T, n) for T in (6, 20) for n in live_counts(T)])
def test_latent_kernel(T, live):
    H, rank, lanes, bs, MB = 4, 128, 128, 16, 5
    rng = np.random.default_rng(300 * T + live)
    tabs, pos = _tables(T, live, MB, bs, rng)
    named = np.unique(tabs[tabs > 0])
    c, r = _pool((2, NB, bs, rank), named, rng), _pool((2, NB, bs, lanes), named, rng)
    q = jnp.asarray(rng.standard_normal((T, H, rank + lanes)) * 0.1, jnp.float32)
    args = (q, c, r, jnp.asarray(tabs), jnp.asarray(pos), jnp.int32(1))
    got = pm.paged_mla_decode_attention(*args, jnp.int32(live), interpret=True)
    clean = (q, jnp.nan_to_num(c), jnp.nan_to_num(r), *args[3:])
    _check(got, pm.paged_mla_decode_attention(*clean, interpret=True),
           pm.xla_paged_mla_attention(*clean), live)
    # the fetch rule knows the rows the grid does not reach: nothing is fetched for them
    n, _ = pm.mla_tile(bs, (rank + lanes) * 4, 4, MB, H)
    named_blocks, fetched = pm.fetch_plan(args[3], args[4], bs, n, jnp.int32(live))
    assert int(named_blocks[live:].sum()) == T - live
    assert int(fetched[max(live, 1):].sum()) == 0
    np.testing.assert_array_equal(fetched[:live], pm.fetch_plan(args[3], args[4], bs, n)[1][:live])
