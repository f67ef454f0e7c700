"""The paged decode-attention kernel at a head of 64 (interpret mode on the
CPU) against the XLA gather reference: a pair of key-value heads a
128-lane slice (``paged_attention._paired``), and a head of 128 left on the
code it took. The kernel compiled for the chip at the LFM2 cell's shape is
in ``test_ssm_state.py``, beside the other compile for a described v5e (one
process loads the TPU's library, so those tests share a file)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.pallas.paged_attention import (kernel_supported, paged_decode_attention,
                                                      selected_tables, xla_paged_attention)


def _case(T, Hkv, G, Dh, bs, MB, NB=24, L=2, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(T, Hkv * G, Dh).astype(np.float32), dtype)
    kc = jnp.asarray(rng.randn(L, NB, bs, Hkv * Dh).astype(np.float32), dtype)
    vc = jnp.asarray(rng.randn(L, NB, bs, Hkv * Dh).astype(np.float32), dtype)
    tabs = jnp.asarray(rng.randint(1, NB, size=(T, MB)).astype(np.int32))
    pos = jnp.asarray(rng.randint(0, MB * bs, size=(T,)).astype(np.int32))
    return q, kc, vc, tabs, pos


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("bs", [16, 64])
@pytest.mark.parametrize("Hkv,G", [(8, 4), (2, 2), (2, 1), (4, 8)])
def test_a_head_of_64_matches_the_gather(Hkv, G, bs, dtype, tol):
    """LFM2's 32 query / 8 key-value heads and narrower ones, every layer
    of the pool, rows past ``live_rows`` zero."""
    T, live = 11, 8
    q, kc, vc, tabs, pos = _case(T, Hkv, G, 64, bs, MB=5, seed=Hkv * G + bs, dtype=dtype)
    pos = pos.at[0].set(0).at[1].set(5 * bs - 1)
    for layer in (0, 1):
        want = xla_paged_attention(q, kc, vc, tabs, pos, jnp.int32(layer))
        got = paged_decode_attention(q, kc, vc, tabs, pos, jnp.int32(layer), live_rows=live,
                                     interpret=True)
        assert got.shape == q.shape and got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got[:live], np.float32),
                                   np.asarray(want[:live], np.float32), rtol=tol, atol=tol)
        assert not np.asarray(got[live:], np.float32).any()


def test_a_head_of_64_under_a_selection_table():
    """A table that is a selection (``selected_tables``): each row reads
    ``count`` blocks and, in the last, the rows up to its own."""
    T, Hkv, G, bs, W = 6, 2, 4, 16, 4
    rng = np.random.RandomState(7)
    q, kc, vc, _, _ = _case(T, Hkv, G, 64, bs, MB=W, seed=7)
    tables = jnp.asarray(rng.randint(1, 24, size=(T, 1, W)).astype(np.int32))
    counts = jnp.asarray(rng.randint(1, W + 1, size=(T, 1)).astype(np.int32))
    token_pos = jnp.asarray(rng.randint(0, 10 * bs, size=(T,)).astype(np.int32))
    tab, at = selected_tables(tables, counts, token_pos, bs)
    want = xla_paged_attention(q, kc, vc, tab, at, jnp.int32(1), selected=True)
    got = paged_decode_attention(q, kc, vc, tab, at, jnp.int32(1), interpret=True, selected=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_the_pair_lays_each_query_in_its_own_heads_half():
    q = jnp.arange(2 * 8 * 64, dtype=jnp.float32).reshape(2, 8, 64) + 1.0
    wide, pick = pa._paired(q, 4)                         # 4 key-value heads, 2 query heads each
    assert wide.shape == (2, 8, 128)
    odd = np.asarray([0, 0, 1, 1, 0, 0, 1, 1], bool)      # query head h reads kv head h // 2
    w = np.asarray(wide)
    assert np.array_equal(w[:, ~odd, :64], np.asarray(q)[:, ~odd]) and not w[:, ~odd, 64:].any()
    assert np.array_equal(w[:, odd, 64:], np.asarray(q)[:, odd]) and not w[:, odd, :64].any()
    assert np.array_equal(np.asarray(pick(wide)), np.asarray(q))


@pytest.mark.parametrize("head_dim,block,kv_heads,ok", [
    (128, 16, 8, True), (128, 64, 1, True), (256, 16, 3, True), (128, 12, 8, False),
    (64, 64, 8, True), (64, 16, 2, True), (64, 16, 12, True),    # whole 128-lane pool rows
    (64, 16, 1, False), (64, 16, 25, False),                     # an odd head is half a tile
    (64, 16, None, False),                                       # the count has to be known
    (64, 12, 8, False), (32, 16, 8, False), (96, 16, 4, False), (16, 16, 2, False)])
def test_kernel_supported_table(head_dim, block, kv_heads, ok):
    assert kernel_supported(head_dim, block, kv_heads) is ok


def test_a_refused_shape_raises_and_never_degrades():
    q, kc, vc, tabs, pos = _case(4, 1, 4, 64, 16, MB=2)               # one narrow head: no pair
    with pytest.raises(ValueError, match="an even number of key-value heads"):
        jax.eval_shape(lambda *a: paged_decode_attention(*a, jnp.int32(0), interpret=False),
                       q, kc, vc, tabs, pos)


def test_a_head_of_128_takes_the_code_it_took(monkeypatch):
    """No widening, no ``head_dim`` given to the kernel: the call a head of
    128 makes is the one it made, and its program has no 256-wide query."""
    q, kc, vc, tabs, pos = _case(5, 2, 2, 128, 16, MB=3, seed=3)
    monkeypatch.setattr(pa, "_paired", lambda *a: pytest.fail("a head of 128 was paired"))
    seen = {}
    call = pa._paged_call

    def spy(*args, **kw):
        seen.update(kw, n_args=len(args))
        return call(*args, **kw)

    monkeypatch.setattr(pa, "_paged_call", spy)
    got = paged_decode_attention(q, kc, vc, tabs, pos, jnp.int32(1), interpret=True)
    assert seen == {"n_args": 9, "tiles": None}            # positional, as before: no head_dim
    want = xla_paged_attention(q, kc, vc, tabs, pos, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    monkeypatch.undo()
    text = jax.jit(lambda *a: paged_decode_attention(*a, jnp.int32(1), interpret=True)).lower(
        q, kc, vc, tabs, pos).as_text()
    assert "5x4x256" not in text and "5x4x128" in text


def test_the_engine_gets_the_kernel_at_a_head_of_64_where_it_is_forced(monkeypatch):
    from deepspeed_tpu.inference.v2.modules.heuristics import instantiate_attn
    q, kc = (8, 32, 64), (2, 40, 64, 512)
    assert instantiate_attn(None, 64, 64, q, kc, None, max_blocks=8)[0] == "xla_gather"   # no TPU
    monkeypatch.setenv("DS_PALLAS", "1")
    assert instantiate_attn(None, 64, 64, q, kc, None, max_blocks=8)[0] == "pallas_paged"
    assert instantiate_attn(None, 64, 64, (8, 4, 64), (2, 40, 64, 64), None,
                            max_blocks=8)[0] == "xla_gather"            # one key-value head
    with pytest.raises(ValueError, match="pinned attention='pallas_paged'"):
        instantiate_attn(None, 64, 64, (8, 4, 64), (2, 40, 64, 64), None, max_blocks=8,
                         override="pallas_paged")


@pytest.mark.parametrize("Hkv,G", [(1, 20), (8, 4), (2, 16), (8, 8)],
                         ids=["jamba-20-over-1", "chat-4-a-head", "agents-16-a-head",
                              "solar-8-a-head-over-8"])
def test_a_query_group_that_is_no_power_of_two_matches_the_gather(Hkv, G):
    """Jamba's 20 query heads over one key-value head of 128 - the first
    group that is no power of two: a tile lays ``32 x 20`` columns - beside
    the groups the other cells run (4 and 16, and Solar Open 2's 8 over 8
    key-value heads: a 1024-lane row), a row a grid step and with
    the step's query tiles: a chunk of 40 rows, a decode row, a chunk that
    starts its sequence, padding."""
    bs, MB, NB, n_seqs = 16, 6, 24, 4
    rng = np.random.RandomState(G)
    runs = [(0, 10, 40), (1, 70, 1), (2, 0, 20)]             # (sequence, first position, rows)
    seq = np.concatenate([np.full(n, s, np.int32) for s, _, n in runs] + [np.full(3, n_seqs)])
    pos = np.concatenate([np.arange(f, f + n) for _, f, n in runs] + [np.zeros(3)]).astype(np.int32)
    T, live = len(seq), 61
    assert T == 2 * pa.QUERY_TILE
    tables = np.concatenate([rng.randint(1, NB, size=(n_seqs, MB)),
                             np.zeros((1, MB))]).astype(np.int32)
    q = jnp.asarray(rng.randn(T, Hkv * G, 128).astype(np.float32))
    kc = jnp.asarray(rng.randn(2, NB, bs, Hkv * 128).astype(np.float32))
    vc = jnp.asarray(rng.randn(2, NB, bs, Hkv * 128).astype(np.float32))
    tabs, seq, pos = jnp.asarray(tables[seq]), jnp.asarray(seq), jnp.asarray(pos)
    want = xla_paged_attention(q, kc, vc, tabs, pos, jnp.int32(1))
    tiles = pa.query_tiles(seq, pos, n_seqs, jnp.int32(live), MB)
    for given in (None, tiles):
        got = paged_decode_attention(q, kc, vc, tabs, pos, jnp.int32(1), live_rows=live,
                                     tiles=given, interpret=True)
        np.testing.assert_allclose(np.asarray(got[:live]), np.asarray(want[:live]),
                                   rtol=1e-5, atol=1e-5)
        assert not np.asarray(got[live:]).any()
