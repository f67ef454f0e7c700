"""The two row kernels of the training exchange (``ops/pallas/moe_rows.py``),
interpreted on the CPU, against their ``jnp`` forms; and that each one's
cotangent is the other. (Mosaic's own verdict on them at the cell's shape is
with the other described-chip compiles, in ``test_ssm_state.py``.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import moe_rows as mr

K = 4


def _case(dtype, n_tokens, n_slots, width, seed=0):
    """A layout as a pass builds it: token ``t`` holds ``t % (K + 1)`` picks
    (so 0, 1 and ``K`` all occur), each in a slot of its own; the other slots
    are padding. → (tokens' rows, the layout's rows with NaN in the rows no
    token names, ``idx`` [S], ``slots`` [T, K], weights)."""
    rng = np.random.default_rng(seed)
    held = [(t, j) for t in range(n_tokens) for j in range(t % (K + 1))]
    assert len(held) <= n_slots
    where = rng.permutation(n_slots)[:len(held)]
    idx = np.full((n_slots,), n_tokens, np.int32)
    slots = np.full((n_tokens, K), n_slots, np.int32)
    for (t, j), s in zip(held, where):
        idx[s], slots[t, j] = t, s
    x = rng.standard_normal((n_tokens, width)).astype(np.float32)
    y = rng.standard_normal((n_slots, width)).astype(np.float32)
    y[idx == n_tokens] = np.nan                     # read by nobody, or the sum says so
    w = rng.uniform(0.1, 1.0, (n_tokens, K)).astype(np.float32)
    return (jnp.asarray(x, dtype), jnp.asarray(y, dtype), jnp.asarray(idx), jnp.asarray(slots),
            jnp.asarray(w))


@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "w_none"])
@pytest.mark.parametrize("dtype,n_tokens,n_slots,width", [
    (jnp.float32, 40, 100, 128),        # neither count a multiple of a block
    (jnp.bfloat16, 70, 300, 256),       # more slots than one block of 256
    (jnp.bfloat16, 32, 64, 512),
], ids=["f32_ragged", "bf16_two_blocks", "bf16_whole_blocks"])
def test_the_kernels_are_their_jnp_forms(dtype, n_tokens, n_slots, width, weighted):
    """``gather_rows``: a live slot gets its token's row bit for bit and a
    sentinel slot zeros, whatever the source holds beyond (NaN is planted in
    the tokens no slot names). ``gather_sum_rows``: a token's held picks
    weighted and summed in float32, a sentinel skipped - the layout's unnamed
    rows are NaN, so a slot read and multiplied by zero would show - for
    tokens with 0, 1 and ``K`` held picks."""
    x, y, idx, slots, w = _case(dtype, n_tokens, n_slots, width)
    named = np.isin(np.arange(n_tokens), np.asarray(idx))
    x = jnp.where(jnp.asarray(named)[:, None], x, jnp.nan)
    got = mr.gather_rows(x, idx, None, True, True)
    want = np.where((np.asarray(idx) < n_tokens)[:, None],
                    np.asarray(x.astype(jnp.float32))[np.minimum(np.asarray(idx), n_tokens - 1)], 0)
    assert got.dtype == x.dtype and got.shape == (n_slots, width)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)
    np.testing.assert_array_equal(np.asarray(mr.gather_rows(x, idx).astype(jnp.float32)), want)

    w = w if weighted else None
    got = mr.gather_sum_rows(y, slots, w, None, True, True)
    rows = np.asarray(y.astype(jnp.float32))[np.minimum(np.asarray(slots), n_slots - 1)]
    rows = np.where((np.asarray(slots) < n_slots)[..., None],
                    rows * (1 if w is None else np.asarray(w)[..., None]), 0)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), rows.sum(1), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mr.gather_sum_rows(y, slots, w)), rows.sum(1),
                               rtol=1e-6, atol=1e-6)
    assert (np.asarray(got)[np.arange(n_tokens) % (K + 1) == 0] == 0).all()    # no pick, no row


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernels"])
def test_each_ones_cotangent_is_the_other(kernel):
    """``jax.vjp`` of ``gather_rows`` is ``gather_sum_rows`` without weights on
    the same pair of index arrays, and ``jax.vjp`` of ``gather_sum_rows`` in
    its rows is ``gather_rows`` scaled by each slot's weight - the same calls,
    so the same bits, not a derivation that agrees to rounding; the weights'
    cotangent is each pick's row against its token's cotangent."""
    x, y, idx, slots, w = _case(jnp.float32, 24, 64, 128, seed=1)
    y = jnp.nan_to_num(y)           # the rows nobody names: zeros here, so that a dot is finite
    rng = np.random.default_rng(2)
    d_layout = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    d_tokens = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)

    _, pull = jax.vjp(lambda a: mr.gather_rows(a, idx, slots, kernel, kernel), x)
    np.testing.assert_array_equal(
        np.asarray(pull(d_layout)[0]),
        np.asarray(mr.gather_sum_rows(d_layout, slots, None, idx, kernel, kernel)))

    _, pull = jax.vjp(lambda a, b: mr.gather_sum_rows(a, slots, b, idx, kernel, kernel), y, w)
    d_y, d_w = pull(d_tokens)
    by_slot = np.zeros((y.shape[0],), np.float32)
    live = np.asarray(slots) < y.shape[0]
    by_slot[np.asarray(slots)[live]] = np.asarray(w)[live]
    rows = mr.gather_rows(d_tokens, idx, slots, kernel, kernel)
    np.testing.assert_array_equal(np.asarray(d_y), np.asarray(rows) * by_slot[:, None])
    want_w = np.where(live, np.einsum(
        "td,tkd->tk", np.asarray(d_tokens),
        np.asarray(y)[np.minimum(np.asarray(slots), y.shape[0] - 1)]), 0)
    np.testing.assert_allclose(np.asarray(d_w), want_w, rtol=1e-5, atol=1e-5)

    with pytest.raises(ValueError, match="needs the slots"):
        jax.vjp(lambda a: mr.gather_rows(a, idx, None, kernel, kernel), x)[1](d_layout)


def test_what_the_kernels_can_address():
    """A row is whole 128-lane vregs of 32-bit words: 2 or 4 bytes an element,
    and a 16-bit row twice as wide."""
    assert mr.rows_kernel_supported(2304, jnp.bfloat16) and mr.rows_kernel_supported(128, jnp.float32)
    assert not mr.rows_kernel_supported(128, jnp.bfloat16)
    assert not mr.rows_kernel_supported(200, jnp.float32)
    assert not mr.rows_kernel_supported(512, jnp.int8)
