"""The Mamba-1 selective scan over a ragged step
(``ops/pallas/selective_scan.py``): the Pallas kernel, interpreted, and its
XLA fallback against a token-by-token recurrence a sequence, written here in
numpy - ``models/jamba.reference_mamba``'s ``one`` step, which the engine's
tests (``tests/unit/inference/v2/test_jamba.py``) compare the whole mixer
with. ``y`` and the state the step leaves are both compared, and every slot
no live row names has to come back bit for bit. (That Mosaic takes the kernel
at the cell's shape is compiled in ``test_ssm_state.py``, beside the other
compiles for a described chip: one process loads the TPU's library.)
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import selective_scan as ss

LM, NS, N, C, LAYER = 3, 9, 16, 256, 1

# name → (rows of the batch T, sequence rows S, [(sequence row, slot, first row, rows, fresh)]);
# the last sequence row is padding's, and every row no run covers is a padding row
CASES = {
    "one-row-a-sequence": (8, 6, [(0, 3, 0, 1, False), (1, 1, 1, 1, False), (2, 7, 2, 1, True),
                                  (3, 2, 3, 1, False), (4, 5, 4, 1, False)]),
    "one-sequence-a-chunk": (24, 3, [(0, 4, 0, 24, True)]),
    "several-runs-in-one-chunk": (32, 6, [(2, 6, 0, 1, False), (0, 1, 1, 7, True),
                                          (3, 8, 8, 11, False), (1, 2, 19, 9, True)]),
    "a-run-from-a-carried-slot": (16, 3, [(1, 5, 0, 13, False)]),
    "a-fresh-sequence-in-a-released-slot": (16, 4, [(0, 2, 0, 6, True), (2, 3, 6, 5, True)]),
    "padding-rows": (24, 4, [(1, 4, 0, 3, False), (0, 6, 3, 2, True)]),
    "no-live-sequence": (8, 3, []),
    "rows-that-are-no-whole-block": (13, 4, [(0, 1, 0, 5, False), (1, 2, 5, 7, True)]),
}


def _inputs(name):
    T, S, runs = CASES[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    pool = rng.normal(size=(LM, NS, N, C)).astype(np.float32)
    if name == "a-fresh-sequence-in-a-released-slot":
        pool[LAYER, 2] = np.nan               # what a former owner left must not be read
        pool[LAYER, 3] = 1e30
    seq = np.full(T, S - 1, np.int32)
    slot, first, length = (np.zeros(S, np.int32) for _ in range(3))
    fresh = np.ones(S, bool)
    for s, sl, r0, n, fr in runs:
        seq[r0:r0 + n] = s
        slot[s], first[s], length[s], fresh[s] = sl, r0, n, fr
    x = rng.normal(size=(T, C)).astype(np.float32)
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(T, C))).astype(np.float32)
    b = rng.normal(size=(T, N)).astype(np.float32)
    c = rng.normal(size=(T, N)).astype(np.float32)
    a = -np.broadcast_to(np.arange(1, N + 1, dtype=np.float32)[:, None], (N, C)).copy()
    return pool, seq, slot, first, length, fresh, x, delta, b, c, a


def _oracle(name):
    """A sequence at a time, a token at a time, in float64."""
    pool, seq, slot, first, length, fresh, x, delta, b, c, a = _inputs(name)
    want_pool, y = pool.astype(np.float64), np.zeros(x.shape, np.float64)
    for s, sl, r0, n, fr in CASES[name][2]:
        state = np.zeros((N, C)) if fr else pool[LAYER, sl].astype(np.float64)
        for t in range(r0, r0 + n):
            state = np.exp(delta[t][None, :] * a) * state \
                + (delta[t] * x[t])[None, :] * b[t][:, None]
            y[t] = (state * c[t][:, None]).sum(0)
        want_pool[LAYER, sl] = state
    return want_pool, y


def _run(impl, name):
    args = [jnp.asarray(v) for v in _inputs(name)]
    pool, rest = args[0], args[1:]
    if impl == "xla":
        return ss.xla_selective_scan(pool, jnp.int32(LAYER), *rest)
    return ss.selective_scan(pool, jnp.int32(LAYER), *rest, interpret=True)


@pytest.mark.parametrize("name", list(CASES))
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_y_and_the_state_are_the_token_by_token_recurrences(impl, name):
    pool, y = _run(impl, name)
    want_pool, want_y = _oracle(name)
    held = _inputs(name)[0]
    touched = {sl for _, sl, _, _, _ in CASES[name][2]}
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-5)
    for sl in range(NS):
        if sl in touched:
            np.testing.assert_allclose(np.asarray(pool[LAYER, sl]), want_pool[LAYER, sl],
                                       rtol=2e-5, atol=2e-5)
        else:       # a slot no live row names keeps what it held, bit for bit (NaN or not)
            assert np.array_equal(np.asarray(pool[LAYER, sl]), held[LAYER, sl], equal_nan=True)
    for layer in (0, 2):
        assert np.array_equal(np.asarray(pool[layer]), held[layer], equal_nan=True)
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("name", ["several-runs-in-one-chunk", "one-row-a-sequence"])
def test_the_kernel_is_the_fallback_to_the_last_bit_on_the_cpu(name):
    """Interpreted, the kernel does the fallback's float32 operations in the
    fallback's order: the same ``y`` and the same pool."""
    (pool_k, y_k), (pool_x, y_x) = _run("kernel", name), _run("xla", name)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_x))
    assert np.array_equal(np.asarray(pool_k), np.asarray(pool_x))


def test_what_mosaic_can_tile_and_what_takes_the_fallback(monkeypatch):
    cell = (26, 257, 16, 5120)
    assert ss.kernel_supported(cell, 512, 257) and ss.kernel_supported(cell, 256, 257)
    assert not ss.kernel_supported(cell, 13, 257)                  # no whole block of rows
    assert not ss.kernel_supported((26, 257, 16, 5000), 512, 257)  # no whole lane tile
    assert not ss.kernel_supported((26, 257, 12, 5120), 512, 257)  # no whole sublane tile
    monkeypatch.delenv("DS_PALLAS", raising=False)
    assert ss.scan_impl(cell, 512, 257) == ss.XLA                  # the CPU: no kernels
    monkeypatch.setenv("DS_PALLAS", "1")
    assert ss.scan_impl(cell, 512, 257) == ss.KERNEL
    assert ss.scan_impl((2, 5, 16, 384), 13, 5) == ss.KERNEL       # interpreted, any shape
    with pytest.raises(ValueError, match="selective scan kernel needs"):
        ss.selective_scan(jnp.zeros((1, 2, 12, 128)), 0, jnp.zeros(8, jnp.int32),
                          *(jnp.zeros(2, jnp.int32),) * 3, jnp.zeros(2, bool),
                          *(jnp.zeros((8, 128)),) * 2, *(jnp.zeros((8, 12)),) * 2,
                          jnp.zeros((12, 128)), interpret=False)
