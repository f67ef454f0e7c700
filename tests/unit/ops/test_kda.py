"""``ops/pallas/kda``: the Kimi delta rule over a ragged step - the kernel
(interpreted) and its XLA fallback against the recurrence a token at a time
(``models/solar_open2.delta_rule``, what ``reference_kda`` runs), ``o`` and
the state both, in every shape a step can take; then the kernel's block
form (PR 49: runs of ``MIN_CHUNK_RUN`` rows or more, a block of ``CHUNK`` at
a time on the matrix unit) against ``xla_kda_delta_rule``, the row-at-a-time
reference, with the control of its precision."""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.models.solar_open2 import delta_rule
from deepspeed_tpu.ops.pallas import kda

H, D, SLOTS, LAYERS, LAYER = 4, 16, 7, 3, 1
TOL = 2e-6      # float32 sums in another order
# The block form's bound, of the largest magnitude of what is compared: the same float32
# recurrence through a block's running sums of decays, a triangular solve and products in
# another order. It reads ~6e-7 here, where the interpreter's products are exact, and ~4.5e-6
# on the chip, whose float32 products are six bfloat16 passes (tools/kernel_census.py --kda,
# PERF.md, PR 49; the row kernel reads 1.3e-7). One bfloat16 pass reads ~3e-3 and must fail it.
BLOCK_TOL = 2e-5
C = kda.CHUNK


def _step(T, S, runs, seed=0, beta=(0.0, 2.0), log_alpha=(-6.0, 1.0), H=H, D=D):
    """``runs``: [(sequence row, first row, rows, slot, fresh)] → the call's
    arguments; ``beta`` / ``log_alpha``: the ranges drawn from (the latter of
    ``log(-log alpha)``)."""
    rng = np.random.default_rng(seed)
    pool = rng.standard_normal((LAYERS, SLOTS + 1, H, D, D)).astype(np.float32)
    seq = np.full((T,), S - 1, np.int32)
    slot, first, length = (np.zeros((S,), np.int32) for _ in range(3))
    fresh = np.zeros((S,), bool)
    for s, f, n, sl, fr in runs:
        seq[f:f + n] = s
        slot[s], first[s], length[s], fresh[s] = sl, f, n, fr
    q = rng.standard_normal((T, H, D)).astype(np.float32) / np.sqrt(D)
    k = rng.standard_normal((T, H, D)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((T, H, D)).astype(np.float32)
    g = -np.exp(rng.uniform(*log_alpha, (T, H, D))).astype(np.float32)
    b = rng.uniform(*beta, (T, H)).astype(np.float32)
    return pool, LAYER, seq, slot, first, length, fresh, q, k, v, g, b


def _reference(args, runs):
    pool, layer, _, _, _, _, _, q, k, v, g, b = args
    pool, o = pool.copy(), np.zeros_like(q)
    for _, f, n, sl, fr in runs:
        state = np.zeros_like(pool[layer, sl]) if fr else pool[layer, sl]
        rows = (jnp.asarray(a[None, f:f + n]) for a in (q, k, v, g, b))
        out, last = delta_rule(*rows, jnp.asarray(state[None]))
        o[f:f + n], pool[layer, sl] = np.asarray(out[0]), np.asarray(last[0])
    return pool, o


IMPLS = {"xla": kda.xla_kda_delta_rule,
         "pallas_kda": lambda *a: kda.kda_delta_rule(*a, interpret=True)}

# name → (rows of the program, sequence rows, the runs)
STEPS = {
    "one-row-a-sequence": (8, 6, [(s, s, 1, s + 1, False) for s in range(5)]),
    "one-sequence-a-chunk": (16, 3, [(0, 0, 16, 4, True)]),
    "several-runs-in-one-chunk": (24, 5, [(0, 0, 5, 3, False), (2, 5, 1, 1, True),
                                          (1, 6, 11, 4, False), (3, 17, 7, 6, True)]),
    "a-run-from-a-carried-slot": (16, 3, [(1, 0, 9, 5, False)]),
    "a-length-that-is-no-multiple-of-the-block": (24, 4, [(0, 0, 13, 2, False),
                                                          (1, 13, 3, 7, False)]),
    "a-fresh-sequence-in-a-released-slot": (8, 3, [(0, 0, 6, 3, True)]),
    "padding-rows-behind-the-live-ones": (32, 9, [(4, 0, 3, 2, False), (0, 3, 2, 5, True)]),
    "no-live-sequence": (8, 4, []),
    "sequence-rows-out-of-row-order": (16, 6, [(3, 0, 4, 1, False), (0, 4, 1, 6, False),
                                               (2, 5, 6, 3, True)]),
}


# The block form's steps: one program shape (192 rows, 8 sequence rows; 8 heads of 32, so the
# kernel is traced once for all of them), runs of 1, C - 1, C, C + 1 and 2 C + 5 rows at
# offsets that are no block's, fresh and carried
BLOCK_STEPS = {
    "blocks:runs-of-1-and-C-1": (192, 8, [(0, 0, 1, 1, False), (1, 1, C - 1, 2, False)]),
    "blocks:a-run-of-C-in-its-block": (192, 8, [(0, C, C, 3, True)]),
    "blocks:a-run-of-C-across-two": (192, 8, [(2, 5, C, 3, False)]),
    "blocks:a-run-of-C+1": (192, 8, [(0, 3, C + 1, 4, False)]),
    "blocks:a-run-of-2C+5": (192, 8, [(1, 7, 2 * C + 5, 5, True)]),
    "blocks:three-runs-with-one-row-sequences-between": (
        192, 8, [(0, 0, 30, 1, False), (1, 30, 1, 2, False), (2, 31, 50, 3, True),
                 (3, 81, 1, 4, False), (4, 82, 70, 5, False), (5, 152, 1, 6, True)]),
    "blocks:two-runs-in-one-block-and-rows-behind": (
        192, 8, [(6, 2, 24, 7, False), (0, 26, 33, 2, False), (3, 59, 2, 1, False)]),
}
STEPS.update(BLOCK_STEPS)


def _shape(step):
    """The block form's steps at 8 heads of 32 (a whole group of heads)."""
    return {"H": 8, "D": 32} if step.startswith("blocks:") else {}


def _bound(step, impl):
    return BLOCK_TOL if step.startswith("blocks:") and impl == "pallas_kda" else TOL


@pytest.mark.parametrize("impl", list(IMPLS))
@pytest.mark.parametrize("step", list(STEPS))
def test_a_step_is_the_recurrence_a_token_at_a_time(step, impl):
    T, S, runs = STEPS[step]
    args = _step(T, S, runs, seed=len(step), **_shape(step))
    want_pool, want_o = _reference(args, runs)
    pool, o = IMPLS[impl](*(jnp.asarray(a) for a in args))
    tol = _bound(step, impl)
    assert np.abs(np.asarray(o) - want_o).max() < tol * max(1.0, np.abs(want_o).max())
    assert np.abs(np.asarray(pool) - want_pool).max() < tol * np.abs(want_pool).max()
    named = {sl for _, _, _, sl, _ in runs}
    for sl in set(range(SLOTS + 1)) - named:      # a slot no live row names: bit for bit
        assert np.array_equal(np.asarray(pool)[:, sl], args[0][:, sl])
    assert np.array_equal(np.asarray(pool)[0], args[0][0])              # another layer
    live = np.zeros(T, bool)
    for _, f, n, _, _ in runs:
        live[f:f + n] = True
    assert not np.asarray(o)[~live].any()                              # padding's rows


ENDS = [("beta-near-0", (0.0, 1e-3), (-6.0, 1.0)),
        ("beta-near-2", (1.999, 2.0), (-6.0, 1.0)),
        ("a-decay-near-0", (0.0, 2.0), (np.log(20.0), np.log(20.0))),
        ("a-decay-at-0", (0.0, 2.0), None)]
# the same through the block form, and a decay of e^-2 a row: neighbours still see one another,
# and exp(-G) over a block of 64 (e^128; e^1280 at -20 a row) is no float32
ENDS += [("blocks:" + name, beta, decay) for name, beta, decay in ENDS]
ENDS.append(("blocks:a-decay-of-2-a-row", (0.0, 2.0), (np.log(2.0), np.log(2.0))))


@pytest.mark.parametrize("impl", list(IMPLS))
@pytest.mark.parametrize("name,beta,log_alpha", ENDS)
def test_the_ends_of_beta_and_of_the_decay(name, beta, log_alpha, impl):
    """``beta`` near 0 (nothing written) and near 2 (the state reflected
    along the key), ``log alpha`` ~ -20 (a key row forgotten at once) and 0
    (kept whole: the plain delta rule)."""
    if name.startswith("blocks:"):
        T, S, runs = 192, 8, [(0, 3, 70, 2, False), (1, 73, 1, 5, False), (2, 74, C + 1, 1, True)]
    else:
        T, S, runs = 16, 4, [(0, 0, 12, 2, False), (1, 12, 1, 5, False), (2, 13, 3, 1, True)]
    args = list(_step(T, S, runs, seed=7, beta=beta, log_alpha=log_alpha or (-6.0, 1.0),
                      **_shape(name)))
    if log_alpha is None:
        args[10] = np.zeros_like(args[10])
    want_pool, want_o = _reference(args, runs)
    pool, o = IMPLS[impl](*(jnp.asarray(a) for a in args))
    tol = _bound(name, impl)
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(o) - want_o).max() < tol * max(1.0, np.abs(want_o).max())
    assert np.abs(np.asarray(pool) - want_pool).max() < tol * np.abs(want_pool).max()


@pytest.mark.parametrize("step", list(BLOCK_STEPS))
def test_the_block_form_is_the_row_at_a_time_reference_and_one_bfloat16_pass_is_not(step):
    """Against ``xla_kda_delta_rule``: every run of ``MIN_CHUNK_RUN`` rows or
    more goes through the block form and meets ``BLOCK_TOL``; **the control**:
    the same products with their operands rounded to bfloat16, what a single
    pass of the matrix unit would see, fail it - so the bound does tell the
    float32 recurrence from a cheaper one."""
    T, S, runs = BLOCK_STEPS[step]
    args = [jnp.asarray(a) for a in _step(T, S, runs, seed=len(step), **_shape(step))]
    blocks = np.asarray(kda.chunk_rows(args[5], T))
    assert list(blocks) == [n >= kda.MIN_CHUNK_RUN for n in np.asarray(args[5])] and blocks.any()
    want_pool, want_o = kda.xla_kda_delta_rule(*args)

    def errors(**how):
        pool, o = kda._delta_call(*args, interpret=True, **how)
        return (float(jnp.abs(o - want_o).max() / max(1.0, float(jnp.abs(want_o).max()))),
                float(jnp.abs(pool - want_pool).max() / jnp.abs(want_pool).max()))

    assert max(errors()) < BLOCK_TOL
    if "one-row" in step:
        assert max(errors(min_run=1)) < BLOCK_TOL        # the one-row runs through it too
    assert min(errors(one_pass=True)) > 10 * BLOCK_TOL


def test_a_program_of_one_row_a_sequence_holds_no_block_form():
    """A burst's step says that it holds one row a sequence: the block form
    is not lowered into its program, and the rows come out the same."""
    import jax
    runs = [(s, s, 1, s + 1, s == 3) for s in range(6)]
    args = [jnp.asarray(a) for a in _step(192, 8, runs, seed=5, H=8, D=32)]

    def names(**how):
        return str(jax.make_jaxpr(lambda *a: kda.kda_delta_rule(*a, interpret=True, **how))(*args))

    assert "kda_delta_rule_blocks" in names()
    assert "kda_delta_rule_blocks" not in names(one_row_runs=True)
    for got, want in zip(kda.kda_delta_rule(*args, interpret=True, one_row_runs=True),
                         kda.kda_delta_rule(*args, interpret=True)):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_no_block_form_where_the_batch_is_no_whole_blocks():
    assert not np.asarray(kda.chunk_rows(jnp.asarray([100, 1, 0]), 200)).any()
    assert list(np.asarray(kda.chunk_rows(jnp.asarray([kda.MIN_CHUNK_RUN, 1, 0]), 192))) == [
        True, False, False]


def test_a_fresh_sequence_ignores_a_slot_that_holds_no_number():
    """What a released slot holds is a former owner's, NaN or not."""
    runs = [(0, 0, 5, 3, True)]
    args = list(_step(8, 3, runs, seed=3))
    clean = _reference(args, runs)
    args[0] = args[0].copy()
    args[0][LAYER, 3] = np.nan
    for impl in IMPLS.values():
        pool, o = impl(*(jnp.asarray(a) for a in args))
        assert np.abs(np.asarray(o) - clean[1]).max() < TOL
        assert np.abs(np.asarray(pool)[LAYER, 3] - clean[0][LAYER, 3]).max() < TOL


def test_the_transition_rotates_and_is_no_diagonal_decay():
    """One row with ``beta`` 2 and no decay reflects the state along the key:
    ``S^T k`` changes sign (less the written value) - what no element-wise
    decay does."""
    rng = np.random.default_rng(1)
    state = rng.standard_normal((1, 1, D, D)).astype(np.float32)
    k = np.zeros((1, 1, 1, D), np.float32)
    k[..., 0] = 1.0
    zeros = np.zeros((1, 1, 1, D), np.float32)
    _, last = delta_rule(jnp.asarray(k), jnp.asarray(k), jnp.asarray(zeros), jnp.asarray(zeros),
                         jnp.full((1, 1, 1), 2.0), jnp.asarray(state))
    np.testing.assert_allclose(np.asarray(last)[0, 0, 0], -state[0, 0, 0], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(last)[0, 0, 1:], state[0, 0, 1:])


def test_which_shapes_the_kernel_takes_and_who_serves_off_the_chip(monkeypatch):
    cell = (3, 193, 64, 128, 128)
    assert kda.kernel_supported(cell, 512, 193) and kda.kernel_supported(cell, 192, 193)
    assert not kda.kernel_supported((3, 193, 64, 64, 64), 512, 193)       # half a lane tile
    assert not kda.kernel_supported((3, 193, 60, 128, 128), 512, 193)     # no whole group of heads
    assert not kda.kernel_supported(cell, 100, 193)                       # no whole block of rows
    assert not kda.kernel_supported((3, 193, 128, 128, 128), 512, 193)    # four states: 32 MB
    monkeypatch.delenv("DS_PALLAS", raising=False)
    assert kda.delta_rule_impl(cell, 512, 193) == "xla"                   # the CPU
    monkeypatch.setenv("DS_PALLAS", "1")
    assert kda.delta_rule_impl((2, 5, 4, 16, 16), 8, 5) == "pallas_kda"   # interpreted: any shape
    with pytest.raises(ValueError, match="KDA kernel needs"):
        args = _step(8, 3, [(0, 0, 5, 3, True)])
        kda.kda_delta_rule(*(jnp.asarray(a) for a in args), interpret=False)
