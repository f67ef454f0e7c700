"""The convolution whose tail is a slot (``model_runner._conv_with_tail``)
against the way it was written before PR 46 - the step's tails gathered a
slab a sequence row, every tap a gather from them, the new tails scattered
back a slab - which is kept here, word for word, as the reference.

Nothing but rows moves in either, so everything is compared **bit for
bit**: the filtered stream, the tail every live row's slot is left with,
and every slot no live row names - padding's slot 0 among them, which the
former way rewrote a step - exactly as it was. Over the three kinds'
shapes (``K - 1`` = 3 / 3 / 2 rows, bfloat16 and float32), and then through
each kind's own mixer (``JambaKind.mamba_layer``,
``NemotronHKind.mamba_layer``, ``Lfm2Kind.conv_layer``: the hooks the
benchmark's checks call) over a prompt cut in chunks and a decode step.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        RaggedInferenceEngineConfig, model_runner)
from deepspeed_tpu.models import build_model

L, NS, LAYER = 3, 9, 1

# name → (rows of the batch T, sequence rows S, [(sequence row, slot, first position, rows)]
# in batch order); the last sequence row is padding's, as every row no run covers
CASES = {
    "one-row-a-sequence": (8, 6, [(0, 3, 40, 1), (1, 1, 7, 1), (2, 7, 0, 1), (3, 2, 1, 1),
                                  (4, 5, 2, 1)]),
    "one-sequence-a-chunk": (24, 3, [(0, 4, 0, 24)]),
    "several-runs-in-one-chunk": (32, 6, [(2, 6, 9, 1), (0, 1, 0, 7), (3, 8, 64, 11),
                                          (1, 2, 0, 2)]),
    "runs-shorter-than-the-tail": (8, 5, [(0, 2, 5, 2), (1, 4, 0, 1), (2, 6, 1, 2),
                                          (3, 8, 2, 1)]),
    "a-fresh-sequence-in-a-released-slot": (16, 4, [(0, 2, 0, 6), (2, 3, 0, 2)]),
    "padding-rows": (24, 4, [(1, 4, 3, 3), (0, 6, 0, 2)]),
    "no-live-sequence": (8, 3, []),
    "the-check-hooks-three-slots-two-rows": (12, 2, [(0, 2, 30, 12)]),
}
# (K - 1, C, dtype): jamba2-3b's, nemotron-3-super's and lfm2-24b's tails, narrowed
SHAPES = {"jamba": (3, 256, jnp.bfloat16), "nemotron": (3, 384, jnp.float32),
          "lfm2": (2, 128, jnp.bfloat16)}


def former_conv_with_tail(stream, kernel, bias, pool, layer, rows):
    """``_conv_with_tail`` as it stood at PR 45."""
    T, C = stream.shape
    K = kernel.shape[0]
    f32 = jnp.float32
    seq, slot, first_row = rows.seq, rows.slot, rows.first_row
    tail = jnp.where(rows.fresh[:, None, None], 0, pool[layer, slot])    # [S, K - 1, C]
    rank = jnp.arange(T, dtype=jnp.int32) - first_row[seq]
    acc = None if bias is None else bias.astype(f32)[None, :]
    kernel = kernel.astype(f32)
    for j in range(K):
        back = K - 1 - j
        tap = stream if back == 0 else jnp.concatenate(
            [jnp.zeros((min(back, T), C), stream.dtype), stream[:max(T - back, 0)]], axis=0)
        if back:
            carried_row = tail[seq, jnp.clip(rank + j, 0, K - 2)]
            tap = jnp.where((rank >= back)[:, None], tap, carried_row)
        term = kernel[j][None, :] * tap.astype(f32)
        acc = term if acc is None else acc + term
    i = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    into = rows.length[:, None] - (K - 1) + i
    kept = jnp.take_along_axis(tail, jnp.clip(rows.length[:, None] + i, 0, K - 2)[..., None],
                               axis=1)
    new_tail = jnp.where((into >= 0)[..., None],
                         stream[jnp.clip(first_row[:, None] + into, 0, T - 1)], kept)
    return acc, pool.at[layer, slot].set(new_tail.astype(pool.dtype))


def _batch(case):
    T, S, runs = CASES[case]
    seq = np.full(T, S - 1, np.int32)
    pos = np.zeros(T, np.int32)
    state = np.zeros((S, 1), np.int32)
    at = 0
    for s, slot, first, n in runs:
        seq[at:at + n], pos[at:at + n], state[s, 0] = s, np.arange(first, first + n), slot
        at += n
    return {"token_seq": jnp.asarray(seq), "token_pos": jnp.asarray(pos),
            "block_tables": jnp.zeros((S, 1), jnp.int32), "seq_state": jnp.asarray(state)}


def _inputs(case, shape, n_slots=NS):
    tail_rows, C, dtype = SHAPES[shape]
    T = CASES[case][0]
    rng = np.random.default_rng(sum(map(ord, case + shape)))
    pool = rng.normal(size=(L, n_slots, tail_rows, C)).astype(np.float32)
    if case == "a-fresh-sequence-in-a-released-slot":
        pool[LAYER, 2] = np.nan               # what a former owner left must not be read
        pool[LAYER, 3] = 1e30
    stream = rng.normal(size=(T, C)).astype(np.float32)
    kernel = rng.normal(size=(tail_rows + 1, C)).astype(np.float32)
    bias = None if shape == "lfm2" else jnp.asarray(rng.normal(size=(C,)), dtype)
    return jnp.asarray(pool, dtype), jnp.asarray(stream, dtype), jnp.asarray(kernel, dtype), bias


def _both(case, shape, n_slots=NS):
    pool, stream, kernel, bias = _inputs(case, shape, n_slots)
    rows = model_runner._SlotStep(None, _batch(case), n_slots)
    run = jax.jit(lambda conv, pool: conv(stream, kernel, bias, pool, jnp.int32(LAYER), rows),
                  static_argnums=0)
    return pool, run(model_runner._conv_with_tail, pool), run(former_conv_with_tail, pool)


def _bits(x):
    """The array's bits: NaN equals NaN, -0.0 differs from 0.0."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("case", CASES)
def test_the_rows_and_the_named_slots_are_the_former_ways_bit_for_bit(case, shape):
    n_slots = 3 if case.startswith("the-check-hook") else NS
    before, (acc, pool), (former_acc, former_pool) = _both(case, shape, n_slots)
    assert acc.dtype == jnp.float32 and pool.dtype == before.dtype
    if before.dtype == jnp.bfloat16:
        np.testing.assert_array_equal(_bits(acc), _bits(former_acc))
    else:   # the same taps in the same order, but the CPU compiler contracts the two
        # programs' float32 multiply-adds differently: a last place
        np.testing.assert_allclose(acc, former_acc, rtol=1e-6, atol=1e-6)
    before, pool, former_pool = _bits(before), _bits(pool), _bits(former_pool)
    named = sorted(slot for _, slot, _, _ in CASES[case][2])
    np.testing.assert_array_equal(pool[LAYER][named], former_pool[LAYER][named])
    # every slot no live row names - padding's slot 0, a released slot's NaN - and every
    # other layer: the bits they held
    unnamed = [ns for ns in range(n_slots) if ns not in named]
    np.testing.assert_array_equal(pool[LAYER][unnamed], before[LAYER][unnamed])
    others = [layer for layer in range(L) if layer != LAYER]
    np.testing.assert_array_equal(pool[others], before[others])


def test_a_step_with_no_live_row_changes_nothing_and_the_former_way_rewrote_paddings_slot():
    before, (acc, pool), (_, former_pool) = _both("no-live-sequence", "jamba")
    np.testing.assert_array_equal(_bits(pool), _bits(before))
    assert not np.array_equal(_bits(former_pool[LAYER, 0]), _bits(before[LAYER, 0]))


def test_a_slot_tells_its_sequence_row_and_padding_names_none():
    rows = model_runner._SlotStep(None, _batch("several-runs-in-one-chunk"), NS)
    padding = CASES["several-runs-in-one-chunk"][1] - 1
    want = np.full(NS, padding, np.int32)
    for s, slot, _, _ in CASES["several-runs-in-one-chunk"][2]:
        want[slot] = s
    np.testing.assert_array_equal(np.asarray(rows.row_of_slot), want)
    none = model_runner._SlotStep(None, _batch("no-live-sequence"), NS)
    np.testing.assert_array_equal(np.asarray(none.row_of_slot), np.full(NS, 2, np.int32))


# ------------------------------------------------- through each kind's own mixer
def _step(rows, n_rows, slots):
    """``rows``: [(sequence row, first position, length)] in batch order."""
    seq = np.concatenate([np.full(n, s, np.int32) for s, _, n in rows])
    pos = np.concatenate([np.arange(f, f + n, dtype=np.int32) for _, f, n in rows])
    state = np.zeros((n_rows, 1), np.int32)
    state[:len(slots), 0] = slots
    return {"token_seq": jnp.asarray(seq), "token_pos": jnp.asarray(pos),
            "block_tables": jnp.zeros((n_rows, 1), jnp.int32), "seq_state": jnp.asarray(state)}


def _jamba(cfg, n_slots):
    pools = (jnp.ones((cfg.count("m"), n_slots, cfg.mamba_d_state, cfg.mamba_inner), jnp.float32),
             jnp.ones((cfg.count("m"), n_slots, cfg.mamba_d_conv - 1, cfg.mamba_inner),
                      jnp.float32))
    return model_runner.JambaKind.mamba_layer, pools


def _nemotron(cfg, n_slots):
    pools = (jnp.ones((cfg.count("M"), n_slots, cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size), jnp.float32),
             jnp.ones((cfg.count("M"), n_slots, cfg.conv_kernel - 1, cfg.conv_dim), jnp.float32))
    return model_runner.NemotronHKind.mamba_layer, pools


def _lfm2(cfg, n_slots):
    return model_runner.Lfm2Kind.conv_layer, \
        (jnp.ones((cfg.count("conv"), n_slots, cfg.conv_L_cache - 1, cfg.hidden_size),
                  jnp.float32),)


MIXERS = {"jamba-debug": _jamba, "nemotron-h-debug": _nemotron, "lfm2-debug": _lfm2}


@pytest.mark.parametrize("preset", MIXERS)
def test_each_kinds_mixer_over_a_chunk_cut_and_a_decode_step_is_the_former_ways(preset,
                                                                               monkeypatch):
    """The benchmark's check hooks, at their own 3 slots x 2 sequence rows:
    a prompt of 12 rows cut at 6, then a decode step - ``y`` and every pool
    after each (but padding's slot 0, which the former way rewrote), with
    ``_conv_with_tail`` as it is and as it was."""
    engine = InferenceEngineV2(
        model=build_model(preset), dtype=jnp.float32, rng=jax.random.PRNGKey(5),
        config=RaggedInferenceEngineConfig(
            kv_block_size=16, num_kv_blocks=16,
            state_manager=DSStateManagerConfig(max_ragged_batch_size=16,
                                               max_ragged_sequence_count=2,
                                               max_tracked_sequences=2, max_context=64)))
    cfg = engine.model_config
    mixer, pools = MIXERS[preset](cfg, 3)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(13, cfg.hidden_size)), jnp.float32)
    steps = [(x[:6], _step([(0, 0, 6)], 2, [2])), (x[6:12], _step([(0, 6, 6)], 2, [2])),
             (x[12:], _step([(0, 12, 1)], 2, [2]))]

    def run():
        call = jax.jit(lambda rows, state, batch: mixer(engine.params, cfg, jnp.int32(0), rows,
                                                        *state, batch))
        state, out = pools, []
        for rows, batch in steps:
            y, *state = call(rows, state, batch)
            out.append((y, [pool[:, 1:] for pool in state]))
        return out

    got = run()
    monkeypatch.setattr(model_runner, "_conv_with_tail", former_conv_with_tail)
    for (y, state), (former_y, former_state) in zip(got, run()):
        # float32 rows: the taps are the same bits, a compiler's contractions a last place
        np.testing.assert_allclose(y, former_y, rtol=2e-6, atol=2e-6)
        np.testing.assert_array_equal(_bits(state[-1]), _bits(former_state[-1]))      # the tails
        for ours, theirs in zip(state[:-1], former_state[:-1]):
            np.testing.assert_allclose(ours, theirs, rtol=2e-6, atol=2e-6)
