"""MiniCPM-SALA through the v2 ragged engine at the debug preset: the
served logits against the plain float32 reference, and the pieces alone.

The served path keeps the sparse layers' keys and values in paged pools
(a pool layer a key-value head), scores group means out of a third pool,
hands the paged attention a table of the selected blocks, and carries the
linear layers' states in a slot pool through packed steps; the reference
(``models/minicpm_sala.reference_logits``) runs whole sequences with a
decay matrix and an explicit top-k mask. They share no line.

Tolerances as ``test_longcat.py``: float32 engines on the CPU, so the two
differ by the order of float32 additions (relative L2 errors of 1.7-2.3e-7
were read when this was written); ``TOL`` = 2e-5 is a hundred times that.
"""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, model_runner
from deepspeed_tpu.inference.v2.ragged.slot_pool import SlotPool
from deepspeed_tpu.models import MINICPM_SALA_CONFIGS, build_model
from deepspeed_tpu.models import minicpm_sala
from deepspeed_tpu.models.minicpm_sala import (LINEAR, PUBLISHED_MIXER_TYPES, SPARSE,
                                               param_shapes, reference_logits,
                                               reference_recurrence, reference_selection)
from deepspeed_tpu.ops.pallas.paged_attention import (SELECTED_SLOT_BYTES, paged_decode_attention,
                                                      selected_tables, tile_blocks,
                                                      xla_paged_attention)

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Plan, Refused, count, engine_config, rel_err,
                                     second_engine, serve)

DEBUG = MINICPM_SALA_CONFIGS["minicpm-sala-debug"]
BLOCK = DEBUG.sparse_block_size
LL = len(DEBUG.linear_positions)
HEADS_LAYERS = DEBUG.num_key_value_heads * len(DEBUG.sparse_positions)

CASE = Case(
    preset="minicpm-sala-debug", block=BLOCK,
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg,
                                                                 prompt_len=prompt),
    refused=tuple(Refused(*row) for row in (
        ("attn_use_rope", True), ("lightning_use_rope", False), ("qk_norm", False),
        ("use_output_norm", False), ("use_output_gate", False), ("attn_use_output_gate", False),
        ("attention_bias", True), ("tie_word_embeddings", True), ("hidden_act", "gelu"),
        ("lightning_nkv", 2), ("lightning_scale", "1"), ("sparse_kernel_size", 12),
        ("sparse_block_size", 18), ("sparse_topk", 2), ("mixer_types", (SPARSE,) * 6),
        ("mixer_types", (SPARSE, LINEAR, "mamba", LINEAR, SPARSE, LINEAR)))),
    prefill=((100, 6, (32, 32, 32, 4)),    # a prompt over dense_len: sparse from its first row
             (100, 4, (7, 32, 29, 32)),    # the same, chunks that end inside blocks and kernels
             (40, 30, (32, 8)),    # under dense_len: dense, then sparse once the context is 64
             (64, 3, (32, 32))),           # exactly dense_len
    # a step's 32 rows hold the end of one prompt, the start of the next and two decode rows;
    # every sequence has its own slot and blocks; prompts 1 and 2 are over dense_len
    plans={"two_sequences_in_one_chunk_beside_decoding_ones": Plan(
        [[(3, 2, 0, 20)], [(4, 3, 0, 32)], [(4, 3, 32, 64)], [(4, 3, 64, 66)],
         [(3, 2, 20, 21), (4, 3, 66, 67), (1, 0, 0, 30)],
         [(3, 2, 21, 22), (4, 3, 67, 68), (1, 0, 30, 60)],
         [(3, 2, 22, 23), (4, 3, 68, 69), (1, 0, 60, 70), (2, 1, 0, 19)],   # two prompts a chunk
         [(3, 2, 23, 24), (4, 3, 69, 70), (1, 0, 70, 71), (2, 1, 19, 48)],
         [(1, 0, 71, 72), (2, 1, 48, 78)], [(2, 1, 78, 90)], [(2, 1, 90, 91), (1, 0, 72, 73)]],
        {1: (0, 70), 2: (1, 90), 3: (2, 20), 4: (3, 66)})},
    burst=Burst(1, 0, 80, (8, 8), {"n_linear_rows": 8 * LL}),
    # rows 64..95 of a sparse-from-0 sequence: each reads topk of its five or six blocks
    records=Plan([[(60, 2, 0, 32)], [(60, 2, 32, 64)], [(60, 2, 64, 96)]], {60: (2, 100)}, {
        "n_blocks_selected": HEADS_LAYERS * 32 * DEBUG.sparse_topk,
        "n_blocks_context": HEADS_LAYERS * sum(p // BLOCK + 1 for p in range(64, 96)),
        "n_linear_rows": 32 * LL}),
    step_counts=("n_blocks_selected", "n_blocks_context", "n_linear_rows"),
    scopes=("ds.sala.select", "ds.sala.sparse_attn", "ds.sala.linear"),
    # a slot: a linear layer's state a head, float32 (the pooled keys are rows of blocks)
    state_extra=("slots", "pooled_keys"),
    slot_bytes=LL * 4 * DEBUG.lightning_nh * DEBUG.lightning_head_dim ** 2)
TOL = CASE.tol


# ------------------------------------------------------------- the model file
def test_the_presets_are_the_published_pattern_and_its_cut():
    cut = MINICPM_SALA_CONFIGS["minicpm-sala-16l"]
    assert PUBLISHED_MIXER_TYPES.count(SPARSE) == 8 and len(PUBLISHED_MIXER_TYPES) == 32
    assert cut.mixer_types == PUBLISHED_MIXER_TYPES[0::2] and cut.layer_ids == tuple(range(0, 32, 2))
    assert cut.sparse_positions == (0, 8, 11, 15) and len(cut.linear_positions) == 12
    assert (cut.hidden_size, cut.num_attention_heads, cut.num_key_value_heads, cut.head_dim,
            cut.intermediate_size, cut.vocab_size) == (4096, 32, 2, 128, 16384, 73448)
    # the published depth stays in the formulas: the residual scale and the decay
    assert cut.residual_scale == pytest.approx(1.4 / math.sqrt(32))
    assert cut.log_decay(1)[31] == pytest.approx(-(8 / 32) * (1 - 2 / 32) * 31)
    assert cut.log_decay(1)[0] == 0.0                       # head 0 never forgets
    assert DEBUG.mixer_types.count(SPARSE) == 2 and DEBUG.mixer_types.count(LINEAR) == 4
    assert model_runner.kind_of(DEBUG) is model_runner.SalaKind
    assert 5.03e9 < count(param_shapes(cut)) < 5.05e9   # ISSUE 34's count: 5.04 B


# ------------------------------------------------------------- the slot pool
def test_a_stale_state_would_move_every_row(engine, tokens):
    """The control of the shared test of a reused slot: the rows of a next
    owner over the state the last one left are far off."""
    serve(engine, [[(11, tokens[2][:30])]])
    slot = engine.state_manager.query(11).state_row[0]
    engine.flush(11)
    carried = jnp.asarray(np.asarray(engine.state_extra["slots"][:, slot])[0])
    x = jax.random.normal(jax.random.PRNGKey(0), (30, DEBUG.num_attention_heads, DEBUG.head_dim))
    moved = jnp.einsum("thd,hde->the", x, carried)
    assert float(jnp.abs(moved).max()) > 1e-2


def test_the_slot_pool_hands_out_every_slot_but_paddings():
    pool = SlotPool(3, bytes_per_slot=64)
    got = [pool.acquire() for _ in range(3)]
    assert sorted(got) == [1, 2, 3] and pool.free_slots == 0 and pool.bytes() == 4 * 64
    with pytest.raises(RuntimeError, match="slot pool exhausted"):
        pool.acquire()
    pool.release(2)
    assert pool.acquire() == 2
    with pytest.raises(ValueError, match="not an owned slot"):
        pool.release(0)


def test_a_first_chunk_of_a_prompt_the_engine_was_not_told_is_refused(engine, reference, tokens):
    """How a prompt's rows attend depends on the whole prompt's length
    (``dense_len``), which a first chunk does not say: ``put`` takes no
    chunk of a sequence that ``prefix_match`` did not announce, long or
    short, and tracks nothing of it; a sequence announced and never put
    gives its slot back when flushed, and its uid starts anew."""
    seq = tokens[0][:100]                                 # over dense_len (64)
    for chunk in (seq[:32], seq[:5]):
        with pytest.raises(ValueError, match="needs the whole prompt.*prefix_match"):
            engine.put([31], [chunk])
    assert engine.state_manager.query(31) is None
    assert engine.slot_pool.free_slots == engine.slot_pool.slots
    engine.prefix_match(31, seq[:20])                     # announced short, then cancelled
    assert engine.slot_pool.free_slots == engine.slot_pool.slots - 1
    assert engine.state_manager.query(31).state_row[1] == DEBUG.sparse_dense_len - 1
    engine.flush(31)
    assert engine.slot_pool.free_slots == engine.slot_pool.slots
    rows = serve(engine, [[(31, seq[:32])], [(31, seq[32:64])], [(31, seq[64:96])],
                          [(31, seq[96:100])]], {31: seq})[31]   # the same uid, a long prompt now
    assert engine.state_manager.query(31).state_row[1] == 0
    engine.flush(31)
    want = reference(seq, 100)
    assert max(rel_err(r, want[p]) for r, p in zip(rows, (31, 63, 95, 99))) < TOL


def test_the_engine_and_the_gate_admit_on_slots(engine, tokens):
    from deepspeed_tpu.serving.admission import CapacityGate
    small = second_engine(CASE, engine, tracked=2)
    assert small.slot_pool.slots == 2 and CapacityGate(small, 32).max_tracked == 2
    serve(small, [[(1, tokens[0][:4]), (2, tokens[1][:4])]])
    with pytest.raises(RuntimeError, match="max_tracked_sequences"):   # a slot a tracked sequence
        small.prefix_match(3, tokens[2][:4])
    small.flush(1)
    serve(small, [[(3, tokens[2][:4])]])
    assert small.slot_pool.free_slots == 0
    assert small.state_extra["slots"].shape == (4, 3, 4, 16, 16)
    assert small.state_extra["slots"].dtype == jnp.float32


# --------------------------------------------------------- the pieces alone
def _step_context(cfg, seqs, first, lengths, n_rows, max_blocks, slots, sparse_from=0):
    """A batch of ``len(seqs)`` sequences' rows: sequence ``i`` at
    positions ``first[i] .. first[i] + lengths[i] - 1``, blocks 1.. laid
    out a sequence after the other."""
    token_seq, token_pos = [], []
    tables = np.zeros((n_rows, max_blocks), np.int32)
    nxt = 1
    for i, (f, n) in enumerate(zip(first, lengths)):
        token_seq += [i] * n
        token_pos += list(range(f, f + n))
        need = -(-(f + n) // cfg.sparse_block_size)
        tables[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    state = np.zeros((n_rows, 2), np.int32)
    state[:len(seqs), 0] = slots
    state[:, 1] = sparse_from
    return {"token_seq": jnp.asarray(token_seq, jnp.int32),
            "token_pos": jnp.asarray(token_pos, jnp.int32),
            "block_tables": jnp.asarray(tables), "seq_state": jnp.asarray(state)}, nxt


def test_the_packed_linear_step_is_the_recurrence_token_by_token(engine):
    """Rows of three sequences in one step — a prompt's first chunk, a
    later chunk over a carried state, a decode row over another — against
    ``S_t = lambda S_{t-1} + k_t^T v_t; o_t = q_t S_t`` run a token at a
    time from each sequence's carried state."""
    cfg = engine.model_config
    H, d, D = cfg.num_attention_heads, cfg.head_dim, cfg.hidden_size
    batch, _ = _step_context(cfg, [0, 1, 2], first=[0, 21, 57], lengths=[9, 14, 1], n_rows=5,
                             max_blocks=8, slots=[2, 1, 3])
    ctx = model_runner._SalaStep(cfg, batch)
    lp = jax.tree.map(lambda w: w[1], engine.params["model"]["linear_layers"])
    log_decay = jnp.asarray(cfg.log_decay(cfg.linear_positions[1]), jnp.float32)
    rng = jax.random.PRNGKey(4)
    h = jax.random.normal(rng, (24, D), jnp.float32)
    slots = jax.random.normal(jax.random.fold_in(rng, 1), (4, 4, H, d, d), jnp.float32)
    got_h, got_slots = model_runner._sala_linear_layer(ctx, lp, log_decay, 1, h, slots)

    a = lp["self_attn"]
    x = model_runner._rms(h, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
    pos = batch["token_pos"]
    q = model_runner._rope_at(model_runner._rms((x @ a["q_proj"]["kernel"]).reshape(24, H, d),
                                                a["q_norm"]["scale"], cfg.rms_norm_eps),
                              pos, cfg.rope_theta)
    k = model_runner._rope_at(model_runner._rms((x @ a["k_proj"]["kernel"]).reshape(24, H, d),
                                                a["k_norm"]["scale"], cfg.rms_norm_eps),
                              pos, cfg.rope_theta)
    v = (x @ a["v_proj"]["kernel"]).reshape(24, H, d)
    lam = jnp.exp(log_decay)[:, None, None]
    outs, want_slots = [], np.asarray(slots).copy()
    for rows, slot, fresh in ((slice(0, 9), 2, True), (slice(9, 23), 1, False),
                              (slice(23, 24), 3, False)):
        state = jnp.zeros((H, d, d)) if fresh else slots[1, slot]
        for t in range(rows.start, rows.stop):
            state = lam * state + k[t][:, :, None] * v[t][:, None, :]
            outs.append(jnp.einsum("hd,hde->he", q[t], state))
        want_slots[1, slot] = np.asarray(state)
    o = (jnp.stack(outs) / math.sqrt(d)).reshape(24, H * d)
    o = model_runner._rms(o, a["o_norm"]["scale"], cfg.rms_norm_eps) \
        * jax.nn.sigmoid(x @ a["o_gate_proj"]["kernel"])
    want_h = h + cfg.residual_scale * (o @ a["o_proj"]["kernel"])
    want_h = model_runner._sala_mlp(cfg, lp, want_h)
    assert rel_err(got_h, want_h) < TOL
    # layer 1's slots of the three sequences hold what the recurrence left; the other
    # layers' and the unowned slot's (padding's may hold anything) are untouched
    got_slots = np.asarray(got_slots)
    assert rel_err(got_slots[1, 1:], want_slots[1, 1:]) < TOL
    assert np.array_equal(got_slots[[0, 2, 3]], np.asarray(slots)[[0, 2, 3]])
    # and the recurrence is what the reference's decay matrix writes out
    whole = reference_recurrence(q[:9], k[:9], v[:9], log_decay)
    assert rel_err(whole, jnp.stack(outs[:9])) < TOL


def test_the_selection_alone_picks_the_blocks_the_reference_picks(engine, tokens):
    """Sparse layer 1's mixer on a whole prompt in one step: the table
    each (row, key-value head) is given holds the blocks of the
    reference's explicit top-k, ascending, its own block last."""
    cfg = engine.model_config
    S, H, Hkv, d = 150, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    batch, n_blocks = _step_context(cfg, [0], first=[0], lengths=[S], n_rows=2, max_blocks=12,
                                    slots=[1])
    x = jax.random.normal(jax.random.PRNGKey(9), (S, cfg.hidden_size), jnp.float32)
    kc = jnp.zeros((2 * Hkv, 16, BLOCK, d), jnp.float32)
    kb = jnp.zeros((2 * Hkv, 16, BLOCK // cfg.sparse_kernel_stride, d), jnp.float32)
    y, kc, vc, kb, tables, counts = model_runner.SalaKind.sparse_layer(
        engine.params, cfg, 1, x, kc, kc, kb, batch)
    a = engine.params["model"]["sparse_layers"]["1"]["self_attn"]
    q = minicpm_sala._rms_norm((x @ a["q_proj"]["kernel"]).reshape(1, S, H, d),
                               a["q_norm"]["scale"], cfg.rms_norm_eps)
    k = minicpm_sala._rms_norm((x @ a["k_proj"]["kernel"]).reshape(1, S, Hkv, d),
                               a["k_norm"]["scale"], cfg.rms_norm_eps)
    chosen, margin = reference_selection(q, k, cfg)               # [1, Hkv, S, NB]
    chosen, tables, counts = np.asarray(chosen[0]), np.asarray(tables), np.asarray(counts)
    picked_by_score = 0
    for p in range(S):
        for h in range(Hkv):
            want = np.flatnonzero(chosen[h, p])
            n = counts[p, h]
            assert n == len(want) == min(cfg.sparse_topk, p // BLOCK + 1)
            assert list(tables[p, h, :n]) == list(want) and tables[p, h, n - 1] == p // BLOCK
            forced = {0, p // BLOCK, max(p // BLOCK - 1, 0)}
            picked_by_score += len(set(want) - forced)
    assert picked_by_score > 100                 # the scores, not only the forced blocks, decide
    # (two neighbouring blocks share a kernel, so where that kernel is the best of both
    # their scores tie exactly: margin 0, broken towards the earlier block on both sides)
    margin = np.asarray(margin)[np.isfinite(np.asarray(margin))]
    assert margin.min() >= 0 and np.median(margin) > 0
    # the mixer's output is the reference's for those rows
    want_y, _ = minicpm_sala.reference_sparse_attention(a, x[None], cfg, jnp.asarray([0]))
    assert rel_err(y, want_y[0]) < TOL
    # and the pooled-key pool holds the mean of every completed stride-group of key rows
    st = cfg.sparse_kernel_stride
    groups = np.asarray(k[0, :S - S % st]).reshape(-1, st, Hkv, d).mean(axis=1)   # [G, Hkv, d]
    table = np.asarray(batch["block_tables"][0])
    for g in (0, 5, len(groups) - 1):
        blk, sub = table[g * st // BLOCK], g % (BLOCK // st)
        assert rel_err(np.asarray(kb)[2:4, blk, sub], groups[g]) < TOL


SELECTIONS = {
    # bs, NB, W, dtype, positions, counts a (token, key-value head)
    "small": (16, 40, 6, jnp.float32, [3, 40, 95, 64, 200],
              [[1, 1], [3, 2], [6, 4], [5, 5], [6, 6]]),
    # the cell's blocks; float32 rows of one head are 512 B: a tile of 16 blocks, W is four
    "tile_edges_f32": (64, 70, 64, jnp.float32, [5, 1023, 1024, 2111, 4095, 9000],
                       [[1, 1], [15, 16], [17, 16], [32, 33], [64, 48], [63, 64]]),
    # bf16 rows are 256 B: the 32-block tile the cell runs, W is two of them
    "tile_edges_bf16": (64, 70, 64, jnp.bfloat16, [63, 2047, 2048, 20000],
                        [[1, 1], [31, 32], [33, 32], [64, 63]]),
}


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("case", sorted(SELECTIONS))
def test_a_table_that_is_a_selection_reads_those_blocks_and_masks_only_the_last(impl, case):
    """The paged call's contract for a selection (``selected_tables``): a
    (token, key-value head) row with ``count`` pool blocks, its own last,
    attends over exactly those blocks' rows, up to its own row in the last
    — on both implementations, the kernel in interpret mode at the lane
    width Mosaic wants and at the tile a selection is given, with counts
    on, before and after its edges; a block no row names is NaN."""
    bs, NB, W, dtype, pos, counts = SELECTIONS[case]
    pos, counts = np.asarray(pos), np.asarray(counts)
    T, Hkv, G, d = len(pos), 2, 4, 128
    n = tile_blocks(bs, d * jnp.dtype(dtype).itemsize, jnp.dtype(dtype).itemsize, W,
                    SELECTED_SLOT_BYTES)
    assert n == {"small": 6, "tile_edges_f32": 16, "tile_edges_bf16": 32}[case]
    rng = np.random.default_rng(1)
    pool_k = rng.standard_normal((1, Hkv * NB, bs, d)).astype(np.float32)
    pool_v = rng.standard_normal((1, Hkv * NB, bs, d)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((T, Hkv, G, d)), dtype)
    tables = np.zeros((T, Hkv, W), np.int32)
    for t in range(T):
        for h in range(Hkv):
            # distinct blocks of head h's pool layer, in no particular physical order
            tables[t, h, :counts[t, h]] = h * NB + 1 + rng.permutation(NB - 1)[:counts[t, h]]
    unnamed = np.setdiff1d(np.arange(1, Hkv * NB), tables.ravel())
    assert len(unnamed)
    pool_k[0, unnamed] = np.nan
    pool_v[0, unnamed] = np.nan
    pool_k, pool_v = jnp.asarray(pool_k, dtype), jnp.asarray(pool_v, dtype)
    tab, at = selected_tables(jnp.asarray(tables), jnp.asarray(counts), jnp.asarray(pos), bs)
    assert tab.shape == (T * Hkv, W) and list(np.asarray(at)[:2]) == [pos[0] % bs] * 2
    fn = xla_paged_attention if impl == "xla" else \
        functools.partial(paged_decode_attention, interpret=True)
    got = np.asarray(fn(q.reshape(T * Hkv, G, d), pool_k, pool_v, tab, at, jnp.int32(0),
                        selected=True).astype(jnp.float32))
    got = got.reshape(T, Hkv, G, d)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    tol = TOL if dtype == jnp.float32 else 2e-2
    for t in range(T):
        for h in range(Hkv):
            n = counts[t, h]
            rows_k = np.concatenate([f32(pool_k[0, b]) for b in tables[t, h, :n]])
            rows_v = np.concatenate([f32(pool_v[0, b]) for b in tables[t, h, :n]])
            keep = (n - 1) * bs + pos[t] % bs + 1
            s = f32(q[t, h]) @ rows_k[:keep].T / math.sqrt(d)
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            want = (p / p.sum(axis=-1, keepdims=True)) @ rows_v[:keep]
            assert rel_err(got[t, h], want) < tol, (t, h)


# ----------------------------------------------------------- what is refused
def test_a_wrong_block_size_is_refused_by_name(engine, tokens):
    wrong = second_engine(CASE, engine, block=32, blocks=16, sequences=2, context=64)
    with pytest.raises(ValueError, match="kv_block_size 32 is not the selection's block size 16"):
        serve(wrong, [[(1, tokens[0][:5])]])


# ------------------------------------------------------------------- tracing
def test_a_prompts_first_rows_select_every_block_they_have(engine, tokens):
    """Rows 0..31 of a sparse-from-0 sequence: each reads min(topk, its blocks) of its blocks."""
    serve(engine, [[(60, tokens[2][:32])]], {60: tokens[2][:100]})
    blocks = [p // BLOCK + 1 for p in range(32)]
    assert engine.last_step.counts == {
        "n_blocks_selected": HEADS_LAYERS * sum(min(DEBUG.sparse_topk, b) for b in blocks),
        "n_blocks_context": HEADS_LAYERS * sum(blocks), "n_linear_rows": 32 * LL}
    engine.flush(60)


def test_the_kinds_without_further_state_compile_to_the_programs_they_had(engine):
    """``xc`` is None for them: no argument and no result of the compiled
    program, whose inputs are the parameters, the two pools and the packed
    batch, and whose outputs are the logits and the two pools."""
    llama = InferenceEngineV2(model=build_model("debug"), config=engine_config(CASE),
                              dtype=jnp.float32)
    assert llama.state_extra is None and llama.slot_pool is None and llama._seq_rows == 0
    packed = llama._batch.finalize_packed()
    lowered = llama._step.lower(llama.params, llama.kv_cache.k, llama.kv_cache.v, None, packed)
    n_in = len(jax.tree.leaves(llama.params)) + 3
    assert len(jax.tree.leaves(lowered.in_avals)) == n_in
    assert [tuple(x.shape) for x in jax.tree.leaves(lowered.out_info)] == [
        (4, llama.model_config.vocab_size), llama.kv_cache.k.shape, llama.kv_cache.v.shape]
    # and the packed vector has no row of per-sequence state
    assert packed.shape[0] == 3 * 32 + 5 * 12 + 4 + 1
    assert engine._batch.finalize_packed().shape[0] == packed.shape[0] + 5 * 2


class TestServing(conformance.Slots, conformance.NotKV):
    pass
