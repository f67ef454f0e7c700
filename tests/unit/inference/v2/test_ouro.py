"""Ouro through the v2 ragged engine at the debug preset: the served logits
against the plain float32 reference, every pass's stream and gate, and the
controls a comparison with that reference has to catch.

The served path runs the three-layer stack four times over the same weights
with the paged pools **twelve layers deep** (pass ``u``, layer ``l`` at pool
layer ``3 u + l``); the reference (``models/ouro.reference_forward``) runs
whole sequences with no cache at all. They share no line.

Tolerances: float32 engines on the CPU differ from the reference by the
order of float32 additions (relative L2 errors of 3-7e-7 were read when this
was written); ``TOL`` = 2e-5. ``CLOSE`` = 0.03 is what the bfloat16 engine is
held to (it read 0.006-0.012) and what every control has to pass by a wide
margin (the smallest read 0.17).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import PrefixCacheConfig, model_runner
from deepspeed_tpu.models import OURO_CONFIGS
from deepspeed_tpu.models import ouro
from deepspeed_tpu.models.ouro import OuroConfig, param_shapes, reference_forward
from deepspeed_tpu.utils import tracing

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Gateway, Plan, Refused, count, rel_err,
                                     second_engine, serve, two_pool_subsystems, two_sequences,
                                     uniform_tokens)

CLOSE = 0.03
DEBUG = OURO_CONFIGS["ouro-debug"]
KIND = model_runner.OuroKind
BLOCK = 8
R, L = DEBUG.total_ut_steps, DEBUG.num_hidden_layers

CASE = Case(
    preset="ouro-debug", block=BLOCK, blocks=64, context=128, rng=7,
    tokens=uniform_tokens(11, (4, 96)),
    reference=lambda params, ids, cfg, prompt: reference_forward(params, ids, cfg).logits,
    refused=tuple(Refused(*row) for row in (
        ("layer_types", ("full_attention", "sliding_attention", "full_attention")),
        ("use_sliding_window", True), ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
        ("tie_word_embeddings", True), ("total_ut_steps", 0))),
    # a prompt of 31 tokens in one chunk or cut at block edges and inside blocks, then six rows
    prefill=((31, 6, [31]), (31, 6, [17, 14]), (31, 6, [8, 16, 7]), (31, 6, [1, 24, 6]),
             (31, 6, [16, 15])),
    plans={"two_prompts_in_one_chunk_beside_decoding_sequences": Plan(
        [[(1, 0, 0, 20), (2, 1, 0, 9)],
         [(1, 0, 20, 21), (2, 1, 9, 10), (3, 2, 0, 14), (4, 3, 0, 15)],
         [(1, 0, 21, 22), (2, 1, 10, 11), (3, 2, 14, 15), (4, 3, 15, 16)]],
        {1: (0, 20), 2: (1, 9), 3: (2, 14), 4: (3, 15)})},
    burst=Burst(2, 0, 30, (5,), {"n_stack_passes": 5 * R, "n_loop_token_layers": 5 * R * L,
                                 "n_exit_early_rows": 0}, 1e-4, 4),
    records=two_sequences({"n_stack_passes": R, "n_loop_token_layers": 29 * R * L,
                           "n_exit_early_rows": 0}),
    step_counts=("n_stack_passes", "n_loop_token_layers", "n_exit_early_rows"),
    scopes=("ds.ouro.attn", "ds.ouro.mlp", "ds.ouro.loop_norm", "ds.ouro.gate"),
    # what a stack run several times does not serve: its state is keys and values, the rest it keeps
    subsystems=two_pool_subsystems()[3:],
    gateway=Gateway(((0, 30), (1, 9)), 6))
TOL = CASE.tol


@pytest.fixture(scope="module")
def forward(engine, tokens):
    """The reference's whole forward of the four sequences, once a module."""
    return jax.tree.map(np.asarray, reference_forward(engine.params, jnp.asarray(tokens),
                                                      DEBUG))


def in_chunks(seq, cuts, decode_from):
    """One sequence's plan: chunks cut at ``cuts``, single rows from ``decode_from``."""
    edges = [0, *cuts, decode_from]
    return ([seq[a:b] for a, b in zip(edges, edges[1:])]
            + [seq[i:i + 1] for i in range(decode_from, len(seq))])


# ------------------------------------------------------------- the model file
def test_the_presets_are_the_published_stack_and_a_small_one_of_its_pattern():
    cfg = OURO_CONFIGS["ouro-2.6b"]
    # the catalog row's keys (https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json)
    row = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
           "max_position_embeddings": 65536, "max_window_layers": 48,
           "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
           "sliding_window": None, "tie_word_embeddings": False, "total_ut_steps": 4,
           "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152}
    assert {k: getattr(cfg, k) for k in row} == row
    assert OuroConfig(layer_types=["full_attention"] * 48).layer_types == ("full_attention",) * 48
    n = count(param_shapes(cfg))
    # the layers counted ONCE: 48 x (4 x 2048^2 + 3 x 2048 x 5632 + 4 x 2048), two tables of
    # 49,152 x 2048, the model's norm and a gate of 2049
    assert n == 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2049
    assert round(n / 1e9, 3) == 2.668
    assert cfg.state_layers == 192 and KIND.state_layers(cfg) == 192
    assert KIND.state_rows(cfg) == (2048, 2048)
    # the small one keeps the pattern: several passes, a query group of one, four norms a block
    assert DEBUG.total_ut_steps == 4 and DEBUG.num_attention_heads == DEBUG.num_key_value_heads
    assert set(param_shapes(DEBUG)["model"]["layers"]) == {
        "input_layernorm", "input_layernorm_2", "post_attention_layernorm",
        "post_attention_layernorm_2", "self_attn", "mlp"}


def test_the_flax_module_is_the_reference_and_every_pass_differs(model, engine, tokens, forward):
    logits = model.apply({"params": engine.params}, jnp.asarray(tokens[:1]))
    assert rel_err(logits[0], forward.logits[0]) < 1e-6
    # at the published threshold every row leaves after the last pass, whose stream is no
    # earlier pass's: the passes do something
    assert (forward.exit_step == R - 1).all()
    for u in range(R - 1):
        assert rel_err(forward.passes[u], forward.passes[R - 1]) > 0.1
    assert ((forward.gates > 0.2) & (forward.gates < 0.8)).all()


def test_the_exit_step_is_the_first_pass_whose_cumulative_probability_reaches_the_threshold():
    g = jnp.asarray([[0.3, 0.9, 0.1], [0.5, 0.05, 0.2], [0.9, 0.1, 0.3], [0.7, 0.7, 0.7]])
    # a column a token; p: [0.3, 0.9, 0.1], [0.35, 0.005, 0.18], [0.315, 0.0095, 0.216], the
    # rest; summed: [0.3, 0.9, 0.1], [0.65, 0.905, 0.28], [0.965, 0.9145, 0.496]
    assert ouro.exit_steps(g, 0.6).tolist() == [1, 0, 3]
    assert ouro.exit_steps(g, 0.95).tolist() == [2, 3, 3]
    assert ouro.exit_steps(g, 1.0).tolist() == [3, 3, 3]
    assert ouro.exit_steps(g, 0.0).tolist() == [0, 0, 0]
    assert ouro.exit_steps(g[:1], 0.0).tolist() == [0, 0, 0]          # one pass: nothing to choose


# ---------------------------------------------------------- the served logits
def _batch(seq_rows, n_rows, table):
    """``seq_rows``: (first position, length) of sequence 0's rows in this
    step; the rest of ``n_rows`` is padding's."""
    first, n = seq_rows
    seq = np.full(n_rows, 1, np.int32)
    seq[:n] = 0
    pos = np.zeros(n_rows, np.int32)
    pos[:n] = np.arange(first, first + n)
    tables = np.zeros((2, len(table)), np.int32)
    tables[0] = table
    return {"token_seq": jnp.asarray(seq), "token_pos": jnp.asarray(pos),
            "block_tables": jnp.asarray(tables)}


def test_every_pass_s_stream_and_gate_through_the_pools(engine, tokens, forward):
    """``OuroKind.passes`` - the step programs' own stack - over a prompt in
    two chunks and six single rows: ``x_u`` and ``g_u`` of every pass at every
    row are the reference's, and every one of the ``R L`` pool layers was
    written."""
    seq = tokens[1][:46]
    params, embed = engine.params, engine.params["model"]["embed_tokens"]
    shape = (KIND.state_layers(DEBUG), 8, BLOCK, DEBUG.num_key_value_heads * DEBUG.head_dim)
    kc, vc = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    table = [3, 1, 6, 2, 5, 7]
    step = jax.jit(lambda kc, vc, ids, batch: KIND.passes(params, DEBUG, embed[ids], kc, vc, batch))
    for first, n, rows in [(0, 27, 32), (27, 13, 32)] + [(40 + i, 1, 4) for i in range(6)]:
        ids = np.zeros(rows, np.int32)
        ids[:n] = seq[first:first + n]
        x, g, kc, vc = step(kc, vc, jnp.asarray(ids), _batch((first, n), rows, table))
        assert x.shape == (R, rows, DEBUG.hidden_size) and g.shape == (R, rows)
        for u in range(R):
            assert rel_err(x[u, :n], forward.passes[u, 1, first:first + n]) < TOL, (first, u)
        assert np.abs(np.asarray(g[:, :n]) - forward.gates[:, 1, first:first + n]).max() < TOL
    written = np.asarray(jnp.any(kc[:, 3] != 0, axis=(1, 2)))
    assert written.all() and written.shape == (R * L,)


def test_a_freed_sequence_s_blocks_are_reused_by_the_next_owner(engine, tokens, forward):
    engine = second_engine(CASE, engine, blocks=6)   # five blocks beside padding's: 40 positions
    free = engine.state_manager.free_blocks
    serve(engine, [[(10, tokens[2][:30])]])
    held = list(engine.state_manager.query(10).blocks)
    engine.flush(10)
    assert engine.state_manager.free_blocks == free == 5
    rows = serve(engine, [[(11, tokens[3][:25])], [(11, tokens[3][25:26])]])[11]
    # stale rows of all twelve layers under the new owner's
    assert len(set(engine.state_manager.query(11).blocks) & set(held)) >= 3
    assert rel_err(rows[0], forward.logits[3, 24]) < TOL
    assert rel_err(rows[1], forward.logits[3, 25]) < TOL
    engine.flush(11)


def test_a_bfloat16_engine_reads_close(engine, tokens, forward):
    served = second_engine(CASE, engine, dtype=jnp.bfloat16)
    assert served.kv_cache.k.dtype == jnp.bfloat16
    seq = tokens[0]
    rows = serve(served, [[(1, part)] for part in in_chunks(seq[:44], (19,), 38)])[1]
    for row, end in zip(rows, [18, 37, 38, 39, 40, 41, 42, 43]):
        assert rel_err(row, forward.logits[0, end]) < CLOSE, end


# ------------------------------------------------------ the controls that must fail
def test_passes_that_share_one_cache_are_seen_from_the_second_chunk_on(engine, tokens, forward,
                                                                      monkeypatch):
    """Every pass reading and writing pool layers ``0 .. L - 1`` (the
    cache-sharing approximation the published code does not make): a
    one-chunk prefill still agrees - a pass's rows are all its own - and a
    second chunk and the first decode step do not."""
    monkeypatch.setattr(KIND, "pool_layers",
                        staticmethod(lambda cfg, u: jnp.arange(L, dtype=jnp.int32)))
    shared = second_engine(CASE, engine)
    seq = tokens[0]
    rows = serve(shared, [[(1, seq[:30])], [(1, seq[30:31])], [(2, seq[:20])], [(2, seq[20:30])]])
    assert rel_err(rows[1][0], forward.logits[0, 29]) < TOL
    assert rel_err(rows[2][0], forward.logits[0, 19]) < TOL
    assert rel_err(rows[1][1], forward.logits[0, 30]) > CLOSE       # the first decode step
    assert rel_err(rows[2][1], forward.logits[0, 29]) > CLOSE       # a second chunk


def _float8(params):
    def rounded(w):
        scale = jnp.max(jnp.abs(w)) / float(jnp.finfo(jnp.float8_e4m3fn).max)
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return jax.tree.map(rounded, params)


CONTROLS = {
    "loop_norm_left_out": lambda p, ids: reference_forward(p, ids, DEBUG, (ouro.LOOP_NORM,)),
    "sandwich_norms_left_out": lambda p, ids: reference_forward(p, ids, DEBUG,
                                                                (ouro.SANDWICH_NORMS,)),
    "one_loop_fewer": lambda p, ids: reference_forward(
        p, ids, dataclasses.replace(DEBUG, total_ut_steps=R - 1)),
    "float8": lambda p, ids: reference_forward(_float8(p), ids, DEBUG),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_a_control_reads_far_from_the_reference(engine, tokens, forward, name):
    """What a program with that fault would give, at every position: beyond
    what the bfloat16 engine is held to, by a wide margin."""
    got = np.asarray(CONTROLS[name](engine.params, jnp.asarray(tokens[:1])).logits[0])
    errors = [rel_err(got[i], forward.logits[0, i]) for i in range(got.shape[0])]
    assert min(errors) > 4 * CLOSE, (name, min(errors))


# --------------------------------------------------- a threshold under 1, one pass
def test_a_threshold_under_one_sends_rows_to_the_head_after_different_passes(engine, tokens):
    cfg = dataclasses.replace(DEBUG, early_exit_threshold=0.45)
    want = jax.tree.map(np.asarray, reference_forward(engine.params, jnp.asarray(tokens[:2]), cfg))
    assert len(set(want.exit_step.ravel().tolist())) >= 2 and want.exit_step.max() < R - 1
    served = second_engine(CASE, engine, cfg)
    rows = serve(served, [[(1, part)] for part in in_chunks(tokens[0][:30], (11,), 24)])[1]
    ends = [10, 23, 24, 25, 26, 27, 28, 29]
    assert len({int(want.exit_step[0, e]) for e in ends}) >= 2
    for row, end in zip(rows, ends):
        assert rel_err(row, want.logits[0, end]) < TOL, end
    # the counter an exit under the last pass moves: this step's one row, if it left early
    assert served.last_step.counts["n_exit_early_rows"] == int(want.exit_step[0, 29] < R - 1)
    # two sequences' rows in one step, each row by its own exit step
    assert want.exit_step[1, 12] != want.exit_step[0, 13]
    rows = serve(served, [[(2, tokens[1][:13]), (3, tokens[0][:14])]])
    assert rel_err(rows[2][0], want.logits[1, 12]) < TOL
    assert rel_err(rows[3][0], want.logits[0, 13]) < TOL
    assert served.last_step.counts["n_exit_early_rows"] == 27


def test_one_pass_is_the_same_block_run_once(engine, tokens):
    cfg = dataclasses.replace(DEBUG, total_ut_steps=1)
    served = second_engine(CASE, engine, cfg)
    assert served.kv_cache.k.shape[0] == L
    want = np.asarray(reference_forward(engine.params, jnp.asarray(tokens[:1]), cfg).logits[0])
    # the same block run once, written out: the layers, the model's norm, the head
    h = engine.params["model"]["embed_tokens"][jnp.asarray(tokens[:1])]
    with jax.default_matmul_precision("highest"):
        for layer in range(L):
            h = ouro.reference_layer(jax.tree.map(lambda w: w[layer],
                                                  engine.params["model"]["layers"]), h, cfg)
        h = ouro._rms_norm(h, engine.params["model"]["norm"]["scale"], cfg.rms_norm_eps)
        once = np.asarray(h @ engine.params["lm_head"]["kernel"])[0]
    assert rel_err(want, once) < 1e-6
    rows = serve(served, [[(1, part)] for part in in_chunks(tokens[0][:24], (9,), 21)])[1]
    for row, end in zip(rows, [8, 20, 21, 22, 23]):
        assert rel_err(row, want[end]) < TOL, end
    assert served.last_step.counts["n_stack_passes"] == 1


# ------------------------------------------------- the pool, the gate, refusals
def test_the_pool_is_r_times_l_layers_deep_and_whoever_sizes_it_counts_them(engine):
    cache = engine.kv_cache
    width = DEBUG.num_key_value_heads * DEBUG.head_dim
    assert cache.num_layers == R * L == 12 and engine.param_layers == L
    assert cache.k.shape == cache.v.shape == (R * L, 64, BLOCK, width)
    assert engine.state_bytes_per_token == cache.bytes_per_token() == R * L * 2 * width * 4
    assert cache.bytes() == 64 * BLOCK * engine.state_bytes_per_token
    assert engine.params["model"]["layers"]["mlp"]["up_proj"]["kernel"].shape[0] == L
    # the gate commits a request's prompt, and refuses what cannot run alone, in blocks of
    # R L layers each: the blocks are the allocator's, whatever their depth
    from deepspeed_tpu.serving.admission import CapacityGate
    gate = CapacityGate(engine, engine.max_tokens)
    assert gate.prompt_blocks(33) == -(-34 // BLOCK) and gate.footprint(33, 20) == -(-53 // BLOCK)
    assert gate.try_commit(1, 33, 20) and gate.committed_blocks == gate.prompt_blocks(33)
    assert gate.headroom() == gate.usable_blocks - gate.prompt_blocks(33)
    gate.release(1)
    assert gate.usable_blocks * BLOCK * engine.state_bytes_per_token <= cache.bytes()


def test_the_prefix_cache_shares_blocks_of_all_the_passes(engine, tokens, forward):
    """A block is ``R L`` layers of a run of positions, so a shared prefix's
    blocks carry every pass's keys and values to the next request."""
    served = second_engine(CASE, engine, prefix_cache=PrefixCacheConfig(enabled=True))
    seq = tokens[0]
    serve(served, [[(1, seq[:32])], [(1, seq[32:40])]])
    served.flush(1)
    matched = served.prefix_match(2, seq[:37])
    assert matched == 32                                     # four whole blocks of eight
    rows = serve(served, [[(2, seq[matched:37])], [(2, seq[37:38])]])[2]
    assert rel_err(rows[0], forward.logits[0, 36]) < TOL
    assert rel_err(rows[1], forward.logits[0, 37]) < TOL


class TestServing(conformance.Served):
    def after_the_rows(self, engine, uid, row, fed, reference):
        """A burst of 5 from the next token on: the engine's own (greedy)
        tokens, judged by the reference's logits on the sequence they make."""
        burst = engine.decode_burst([uid], [int(row[fed])], 5)[:, 0]
        full = np.concatenate([row[:fed + 1], burst])
        want = reference(full)
        assert burst.tolist() == np.argmax(want[fed:fed + 5], axis=-1).tolist()
        after = engine.put([uid], [full[-1:]])[0]                # through what the burst wrote
        assert rel_err(after, want[-1]) < TOL

    def recorded(self, engine, tokens):
        assert engine.decode_burst([60, 61], [1, 2], 4).shape == (4, 2)
        assert engine.last_step.counts == {"n_stack_passes": 4 * R, "n_loop_token_layers":
                                           4 * 2 * R * L, "n_exit_early_rows": 0}

    def around_the_traffic(self, gateway, served):
        yield
        assert served.free_blocks == served.kv_cache.num_blocks - 1      # every block came back
        yield
        records = [r for r in tracing.snapshot()["steps"] if r["engine"] == served.trace_id]
        assert all(r["counts"]["n_stack_passes"] % R == 0 for r in records
                   if r["kind"] in ("burst", "put"))
