"""Jamba through the v2 ragged engine at the debug preset: the served
logits against the plain float32 reference, and the pieces alone.

The served path keeps the attention layers' keys and values in paged pools
and every Mamba-1 layer's state - ``N`` columns a channel, float32 - and
convolution tail in a slot a sequence; a step's rows go through
``ops/pallas/selective_scan`` (the kernel, interpreted, under
``DS_PALLAS=1``; its XLA fallback otherwise), each sequence's run from its
own slot. The reference (``models/jamba.reference_logits``) runs whole
sequences, the convolution as shifted products and the recurrence a token
at a time from a zero start. They share no line.

Tolerances: float32 engines on the CPU differ from the reference by the
order of float32 additions (relative L2 errors of 0.7-1.1e-6 were read when
this was written); ``TOL`` = 2e-5. The bfloat16 engine's is written where it
is used.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2, model_runner
from deepspeed_tpu.models import JAMBA_CONFIGS, build_model
from deepspeed_tpu.models.jamba import (JambaConfig, layer_params, param_shapes,
                                        reference_attention, reference_logits,
                                        reference_mamba)

from unit.inference.v2 import kind_conformance as conformance
from unit.inference.v2.kinds import (Burst, Case, Refused, count, engine_config, rel_err,
                                     second_engine, serve, slot_batch, two_prompts,
                                     two_sequences)

DEBUG = JAMBA_CONFIGS["jamba-debug"]
KIND = model_runner.JambaKind
LM = DEBUG.count("m")
# around the convolution's four taps and the scan's blocks of 8
CUTS = (1, 2, 3, 4, 5, 8, 9, 16, 17)

CASE = Case(
    preset="jamba-debug",
    reference=lambda params, ids, cfg, prompt: reference_logits(params, ids, cfg),
    refused=tuple(Refused(*row) for row in (
        ("num_experts", 16), ("hidden_act", "gelu"), ("mamba_conv_bias", False),
        ("mamba_proj_bias", True), ("mamba_d_conv", 1), ("sliding_window", 4096),
        ("tie_word_embeddings", False), ("attn_layer_offset", 5), ("num_attention_heads", 5),
        ("num_key_value_heads", 2))),
    # whole and in chunks (of 1 and 2 rows among them: shorter than the convolution's tail),
    # then decode rows - 24 of them in the last case, so that an error of the state would compound
    prefill=((20, 6, [20]), (75, 12, [32, 32, 11]), (100, 4, [7, 32, 32, 29]), (3, 8, [2, 1]),
             (40, 3, [1, 1, 1, 31, 6]), (62, 3, [30, 31, 1]), (64, 24, [32, 32])),
    cuts=tuple((19, 1, [cut, 19 - cut]) for cut in CUTS),
    # three runs of the scan in the third step, each from its own slot
    plans={"two_prompts_in_one_chunk": two_prompts({
        2: {"n_ssm_rows": 32 * LM, "n_state_slots": 3 * LM, "n_scan_runs": 2 * LM},
        4: {"n_ssm_rows": 2 * LM, "n_state_slots": 2 * LM, "n_scan_runs": 0}})},
    burst=Burst(1, 0, 80, (8, 8),
                {"n_ssm_rows": 8 * LM, "n_state_slots": 8 * LM, "n_scan_runs": 0}),
    records=two_sequences({"n_ssm_rows": 29 * LM, "n_state_slots": 2 * LM,
                           "n_scan_runs": 2 * LM}),
    step_counts=("n_ssm_rows", "n_state_slots", "n_scan_runs"),
    scopes=("ds.jamba.mamba", "ds.jamba.scan", "ds.jamba.attn", "ds.jamba.mlp"),
    # a slot: a layer's state a channel and the convolution's K - 1 rows, float32
    state_extra=("ssm", "conv"), state_step="pallas_selective_scan",
    slot_bytes=LM * 4 * DEBUG.mamba_inner * (DEBUG.mamba_d_state + DEBUG.mamba_d_conv - 1),
    kernel_tests=("test_a_chunk_cut_at_every_offset", "test_sequences_side_by_side_in_a_step",
                  "test_a_slot_is_reused_with_its_stale_state_and_the_next_owner_starts_from_zero"))
TOL = CASE.tol


# ------------------------------------------------------------- the model file
def test_the_presets_are_the_published_stack_and_a_small_one_of_its_pattern():
    whole = JambaConfig()
    assert build_model("jamba2-3b").config == whole
    assert whole.letters == "mmmmmmmammmmmm" * 2
    assert [i for i, t in enumerate(whole.letters) if t == "a"] == [7, 21]
    assert whole.segments == (("m", 7), ("a", 1), ("m", 13), ("a", 1), ("m", 6))
    assert (whole.hidden_size, whole.head_dim, whole.num_attention_heads,
            whole.num_key_value_heads, whole.intermediate_size, whole.mamba_inner,
            whole.mamba_d_state, whole.mamba_d_conv, whole.mamba_dt_rank, whole.vocab_size,
            whole.max_position_embeddings, whole.rms_norm_eps) == (
                2560, 128, 20, 1, 8192, 5120, 16, 4, 160, 65536, 262144, 1e-6)
    assert DEBUG.letters == "mmammmmamm"         # two Mamba layers either side of an attention
    assert DEBUG.segments == (("m", 2), ("a", 1), ("m", 4), ("a", 1), ("m", 2))
    assert DEBUG.num_attention_heads // DEBUG.num_key_value_heads == 3    # no power of two
    assert model_runner.kind_of(DEBUG) is KIND
    # ISSUE 45's count: 26 x 104.1 M + 2 x 76.7 M + 167.8 M = 3.03 B, the head tied
    assert count(param_shapes(whole)) == 3029337472


def test_the_shapes_are_the_catalog_rows():
    shapes = param_shapes(JambaConfig())["model"]
    assert shapes["embed_tokens"] == (65536, 2560) and "lm_head" not in param_shapes(JambaConfig())
    mamba, attn = shapes["mamba_layers"], shapes["attn_layers"]
    assert mamba["in_proj"]["kernel"] == (26, 2560, 10240)
    assert mamba["x_proj"]["kernel"] == (26, 5120, 192)            # dt 160 | B 16 | C 16
    assert mamba["dt_proj"]["kernel"] == (26, 160, 5120)
    assert mamba["conv_kernel"] == (26, 4, 5120) and mamba["A_log"] == (26, 16, 5120)
    assert [mamba[k]["scale"] for k in ("dt_layernorm", "b_layernorm", "c_layernorm")] == [
        (26, 160), (26, 16), (26, 16)]
    assert attn["q_proj"]["kernel"] == (2, 2560, 2560)
    assert attn["k_proj"]["kernel"] == (2, 2560, 128)
    assert shapes["ffn"]["gate_proj"]["kernel"] == (28, 2560, 8192)


def test_the_flax_module_is_the_reference_and_the_seeded_recurrence_lives(model):
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 24), dtype=np.int32))
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    got = model.apply({"params": params}, ids)
    assert got.shape == (2, 24, 256) and got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got), np.asarray(reference_logits(params, ids, DEBUG)))
    # causal: a sequence's later tokens do not reach its earlier logits
    again = model.apply({"params": params}, ids.at[:, 20:].set(0))
    assert rel_err(again[:, :20], got[:, :20]) < 1e-6
    mamba = params["model"]["mamba_layers"]
    # A_log = log(1 .. N) a channel; the step log-uniform in [1e-3, 1e-1]
    np.testing.assert_allclose(np.exp(np.asarray(mamba["A_log"][3, :, 7])), np.arange(1, 17),
                               rtol=1e-6)
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert 9e-4 < step.min() < 2e-3 and 0.05 < step.max() < 0.11
    # neither dead nor exploding: a state after 150 tokens is of its increments' order
    lp = jax.tree.map(lambda w: w[0], mamba)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 150, DEBUG.hidden_size))
    _, state, _ = reference_mamba(lp, x, DEBUG)
    assert 1e-3 < float(jnp.abs(state).max()) < 1e2 and bool(jnp.isfinite(state).all())


# ------------------------------------------------------------ the served types
def test_a_bfloat16_engine_keeps_its_state_in_float32_and_reads_close(model, engine, tokens):
    """The served types: bfloat16 weights, stream, keys, values and tails,
    a float32 state. Against the float32 reference on the same (bfloat16)
    weights the logits differ by bfloat16's rounding of the stream, 2**-8 a
    value, gathered over ten layers: 0.006-0.012 was read; 0.03 holds it and
    is a tenth of what a state carried in bfloat16 over these 40 steps or a
    dropped tail reads (0.3 and more)."""
    served = InferenceEngineV2(model=model, config=engine_config(CASE), dtype=jnp.bfloat16,
                               rng=jax.random.PRNGKey(CASE.rng))
    assert engine.state_extra["ssm"].dtype == served.state_extra["ssm"].dtype == jnp.float32
    assert engine.kv_cache.k.shape[0] == DEBUG.count("a")
    assert served.state_extra["conv"].dtype == jnp.bfloat16
    seq = tokens[2][:100]
    rows = serve(served, [[(5, seq[:32])], [(5, seq[32:60])]]
                 + [[(5, seq[60 + j:61 + j])] for j in range(40)])[5]
    served.flush(5)
    want = np.asarray(reference_logits(served.params, jnp.asarray(seq)[None], DEBUG))[0]
    errs = [rel_err(r, want[p]) for r, p in zip(rows, [31, 59] + list(range(60, 100)))]
    assert max(errs) < 0.03, max(errs)


# --------------------------------------------------------- the pieces alone
_MIXERS = {}


def mamba_layer(state_step, params, layer, x, ssm, conv, batch):
    """``JambaKind.mamba_layer`` at the debug preset, jitted - a program a
    shape for each of ``state_step``'s cases, which is read when a program is
    traced."""
    if state_step not in _MIXERS:
        _MIXERS[state_step] = jax.jit(
            lambda params, layer, x, ssm, conv, batch: KIND.mamba_layer(
                params, DEBUG, layer, x, ssm, conv, batch))
    return _MIXERS[state_step](params, jnp.int32(layer), x, ssm, conv, batch)


def _pools(cfg, slots, fill):
    return (jnp.full((LM, slots + 1, cfg.mamba_d_state, cfg.mamba_inner), fill, jnp.float32),
            jnp.full((LM, slots + 1, cfg.mamba_d_conv - 1, cfg.mamba_inner), fill, jnp.float32))


@pytest.mark.parametrize("chunk", [1, 7, 64, 100])
def test_a_prompt_in_chunks_leaves_the_state_and_the_tail_of_the_recurrence(engine, chunk,
                                                                            state_step):
    """A prompt of 100 rows through Mamba layer 3 one row a call, in chunks
    of 7 and 64 rows and whole, in a slot that held ones: the reference's
    output rows, state and tail."""
    cfg, layer, S = engine.model_config, 3, 100
    x = jax.random.normal(jax.random.PRNGKey(2), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["mamba_layers"])
    with jax.default_matmul_precision("highest"):
        want, state, tail = reference_mamba(lp, x[None], cfg)
    ssm, conv = _pools(cfg, 2, 1.0)
    got = []
    for at in range(0, S, chunk):
        n = min(chunk, S - at)
        y, ssm, conv = mamba_layer(state_step, engine.params, layer, x[at:at + n], ssm, conv,
                                   slot_batch([(0, at, n)], 2, [2]))
        got.append(y)
    assert rel_err(jnp.concatenate(got), want[0]) < TOL
    assert rel_err(ssm[layer, 2], state[0]) < TOL and rel_err(conv[layer, 2], tail[0]) < TOL
    assert np.asarray(ssm[layer, 1] == 1.0).all() and np.asarray(ssm[0] == 1.0).all()


def test_several_runs_in_one_step_each_from_its_own_slot(engine, state_step):
    """Decode rows, runs that go on from a carried slot and runs that start,
    side by side in one call, then padding's rows: every run the
    reference's continuation of its own sequence, the slot of a sequence
    that starts ignored (it held 0.5), a slot no row names untouched."""
    cfg, layer = engine.model_config, 5
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["mamba_layers"])
    key = jax.random.PRNGKey(4)
    before = [30, 12, 0, 9, 0, 1]                # rows each sequence has behind it
    now = [1, 1, 6, 2, 9, 1]
    slots = [3, 1, 6, 2, 5, 4]
    xs = [jax.random.normal(jax.random.fold_in(key, i), (b + n, cfg.hidden_size))
          for i, (b, n) in enumerate(zip(before, now))]
    ssm, conv = _pools(cfg, 7, 0.5)
    want = []
    with jax.default_matmul_precision("highest"):
        for i, (b, n) in enumerate(zip(before, now)):
            state = tail = None
            if b:
                _, state, tail = reference_mamba(lp, xs[i][None, :b], cfg)
                ssm = ssm.at[layer, slots[i]].set(state[0])
                conv = conv.at[layer, slots[i]].set(tail[0])
            want.append(reference_mamba(lp, xs[i][None, b:], cfg, state, tail))
    batch = slot_batch([(i, b, n) for i, (b, n) in enumerate(zip(before, now))] + [(7, 0, 1)] * 4,
                   8, slots)
    x = jnp.concatenate([xs[i][b:] for i, b in enumerate(before)]
                        + [jnp.ones((4, cfg.hidden_size))])
    held = np.asarray(ssm)
    y, ssm, conv = mamba_layer(state_step, engine.params, layer, x, ssm, conv, batch)
    at = 0
    for i, n in enumerate(now):
        out, state, tail = want[i]
        assert rel_err(y[at:at + n], out[0]) < TOL, i
        assert rel_err(ssm[layer, slots[i]], state[0]) < TOL, i
        assert rel_err(conv[layer, slots[i]], tail[0]) < TOL, i
        at += n
    assert np.array_equal(np.asarray(ssm[layer, 7]), held[layer, 7])      # a slot no row names
    assert np.array_equal(np.asarray(ssm[layer, 0]), held[layer, 0])      # padding's
    assert np.array_equal(np.asarray(ssm[0]), held[0])


def test_a_state_carried_in_bfloat16_is_seen(engine):
    """The control of the checks above: the state rounded to bfloat16 after
    every decode row drifts from the float32 recurrence by far more than
    ``TOL``."""
    cfg, layer, S = engine.model_config, 1, 80
    x = jax.random.normal(jax.random.PRNGKey(7), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["mamba_layers"])
    with jax.default_matmul_precision("highest"):
        _, state, _ = reference_mamba(lp, x[None], cfg)
    ssm, conv = _pools(cfg, 2, 0.0)
    for at in range(S):
        _, ssm, conv = mamba_layer("xla", engine.params, layer, x[at:at + 1], ssm, conv,
                                   slot_batch([(0, at, 1)], 2, [2]))
        ssm = ssm.astype(jnp.bfloat16).astype(jnp.float32)
    assert rel_err(ssm[layer, 2], state[0]) > 50 * TOL


def test_the_served_attention_layer_is_the_references(engine):
    """One key-value head under three query heads, no positional term, over
    a chunk cut."""
    cfg, layer, S = engine.model_config, 1, 40
    x = jax.random.normal(jax.random.PRNGKey(6), (S, cfg.hidden_size))
    lp = jax.tree.map(lambda w: w[layer], engine.params["model"]["attn_layers"])
    with jax.default_matmul_precision("highest"):
        want = reference_attention(lp, x[None], cfg)[0]
    shape = (cfg.count("a"), 8, CASE.block, cfg.num_key_value_heads * cfg.head_dim)
    kc, vc = jnp.zeros(shape), jnp.zeros(shape)
    got = []
    for at, n in ((0, 25), (25, 15)):
        batch = slot_batch([(0, at, n)], 2, [1])
        batch["block_tables"] = jnp.asarray([[1, 2, 3], [0, 0, 0]], jnp.int32)
        y, kc, vc = KIND.attention_layer(engine.params, cfg, layer, x[at:at + n], kc, vc, batch)
        got.append(y)
    assert rel_err(jnp.concatenate(got), want) < TOL


def test_layer_params_cuts_each_layers_mixer_and_feed_forward(engine):
    cfg, params = engine.model_config, engine.params
    mixer, ffn = layer_params(params, cfg, 0)
    assert "A_log" in mixer and "gate_proj" in ffn
    mixer, ffn = layer_params(params, cfg, 7)                # letters mmammmmamm: the second 'a'
    assert np.array_equal(np.asarray(mixer["q_proj"]["kernel"]),
                          np.asarray(params["model"]["attn_layers"]["q_proj"]["kernel"][1]))
    assert np.array_equal(np.asarray(ffn["up_proj"]["kernel"]),
                          np.asarray(params["model"]["ffn"]["up_proj"]["kernel"][7]))
    mixer, _ = layer_params(params, cfg, 8)                  # the seventh Mamba layer
    assert np.array_equal(np.asarray(mixer["dt_bias"]),
                          np.asarray(params["model"]["mamba_layers"]["dt_bias"][6]))


def test_a_thousand_short_requests_take_and_return_every_slot(engine):
    """The turnover of a saturated chat endpoint at the debug size: 1000
    requests of 3-12 prompt tokens and 1-4 answers through the gateway,
    eight slots. Every slot comes back, none is handed out twice, and the
    engine never tracks more sequences than it has slots."""
    from deepspeed_tpu.serving import ServingConfig, ServingGateway
    served = second_engine(CASE, engine, sequences=8, context=64)
    pool = served.slot_pool
    assert pool.slots == 8 and served.state_extra["ssm"].shape[1] == 8 + 1
    most, owned, acquire = [0], set(), pool.acquire

    def counted():
        slot = acquire()
        assert 1 <= slot <= pool.slots and slot not in owned       # never padding's, never twice
        owned.add(slot)
        most[0] = max(most[0], pool.slots - pool.free_slots)
        return slot

    def released(slot, release=pool.release):
        owned.discard(int(slot))
        release(slot)

    pool.acquire, pool.release = counted, released
    rng = np.random.default_rng(45)
    gateway = ServingGateway(served, config=ServingConfig(default_max_new_tokens=4,
                                                          max_queue_depth=2048))
    try:
        handles = [gateway.submit(rng.integers(0, 256, int(rng.integers(3, 13)), dtype=np.int32),
                                  max_new_tokens=int(rng.integers(1, 5))) for _ in range(1000)]
        streams = [h.result(timeout=600) for h in handles]
    finally:
        gateway.shutdown()
    assert all(1 <= len(s) <= 4 for s in streams)
    assert pool.free_slots == pool.slots == 8              # every slot returned, none leaked
    assert 1 <= most[0] <= 8                               # slots + 1 rows were never exceeded
    assert not owned


class TestServing(conformance.ChunkCuts, conformance.Slots, conformance.NotKV):
    pass
