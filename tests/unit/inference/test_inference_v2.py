"""Inference v2 (ragged serving) tests.

Mirrors the reference's tests/unit/inference/v2/: allocator/manager
bookkeeping, ragged batch assembly, and — the core contract — that
``put`` over mixed prefill/decode ragged batches produces the same
logits as the dense ``model.apply`` path on the flagship model."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.ragged import (BlockedKVCache, DSStateManager,
                                               RaggedBatchWrapper)
from deepspeed_tpu.models import build_llama

CFG = RaggedInferenceEngineConfig(
    kv_block_size=8,
    state_manager=DSStateManagerConfig(max_ragged_batch_size=64,
                                       max_ragged_sequence_count=4,
                                       max_tracked_sequences=4,
                                       max_context=64))


@pytest.fixture(scope="module")
def setup():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngineV2(model=model, config=CFG, params=params, dtype=jnp.float32)
    return model, params, engine


def dense_logits(model, params, ids):
    """Reference: full dense forward, fp32."""
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    logits = model.apply({"params": p32}, jnp.asarray(ids)[None, :])
    return np.asarray(logits[0], np.float32)


class TestRaggedState:

    def test_manager_slots_and_blocks(self):
        cache = BlockedKVCache(2, 9, 8, 2, 4, dtype=jnp.float32)
        mgr = DSStateManager(cache, max_tracked_sequences=2)
        d = mgr.get_or_create_sequence(7)
        mgr.allocate_for(d, 20)  # 20 tokens / block 8 → 3 blocks
        assert d.cur_allocated_blocks == 3
        assert cache.free_blocks == 9 - 1 - 3  # null block pinned
        d.advance(20)
        mgr.allocate_for(d, 4)  # fits in the existing 3rd block
        assert d.cur_allocated_blocks == 3
        mgr.flush_sequence(7)
        assert cache.free_blocks == 8
        with pytest.raises(KeyError):
            mgr.flush_sequence(7)

    def test_wrapper_overflow_and_positions(self):
        w = RaggedBatchWrapper(max_tokens=8, max_seqs=2, max_blocks_per_seq=4)

        class D:
            slot, seen_tokens, blocks = 0, 5, [3, 4]

        w.insert_sequence(D(), [1, 2, 3])
        arrays = w.finalize()
        assert arrays["token_pos"][:3].tolist() == [5, 6, 7]
        assert arrays["block_tables"][0, :2].tolist() == [3, 4]
        assert arrays["last_index"][0] == 2
        with pytest.raises(ValueError):
            w.insert_sequence(D(), list(range(9)))


class TestEngineV2Correctness:

    def test_single_prefill_matches_dense(self, setup):
        model, params, engine = setup
        ids = np.arange(10, dtype=np.int32) % 250
        out = engine.put([101], [ids])
        want = dense_logits(model, params, ids)[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        engine.flush(101)

    def test_split_prefill_matches_dense(self, setup):
        """Dynamic SplitFuse: a prompt split across two puts must give
        the same final logits as one dense pass."""
        model, params, engine = setup
        ids = (np.arange(13, dtype=np.int32) * 7) % 250
        engine.put([202], [ids[:6]])
        out = engine.put([202], [ids[6:]])
        want = dense_logits(model, params, ids)[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        engine.flush(202)

    def test_decode_steps_match_dense(self, setup):
        model, params, engine = setup
        ids = (np.arange(9, dtype=np.int32) * 3) % 250
        engine.put([303], [ids])
        nxt = 42
        out = engine.put([303], [[nxt]])  # one decode token
        full = np.concatenate([ids, [nxt]]).astype(np.int32)
        want = dense_logits(model, params, full)[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        engine.flush(303)

    def test_mixed_batch_prefill_and_decode(self, setup):
        """One ragged batch: seq A decoding while seq B prefills."""
        model, params, engine = setup
        a = (np.arange(8, dtype=np.int32) * 5) % 250
        b = (np.arange(11, dtype=np.int32) * 11) % 250
        engine.put([1], [a])
        out = engine.put([1, 2], [[99], b])  # decode A + prefill B together
        want_a = dense_logits(model, params, np.append(a, 99).astype(np.int32))[-1]
        want_b = dense_logits(model, params, b)[-1]
        np.testing.assert_allclose(out[0], want_a, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(out[1], want_b, rtol=2e-4, atol=2e-4)
        engine.flush(1)
        engine.flush(2)

    def test_flush_frees_blocks_for_reuse(self, setup):
        _, _, engine = setup
        free0 = engine.free_blocks
        engine.put([5], [np.arange(20, dtype=np.int32)])
        assert engine.free_blocks < free0
        engine.flush(5)
        assert engine.free_blocks == free0

    def test_on_device_greedy_matches_host_argmax(self, setup):
        """put(sample='greedy') returns exactly argmax of the logits the
        plain put would have produced, as int32 token ids."""
        _, _, engine = setup
        ids = (np.arange(12, dtype=np.int32) * 5) % 250
        logits = engine.put([81], [ids])
        engine.flush(81)
        toks = engine.put([82], [ids], sample="greedy")
        engine.flush(82)
        assert toks.dtype == np.int32 and toks.shape == (1,)
        assert int(toks[0]) == int(np.argmax(logits[0]))
        with pytest.raises(ValueError, match="sample"):
            engine.put([83], [ids], sample="top_p")

    def test_on_device_stochastic_sampling(self, setup):
        """put(sample=dict): top_k=1 is exactly greedy regardless of
        temperature; free sampling is deterministic per engine stream and
        actually stochastic across streams."""
        _, _, engine = setup
        ids = (np.arange(11, dtype=np.int32) * 13) % 250
        g = int(engine.put([71], [ids], sample="greedy")[0])
        engine.flush(71)
        t1 = int(engine.put([72], [ids], sample={"top_k": 1, "temperature": 0.7})[0])
        engine.flush(72)
        assert t1 == g  # top-1 sampling == argmax
        # seeded determinism: same engine stream state → same draw
        import jax as _jax
        engine._rng = _jax.random.PRNGKey(123)
        a = int(engine.put([73], [ids], sample={"temperature": 1.5, "top_k": 0})[0])
        engine.flush(73)
        engine._rng = _jax.random.PRNGKey(123)
        b = int(engine.put([74], [ids], sample={"temperature": 1.5, "top_k": 0})[0])
        engine.flush(74)
        assert a == b
        # different streams eventually differ (64 draws at T=5)
        engine._rng = _jax.random.PRNGKey(7)
        draws = set()
        for uid in range(200, 208):
            draws.add(int(engine.put([uid], [ids], sample={"temperature": 5.0})[0]))
            engine.flush(uid)
        assert len(draws) > 1
        # typo'd keys refuse BEFORE any state mutation
        free = engine.free_blocks
        with pytest.raises(ValueError, match="unknown sampling keys"):
            engine.put([75], [ids], sample={"topk": 5})
        assert engine.free_blocks == free

    def test_scheduler_sampling_bursts(self, setup):
        """Scheduler(sampling=...) drives stochastic bursts end-to-end:
        requested token counts come back, and top_k=1 sampling reproduces
        the greedy run exactly (burst path included)."""
        model, params, engine = setup
        sched = DynamicSplitFuseScheduler(engine, token_budget=16,
                                          sampling={"top_k": 1, "temperature": 0.9})
        prompt = (np.arange(9, dtype=np.int32) * 17) % 250
        sched.add_request(301, prompt, max_new_tokens=6)
        out = sched.run_to_completion()
        greedy = DynamicSplitFuseScheduler(engine, token_budget=16)
        greedy.add_request(302, prompt, max_new_tokens=6)
        ref = greedy.run_to_completion()
        assert out[301] == ref[302] and len(out[301]) == 6

    def test_decode_burst_matches_stepwise(self, setup):
        """k-step on-device burst == k separate greedy put() steps."""
        _, _, engine = setup
        prompt = (np.arange(10, dtype=np.int32) * 11) % 250
        # stepwise reference
        tok = int(engine.put([91], [prompt], sample="greedy")[0])
        ref = []
        for _ in range(4):
            ref.append(tok)
            tok = int(engine.put([91], [[tok]], sample="greedy")[0])
        engine.flush(91)
        # burst path: prefill, then one 4-step burst continuing from the
        # first sampled token
        first = int(engine.put([92], [prompt], sample="greedy")[0])
        out = engine.decode_burst([92], [first], 4)
        engine.flush(92)
        assert out.shape == (4, 1)
        assert [first] + [int(t) for t in out[:-1, 0]] == ref
        with pytest.raises(ValueError, match="no prefilled context"):
            engine.decode_burst([93], [5], 2)

    def test_gemma_knobs_in_ragged_path(self):
        """The ragged runner honors the Gemma config knobs (GeGLU gate,
        embedding multiplier, explicit head_dim): v2 serving logits match
        the dense flax forward of the same gemma-configured model."""
        import dataclasses
        from deepspeed_tpu.models import build_llama
        model = build_llama("debug", head_dim_override=8, mlp_activation="gelu_tanh",
                            embedding_multiplier=8.0, tie_word_embeddings=True)
        rng = jax.random.PRNGKey(3)
        params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
        engine = InferenceEngineV2(model=model, config=CFG, params=params,
                                   dtype=jnp.float32)
        ids = (np.arange(10, dtype=np.int32) * 7) % 250
        got = engine.put([1], [ids])[0]
        want = dense_logits(model, params, ids)[-1]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

    def test_suspend_resume_kv_swapping(self, setup):
        """KV host swap (beyond the reference, whose offload() raises
        NotImplementedError): suspend a mid-generation sequence, let
        another sequence claim + overwrite its freed blocks, resume, and
        the continuation matches an uninterrupted run exactly."""
        _, _, engine = setup
        prompt = (np.arange(14, dtype=np.int32) * 9) % 250

        # uninterrupted reference rollout
        tok = int(engine.put([61], [prompt], sample="greedy")[0])
        ref = [tok]
        for _ in range(3):
            tok = int(engine.put([61], [[tok]], sample="greedy")[0])
            ref.append(tok)
        engine.flush(61)

        # suspended run: prefill, suspend, trample the pool, resume
        tok = int(engine.put([62], [prompt], sample="greedy")[0])
        free_before = engine.free_blocks
        engine.suspend(62)
        assert engine.free_blocks > free_before  # blocks really freed
        engine.put([63], [np.arange(40, dtype=np.int32)])  # overwrite pool
        engine.flush(63)
        seen = engine.resume(62)
        assert seen == len(prompt)
        got = [tok]
        for _ in range(3):
            tok = int(engine.put([62], [[tok]], sample="greedy")[0])
            got.append(tok)
        engine.flush(62)
        assert got == ref
        with pytest.raises(KeyError):
            engine.resume(99)
        # resume refuses when the uid was re-registered live meanwhile
        engine.put([64], [prompt], sample="greedy")
        engine.suspend(64)
        engine.put([64], [prompt[:4]])
        with pytest.raises(ValueError, match="re-registered"):
            engine.resume(64)
        # flush is a total discard: live KV AND the suspended host copy
        free0 = engine.free_blocks
        engine.flush(64)
        assert engine.free_blocks > free0
        with pytest.raises(KeyError):
            engine.resume(64)

    def test_budget_enforced(self, setup):
        _, _, engine = setup
        with pytest.raises(ValueError, match="max_ragged_batch_size"):
            engine.put([9], [np.zeros(100, np.int32)])

    def test_context_overflow_raises(self, setup):
        _, _, engine = setup
        engine.put([71], [np.zeros(60, np.int32)])
        with pytest.raises(ValueError, match="max_context"):
            engine.put([71], [np.zeros(10, np.int32)])  # 60+10 > 64
        engine.flush(71)

    def test_pool_exhaustion_pre_validated(self, setup):
        """A failing batch must not corrupt earlier sequences' state."""
        model, params, _ = setup
        small = RaggedInferenceEngineConfig(
            kv_block_size=8, num_kv_blocks=10,  # 9 usable after the null block
            state_manager=DSStateManagerConfig(max_ragged_batch_size=64,
                                               max_ragged_sequence_count=4,
                                               max_tracked_sequences=4,
                                               max_context=64))
        engine = InferenceEngineV2(model=model, config=small, params=params,
                                   dtype=jnp.float32)
        engine.put([1], [np.zeros(40, np.int32)])  # 5 blocks → 4 free
        free0 = engine.free_blocks
        with pytest.raises(RuntimeError, match="KV pool exhausted"):
            engine.put([2, 3], [np.zeros(20, np.int32)] * 2, do_checks=False)  # needs 6
        # pre-validation: nothing allocated, no phantom sequences
        assert engine.free_blocks == free0
        assert engine.state_manager.query(2) is None
        assert engine.state_manager.query(3) is None


# --------------------------------------------------------------- the pool
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<type>\w+)\[(?P<dims>[\d,]*)\]"
                        r"(?:\{[^}]*\})? (?P<op>[\w\-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "reshape", "transpose")


def moves_of(hlo):
    """Optimised HLO text → ``[(instruction, result dims, result bytes)]``
    of its ``copy`` / ``dynamic-slice`` / ``dynamic-update-slice`` /
    ``reshape`` / ``transpose`` instructions, in any computation (fused
    ones included)."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        if not m or m["op"] not in _MOVES or not m["dims"]:
            continue
        dims = tuple(int(d) for d in m["dims"].split(","))
        width = int(re.sub(r"\D", "", m["type"]) or 8) // 8  # f32 -> 4, bf16 -> 2, pred -> 1
        found.append((f"{m['name']} {m['op']} {m['type']}[{m['dims']}]", dims,
                      int(np.prod(dims)) * width))
    return found


def pool_findings(hlo, pool_shape, dtype):
    """Read a compiled program's optimised HLO text → ``(pool parameters
    of the entry computation, those of them aliased to a result,
    offenders)``: an offender is one of :func:`moves_of` whose result has
    as many bytes as the pool or as one layer of it. A scatter may have."""
    item = jnp.dtype(dtype).itemsize
    pool_bytes = int(np.prod(pool_shape)) * item
    sizes = {pool_bytes, pool_bytes // pool_shape[0]}
    hlo_type = {"float32": "f32", "bfloat16": "bf16"}[jnp.dtype(dtype).name]
    want = f"{hlo_type}[{','.join(str(d) for d in pool_shape)}]"
    entry = hlo[hlo.index("\nENTRY "):]
    pool_params = [int(n) for n in re.findall(
        r"= " + re.escape(want) + r"(?:\{[^}]*\})? parameter\((\d+)\)", entry)]
    header = hlo[:hlo.index("\n")]
    block = re.search(r"input_output_alias=\{(.*?)\}, \w+=", header)
    aliased = {int(n) for n in re.findall(r"\((\d+), \{\}", block[1])} if block else set()
    return pool_params, aliased, [name for name, _, nbytes in moves_of(hlo) if nbytes in sizes]


def capture_programs(engine, uid=71):
    """Serve one prompt, one greedy step and a 4-step burst through the
    engine, and → ``{program: (jitted fn, its arguments as shapes)}`` of
    the greedy step program and the k = 4 burst as the engine ran them."""
    seen = {}

    def spy(name, fn):
        def call(*args):
            seen[name] = (fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args))
            return fn(*args)
        return call

    step, make = engine._step_greedy, engine._make_burst_fn
    engine._step_greedy = spy("greedy_step", step)
    engine._make_burst_fn = lambda k, *a, **kw: spy(f"burst{k}", make(k, *a, **kw))
    try:
        prompt = (np.arange(10, dtype=np.int32) * 7) % 250
        first = int(engine.put([uid], [prompt], sample="greedy")[0])
        engine.decode_burst([uid], [first], 4)
    finally:
        engine._step_greedy, engine._make_burst_fn = step, make
        engine._burst_fns.clear()  # the spied program is not the engine's to keep
        engine.flush(uid)
    return seen


class TestPoolStaysInPlace:
    """No serving program copies, slices, stacks or relays out the KV
    pool: the layer scan carries it, writes it with a scatter and the
    attention reads it by layer. Fails if a copy comes back."""

    @pytest.fixture(scope="class")
    def programs(self):
        model = build_llama("debug")
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        # 37 blocks: no weight of the debug model has a layer's or the pool's bytes
        cfg = RaggedInferenceEngineConfig(
            kv_block_size=8, num_kv_blocks=37,
            state_manager=DSStateManagerConfig(max_ragged_batch_size=32,
                                               max_ragged_sequence_count=4,
                                               max_tracked_sequences=4, max_context=64))
        engine = InferenceEngineV2(model=model, config=cfg, params=params, dtype=jnp.float32)
        return engine.kv_cache.k.shape, capture_programs(engine)

    @pytest.mark.parametrize("program", ["greedy_step", "burst4"])
    def test_no_pool_sized_copy_in_compiled_program(self, programs, program):
        pool_shape, seen = programs
        fn, shapes = seen[program]
        hlo = fn.lower(*shapes).compile().as_text()
        pool_params, aliased, offenders = pool_findings(hlo, pool_shape, jnp.float32)
        assert len(pool_params) == 2, pool_params
        assert set(pool_params) <= aliased, (pool_params, aliased)
        assert offenders == []

    def test_findings_see_a_copy(self):
        """The reader itself: a program that stacks the pool out of a
        scan (the form this replaced) is flagged, an in-place one is not."""
        pool = jnp.zeros((2, 37, 8, 32))

        def stacked(p):
            return jax.lax.scan(lambda c, layer: (c, layer.at[0, 0].set(1.0)), 0, p)[1]

        def in_place(p):
            return p.at[jnp.array([0, 1]), jnp.array([3, 4]), 0].set(1.0)

        for fn, clean in ((stacked, False), (in_place, True)):
            hlo = jax.jit(fn, donate_argnums=0).lower(pool).compile().as_text()
            params, aliased, offenders = pool_findings(hlo, pool.shape, jnp.float32)
            assert params == [0] and aliased == {0}
            assert (offenders == []) == clean, offenders


# ------------------------------------------------------------ the experts
def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def expert_findings(jaxpr, stack_shapes):
    """A traced program → ``(shapes of the scans' xs that are an expert
    stack's [L, E, in, out], the number of groups of each ragged_dot's
    weight operand)``. Read from the jaxpr, which no backend has touched
    (the CPU's compiler expands ``ragged_dot``, the TPU's does not)."""
    sliced, groups = [], []
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "scan":
            first = eqn.params["num_consts"] + eqn.params["num_carry"]
            sliced += [v.aval.shape for v in eqn.invars[first:] if v.aval.shape in stack_shapes]
        elif eqn.primitive.name.startswith("ragged_dot"):
            groups.append(eqn.invars[1].aval.shape[0])
    return sliced, groups


def stack_shapes_of(engine):
    from deepspeed_tpu.inference.v2.model_runner import EXPERT_STACKS
    moe = engine.params["model"]["layers"]["moe_mlp"]["deepspeed_moe"]
    return {moe[name].shape for name in EXPERT_STACKS}


def layer_stack_moves(hlo, stack_shapes):
    """The moves of a compiled program whose result is one layer's expert
    stack ``[E, in, out]``, in any order of its dimensions (by dimensions
    and not by bytes: the debug model's activations have the same bytes)."""
    layer = {tuple(sorted(shape[1:])) for shape in stack_shapes}
    return [name for name, dims, _ in moves_of(hlo)
            if tuple(sorted(d for d in dims if d != 1)) in layer]


def mixtral_engine(experts=4, **overrides):
    model = build_llama("mixtral-debug", remat=False, moe_num_experts=experts)
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
    config = RaggedInferenceEngineConfig(
        kv_block_size=8, **overrides,
        state_manager=DSStateManagerConfig(max_ragged_batch_size=32,
                                           max_ragged_sequence_count=4,
                                           max_tracked_sequences=4, max_context=128))
    return InferenceEngineV2(model=model, config=config, params=params, dtype=jnp.float32)


def slice_per_layer(monkeypatch):
    """The form this replaced: the expert stacks stay in the scan's xs and
    the layer step gets its layer's slice (what sharded and quantized
    experts still do)."""
    from deepspeed_tpu.inference.v2 import model_runner
    monkeypatch.setattr(model_runner, "_split_expert_stacks", lambda layers, mesh: (layers, None))


class TestExpertsStayInPlace:
    """No serving program of a mixture model cuts a layer's experts out of
    the stack: the stacks ride the step whole, and the grouped matmul
    reads the layer's groups out of one table of ``L x E``. Fails on the
    per-layer slice (``ragged_dot`` takes a buffer, so the TPU's compiler
    materialises the slice first: 47 % of Mixtral's device time, PR 29)."""

    @pytest.fixture(scope="class")
    def programs(self):
        engine = mixtral_engine()
        return engine, capture_programs(engine)

    @pytest.mark.parametrize("program", ["greedy_step", "burst4"])
    def test_no_expert_stack_in_the_scan_and_one_table_of_groups(self, programs, program):
        engine, seen = programs
        fn, shapes = seen[program]
        cfg = engine.model_config
        sliced, groups = expert_findings(jax.make_jaxpr(fn)(*shapes).jaxpr,
                                         stack_shapes_of(engine))
        assert sliced == []
        assert groups == [cfg.num_hidden_layers * cfg.moe_num_experts] * 3

    @pytest.mark.parametrize("program", ["greedy_step", "burst4"])
    def test_no_expert_stack_sized_copy_in_compiled_program(self, programs, program):
        engine, seen = programs
        fn, shapes = seen[program]
        hlo = fn.lower(*shapes).compile().as_text()
        assert layer_stack_moves(hlo, stack_shapes_of(engine)) == []

    @pytest.fixture(scope="class")
    def moonlight_chunk(self):
        """Moonlight's prompt-chunk program with the Pallas grouped matmul
        as the dispatch (interpreted: the CPU has no Mosaic) →
        (engine, its jaxpr, its compiled HLO, ``GMM_STATS``)."""
        import deepspeed_tpu.ops.grouped_gemm as gg
        from deepspeed_tpu.models import build_model
        config = RaggedInferenceEngineConfig(
            kv_block_size=16, num_kv_blocks=24,
            state_manager=DSStateManagerConfig(max_ragged_batch_size=32,
                                               max_ragged_sequence_count=4,
                                               max_tracked_sequences=4, max_context=128))
        gg.FORCE_INTERPRET = True
        gg.GMM_STATS.reset()
        try:
            engine = InferenceEngineV2(model=build_model("moonlight-debug"), config=config,
                                       dtype=jnp.float32, rng=jax.random.PRNGKey(3))
            fn, shapes = capture_programs(engine)["greedy_step"]
            paths = gg.GMM_STATS.snapshot()     # every program traced so far
            jaxpr = jax.make_jaxpr(fn)(*shapes).jaxpr
            hlo = fn.lower(*shapes).compile().as_text()
        finally:
            gg.FORCE_INTERPRET = False
        return engine, jaxpr, hlo, paths

    @pytest.mark.parametrize("finding", ["jaxpr", "compiled", "paths"])
    def test_the_pallas_grouped_matmul_reads_the_table_too(self, moonlight_chunk, finding):
        """Fails where the kernel gets the layer's groups cut out of the
        table (``dynamic_slice_in_dim``, until PR 31): three ops a layer
        with the shape of its 8 experts' stack."""
        engine, jaxpr, hlo, paths = moonlight_chunk
        cfg = engine.model_config
        experts = engine.params["model"]["layers"]["mlp"]["experts"]
        stacks = {w.shape for w in jax.tree.leaves(experts)}            # [Lm, E, in, out]
        if finding == "jaxpr":
            layer = {shape[1:] for shape in stacks}
            eqns = list(_equations(jaxpr))
            cut = [e.primitive.name for e in eqns
                   if any(v.aval.shape in layer for v in e.outvars)]
            groups = [e.invars[3].aval.shape[0] for e in eqns if e.primitive.name == "pallas_call"]
            assert cut == []
            assert groups == [cfg.num_moe_layers * cfg.n_routed_experts] * 3
            assert expert_findings(jaxpr, stacks) == ([], [])
        elif finding == "compiled":
            assert layer_stack_moves(hlo, stacks) == []
        else:
            assert set(paths) == {"pallas_table"}

    def test_findings_see_a_slice(self, monkeypatch):
        """The readers themselves: the sliced form (the engine's own, with
        the stacks left in the scan) is flagged by both, the table form
        (the tests above) by neither; and a bare scan over a stack by the
        jaxpr reader, its table twin not."""
        slice_per_layer(monkeypatch)
        engine = mixtral_engine()
        cfg, stacks = engine.model_config, stack_shapes_of(engine)
        for name, (fn, shapes) in capture_programs(engine).items():
            sliced, groups = expert_findings(jax.make_jaxpr(fn)(*shapes).jaxpr, stacks)
            assert len(sliced) == 3 and set(sliced) == stacks, name
            assert groups == [cfg.moe_num_experts] * 3, name
            # the CPU's compiler expands ragged_dot, and still cuts the layer out
            cut = layer_stack_moves(fn.lower(*shapes).compile().as_text(), stacks)
            assert sum("dynamic-slice" in move for move in cut) == 3, (name, cut)

        x, sizes = jnp.ones((8, 16)), jnp.array([3, 5], jnp.int32)
        stack = jnp.ones((3, 2, 16, 32))

        def per_layer(w):
            return jax.lax.scan(lambda c, wl: (c + jax.lax.ragged_dot(x, wl, sizes), None),
                                jnp.zeros((8, 32)), w)[0]

        def table(w):
            flat = w.reshape((-1,) + w.shape[2:])
            return jax.lax.scan(lambda c, l: (c + jax.lax.ragged_dot(
                x, flat, jnp.zeros(6, jnp.int32).at[2 * l + jnp.arange(2)].set(sizes)), None),
                jnp.zeros((8, 32)), jnp.arange(3))[0]

        assert expert_findings(jax.make_jaxpr(per_layer)(stack).jaxpr, {stack.shape}) == (
            [stack.shape], [2])
        assert expert_findings(jax.make_jaxpr(table)(stack).jaxpr, {stack.shape}) == ([], [6])


def served(engine, lora_uid=None):
    """Three prompts through one engine: two short ones that decode while a
    70-token one is prefilled in three chunks beside them, 24 burst steps
    of all three, one more step → (logits of every mixed step, burst
    tokens, logits after the bursts, both pools)."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 250, n).astype(np.int32) for n in (9, 13, 70)]
    if lora_uid is not None:
        store = engine.lora_store
        rs = np.random.RandomState(3)
        engine.register_adapter(101, {
            site: (rs.randn(store.num_layers, din, 4).astype(np.float32) * 0.05,
                   rs.randn(store.num_layers, 4, dout).astype(np.float32) * 0.05)
            for site, (din, dout) in store.dims.items()}, alpha=8.0)
        engine.bind_adapter(lora_uid, 101)
    logits = [engine.put([1, 2], prompts[:2])]
    last = [int(np.argmax(row)) for row in logits[0]]
    for chunk in (prompts[2][:30], prompts[2][30:60], prompts[2][60:]):
        out = engine.put([1, 2, 3], [[last[0]], [last[1]], chunk])
        logits.append(out)
        last = [int(np.argmax(row)) for row in out]
    tokens = []
    for _ in range(3):
        burst = np.asarray(engine.decode_burst([1, 2, 3], last, 8))
        tokens.append(burst)
        last = [int(t) for t in burst[-1]]
    after = engine.put([1, 2, 3], [[t] for t in last])
    pools = np.asarray(engine.kv_cache.k), np.asarray(engine.kv_cache.v)
    return logits, np.concatenate(tokens), after, pools


class TestExpertTableSameAnswers:
    """The table of groups gives what the per-layer slice gave, to 1e-5 in
    float32: a multi-chunk prefill beside decoding rows, 24 burst steps
    and the pool they wrote, in both dispatches the cell's sizes take and
    with LoRA's slabs beside the layers in the scan's xs."""

    @pytest.mark.parametrize("experts,lora,paths", [
        (4, False, {"ragged"}),                 # 8 decode rows >= 4 experts
        (16, False, {"gathered", "ragged"}),    # 8 decode rows < 16 experts; 64 prefill rows
        (4, True, {"ragged"}),
    ])
    def test_logits_tokens_and_pool_match_the_per_layer_slice(self, monkeypatch, experts, lora,
                                                               paths):
        from deepspeed_tpu.inference.v2.config_v2 import LoRAServingConfig
        from deepspeed_tpu.ops.grouped_gemm import GMM_STATS
        extra = {"lora": LoRAServingConfig(enabled=True, hot_set=4, max_rank=4,
                                           prefetch=False)} if lora else {}
        results = {}
        for form in ("sliced", "table"):
            with monkeypatch.context() as patch:
                if form == "sliced":
                    slice_per_layer(patch)
                engine = mixtral_engine(experts=experts, **extra)
                GMM_STATS.reset()
                results[form] = served(engine, lora_uid=2 if lora else None)
                suffix = "_table" if form == "table" else ""
                assert set(GMM_STATS.snapshot()) == {path + suffix for path in paths}
        (want_logits, want_tokens, want_after, want_pools) = results["sliced"]
        (logits, tokens, after, pools) = results["table"]
        assert tokens.shape == (24, 3)
        np.testing.assert_array_equal(tokens, want_tokens)
        for got, want in zip(logits + [after], want_logits + [want_after]):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        for got, want in zip(pools, want_pools):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("overrides,form,paths", [
        ({}, "table", {"ragged_table"}),
        ({"expert_parallel_degree": 2}, "sliced", {"ragged"}),
        ({"tensor_parallel_degree": 2}, "sliced", {"ragged"}),
        ({"quantization": {"quantization_mode": "int8"}}, "sliced", {"ragged_quant"}),
    ])
    def test_who_takes_the_table(self, overrides, form, paths):
        """Plain stacks on one device ride the step whole; sharded experts
        (``E/ep`` a shard inside ``shard_map``) and quantized carriers keep
        the scan's per-layer slice and the dispatch they had."""
        from deepspeed_tpu.ops.grouped_gemm import GMM_STATS
        engine = mixtral_engine(**overrides)
        assert engine.kind.experts_form(engine.params, engine.mesh) == form
        GMM_STATS.reset()
        out = engine.put([1], [(np.arange(10, dtype=np.int32) * 13) % 250])
        assert np.isfinite(out).all()
        assert set(GMM_STATS.snapshot()) == paths


class TestGPTFamilyServing:
    """The v2 model zoo beyond Llama (reference
    inference/v2/model_implementations/: falcon, opt, phi, qwen...):
    every GPT-family wiring serves correctly through the ragged engine."""

    @pytest.mark.parametrize("preset", ["gptj-debug", "bloom-debug", "opt-debug",
                                        "falcon-debug", "neox-debug"])
    def test_gpt_split_prefill_and_decode_matches_dense(self, preset):
        from deepspeed_tpu.models import build_gpt
        model = build_gpt(preset, remat=False)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        engine = InferenceEngineV2(model=model, config=CFG, params=params, dtype=jnp.float32)
        ids = (np.arange(11, dtype=np.int32) * 7) % 250
        engine.put([1], [ids[:6]])
        out = engine.put([1], [ids[6:]])   # split prefill
        want = dense_logits(model, params, ids)[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        out = engine.put([1], [[42]])      # decode step
        want = dense_logits(model, params, np.append(ids, 42).astype(np.int32))[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        engine.flush(1)

    def test_mixtral_moe_serving_matches_dense(self):
        """Mixtral-style MoE through the ragged engine: the dropless
        top-k serving path must match the dense forward (built with
        ample capacity so the dense gate drops nothing either)."""
        model = build_llama("mixtral-debug", remat=False, moe_capacity_factor=64.0)
        params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
        engine = InferenceEngineV2(model=model, config=CFG, params=params, dtype=jnp.float32)
        ids = (np.arange(10, dtype=np.int32) * 13) % 250

        def dense_last(tokens):
            p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
            logits = model.apply({"params": p32}, jnp.asarray(tokens)[None, :])
            return np.asarray(logits[0], np.float32)[-1]

        out = engine.put([1], [ids])
        np.testing.assert_allclose(out[0], dense_last(ids), rtol=2e-4, atol=2e-4)
        out = engine.put([1], [[7]])  # decode
        np.testing.assert_allclose(out[0], dense_last(np.append(ids, 7).astype(np.int32)),
                                   rtol=2e-4, atol=2e-4)
        engine.flush(1)

    def test_attention_softmax_scale_matches_dense(self):
        """GPT-family with attention_softmax_scale set (GPT-Neo imports
        use 1.0 = unscaled attention; MPT sets attn_config.softmax_scale):
        the ragged runner must apply the same q pre-scale as the dense
        forward (models/gpt.py:209) or serving silently yields wrong
        logits (round-4 advisor high finding)."""
        from deepspeed_tpu.models import build_gpt
        model = build_gpt("gptj-debug", attention_softmax_scale=1.0, remat=False)
        params = model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
        engine = InferenceEngineV2(model=model, config=CFG, params=params, dtype=jnp.float32)
        ids = (np.arange(10, dtype=np.int32) * 11) % 250
        out = engine.put([1], [ids])
        want = dense_logits(model, params, ids)[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        assert int(np.argmax(out[0])) == int(np.argmax(want))
        out = engine.put([1], [[5]])  # decode step keeps the scale too
        want = dense_logits(model, params, np.append(ids, 5).astype(np.int32))[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        engine.flush(1)

    def test_qwen2_style_qkv_bias_matches_dense(self):
        """Llama-family with attention_bias=True (Qwen2) — biases must
        flow through the ragged runner's projections."""
        model = build_llama("debug", attention_bias=True, remat=False)
        params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
        assert "bias" in params["model"]["layers"]["self_attn"]["q_proj"]
        engine = InferenceEngineV2(model=model, config=CFG, params=params, dtype=jnp.float32)
        ids = (np.arange(9, dtype=np.int32) * 5) % 250
        out = engine.put([1], [ids])
        want = dense_logits(model, params, ids)[-1]
        np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
        engine.flush(1)


class TestScheduler:

    def test_splitfuse_generates_greedy_tokens(self, setup):
        model, params, engine = setup
        sched = DynamicSplitFuseScheduler(engine, token_budget=16)
        prompt_a = (np.arange(20, dtype=np.int32) * 3) % 250   # > budget → split
        prompt_b = (np.arange(5, dtype=np.int32) * 7) % 250
        sched.add_request(11, prompt_a, max_new_tokens=3)
        sched.add_request(12, prompt_b, max_new_tokens=3)
        out = sched.run_to_completion()
        assert len(out[11]) == 3 and len(out[12]) == 3

        # greedy reference: dense argmax rollout
        def rollout(ids, n):
            ids = list(ids)
            for _ in range(n):
                ids.append(int(np.argmax(dense_logits(model, params, np.asarray(ids, np.int32))[-1])))
            return ids[-n:]

        assert out[11] == rollout(prompt_a, 3)
        assert out[12] == rollout(prompt_b, 3)
        # all sequences flushed → all blocks back
        assert engine.state_manager.n_tracked_sequences == 0

    def test_burst_respects_token_budget(self, setup):
        """A token_budget smaller than the live-request count must keep
        bounding per-step work on the all-decoding path too — _try_burst
        may not bypass it (round-4 advisor finding)."""
        model, params, engine = setup
        sched = DynamicSplitFuseScheduler(engine, token_budget=16, max_burst=8)
        for uid in (21, 22, 23):
            sched.add_request(uid, (np.arange(4, dtype=np.int32) * (uid % 7 + 1)) % 250,
                              max_new_tokens=6)
        sched.step()  # budget 16 prefills all three → all live decoding
        assert all(not r.prefilling and r.next_token is not None
                   for r in sched.requests.values())
        sched.budget = 2  # now 3 live > budget → burst must refuse...
        assert sched._try_burst() is None
        sched.budget = 16  # ...and the budget really was the deciding factor
        assert sched._try_burst() is not None
        out = sched.run_to_completion()
        assert all(len(out[u]) == 6 for u in (21, 22, 23))
        assert engine.state_manager.n_tracked_sequences == 0


class TestZeroInferenceQuantizedServing:
    """Weight-only quantized v2 serving (reference ZeRO-Inference +
    FP6-LLM): quantized bytes resident, dequant fused into the step."""

    @pytest.mark.parametrize("scheme,tol", [("int8", 0.20), ("fp8", 0.35),
                                            ("fp6", 0.80)])
    def test_quantized_serving_close_to_full_precision(self, scheme, tol):
        from deepspeed_tpu.inference.quantization import quantized_bytes
        model = build_llama("debug", remat=False)
        params = model.init(jax.random.PRNGKey(4), jnp.zeros((1, 8), jnp.int32))["params"]
        full = InferenceEngineV2(model=model, config=CFG, params=params,
                                 dtype=jnp.float32)
        qcfg = RaggedInferenceEngineConfig(
            kv_block_size=8, state_manager=CFG.state_manager,
            quantization={"quantization_mode": scheme})
        quant = InferenceEngineV2(model=model, config=qcfg, params=params,
                                  dtype=jnp.float32)
        # the resident params really are quantized (fewer bytes than fp32)
        raw = sum(np.asarray(l).nbytes for l in jax.tree.leaves(params))
        assert quantized_bytes(quant.params) < raw * 0.5
        ids = (np.arange(10, dtype=np.int32) * 3) % 250
        want = full.put([1], [ids])
        got = quant.put([1], [ids])
        # low-bit weights shift logits a little; same top-1 region expected
        assert np.abs(got - want).max() < tol * np.abs(want).max() + 1.0, scheme
        got2 = quant.put([1], [[int(np.argmax(got[0]))]])  # decode step
        assert np.all(np.isfinite(got2))

    @pytest.mark.parametrize("scheme", ["int8", "fp8", "fp6"])
    def test_quantized_tp_matches_unsharded_quantized(self, scheme):
        """Quantized weights composed with TP serving (the reference's
        FP6-LLM TP2 headline): grouped-layout quantization preserves the
        leaf dim structure, so the same quantization math runs sharded
        and the logits match the single-device quantized engine."""
        model = build_llama("debug", remat=False)
        params = model.init(jax.random.PRNGKey(4), jnp.zeros((1, 8), jnp.int32))["params"]
        ids = (np.arange(10, dtype=np.int32) * 3) % 250
        qdict = {"quantization_mode": scheme}
        ref = InferenceEngineV2(
            model=model, params=params, dtype=jnp.float32,
            config=RaggedInferenceEngineConfig(
                kv_block_size=8, state_manager=CFG.state_manager, quantization=qdict))
        want = ref.put([1], [ids])
        eng = InferenceEngineV2(
            model=model, params=params, dtype=jnp.float32,
            config=RaggedInferenceEngineConfig(
                kv_block_size=8, state_manager=CFG.state_manager,
                tensor_parallel_degree=2, quantization=qdict))
        got = eng.put([1], [ids])
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)
        # the quantized carriers really are sharded over 'tensor'
        qk = eng.params["model"]["layers"]["self_attn"]["q_proj"]["kernel"]
        assert qk.values.addressable_shards[0].data.shape[-1] == qk.values.shape[-1] // 2

    def test_quantized_tp_ep_moe_serving(self):
        """int8 weights + tensor=2 x expert=2 MoE serving: expert dim and
        feature dims shard while the grouped quantization stays exact
        per-leaf."""
        model = build_llama("mixtral-debug", remat=False, moe_capacity_factor=64.0)
        params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
        ids = (np.arange(10, dtype=np.int32) * 13) % 250
        qdict = {"quantization_mode": "int8"}
        ref = InferenceEngineV2(
            model=model, params=params, dtype=jnp.float32,
            config=RaggedInferenceEngineConfig(
                kv_block_size=8, state_manager=CFG.state_manager, quantization=qdict))
        want = ref.put([1], [ids])
        eng = InferenceEngineV2(
            model=model, params=params, dtype=jnp.float32,
            config=RaggedInferenceEngineConfig(
                kv_block_size=8, state_manager=CFG.state_manager,
                tensor_parallel_degree=2, expert_parallel_degree=2,
                quantization=qdict))
        got = eng.put([1], [ids])
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)
        w1 = eng.params["model"]["layers"]["moe_mlp"]["deepspeed_moe"]["experts_w1"]
        assert w1.values.addressable_shards[0].data.shape[1] == w1.values.shape[1] // 2
