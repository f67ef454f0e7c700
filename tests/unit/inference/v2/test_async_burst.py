"""One decode-burst path at every pipeline depth (``async_burst.depth``).

Contract under test: depth 0 fetches every burst in the call that
dispatched it; at depth 2 the host plans/dispatches burst k+1 while
burst k executes and consumes its results late through a single
device→host copy — and every stream (greedy, sampled,
schema-constrained, speculative, replayed) is BIT-IDENTICAL between
the two, because both run ONE program family whose entry tokens and
DFA states are device arguments and the counter PRNG keys randomness
by absolute position, not burst shape. EOS discovered mid-pipeline
settles at drain time (rewind of the speculatively-dispatched tail +
flush) with exact pool accounting; sequence token logs stay
device-resident until something fences, and an unfenced host read is
a typed error, never a silent sync; a running pipeline yields to the
drafter; whoever rebinds ``engine.decode_burst`` on an instance sees
every fetched burst; the burst-program cache absorbs the program set
with zero evictions; and syncs-per-generated-token drops >= 4x."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.structured.grammar import (CompiledSchema,
                                                        byte_vocab)
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig,
                                        DynamicSplitFuseScheduler,
                                        InferenceEngineV2, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig,
                                        SpecDecodeConfig, StructuredConfig)
from deepspeed_tpu.inference.v2.config_v2 import AsyncBurstConfig
from deepspeed_tpu.inference.v2.engine_v2 import _burst_layout
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import (
    TokenLog, UnfencedTokenLogError)
from deepspeed_tpu.models import build_llama

EOS = 2
SCHEMA = {"type": "object",
          "properties": {"ok": {"type": "boolean"},
                         "mode": {"enum": ["fast", "safe"]}},
          "required": ["ok", "mode"]}

PROMPT = (np.arange(1, 17) % 250).astype(np.int32)          # 16 tokens
REPETITIVE = np.tile(np.array([7, 8, 9, 10], np.int32), 6)  # 24 tokens


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(model_and_params, depth, spec=False,
                structured=False, prefix=False, n_seqs=4, max_context=128,
                batch=64):
    model, params = model_and_params
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=8,
        num_kv_blocks=0,
        async_burst=AsyncBurstConfig(depth=depth),
        spec_decode=SpecDecodeConfig(enabled=spec),
        structured=StructuredConfig(enabled=structured),
        prefix_cache=PrefixCacheConfig(enabled=prefix),
        state_manager=DSStateManagerConfig(max_ragged_batch_size=batch,
                                           max_ragged_sequence_count=n_seqs,
                                           max_tracked_sequences=n_seqs,
                                           max_context=max_context))
    return InferenceEngineV2(model=model, config=cfg, params=params,
                             dtype=jnp.float32)


def run_fleet(eng, reqs, max_new=20, max_burst=8, budget=48, eos=None,
              retire=False):
    """reqs: [(uid, prompt, sample, schema)] → {uid: generated}."""
    sched = DynamicSplitFuseScheduler(eng, token_budget=budget,
                                      max_burst=max_burst, eos_token_id=eos)
    for uid, p, sample, schema in reqs:
        sched.add_request(uid, p, max_new_tokens=max_new, sample=sample,
                          schema=schema)
    out = sched.run_to_completion()
    if retire:
        for uid in out:
            sched.retire(uid)
    return out


def greedy_reqs(uids):
    return [(u, PROMPT + (u % 5), None, None) for u in uids]


# ------------------------------------------------------ streams bit-identical
class TestStreamsBitIdentical:

    def test_greedy_matches_depth0_and_engages_pipeline(self, model_and_params):
        eng0 = make_engine(model_and_params, depth=0)
        want = run_fleet(eng0, greedy_reqs([1, 2, 3]), max_new=21)
        eng0.destroy()
        eng = make_engine(model_and_params, depth=2)
        sched = DynamicSplitFuseScheduler(eng, token_budget=48, max_burst=8)
        for uid, p, _, _ in greedy_reqs([1, 2, 3]):
            sched.add_request(uid, p, max_new_tokens=21)
        deepest = 0
        while sched.has_work:
            sched.step()
            deepest = max(deepest, len(sched._pipeline))
        assert {u: r.generated for u, r in sched.requests.items()} == want
        # ...and the pipeline actually engaged (not a vacuous pass)
        assert deepest == 2
        eng.destroy()

    def test_sampled_streams_match_depth0(self, model_and_params):
        specs = [{"temperature": 0.9 + 0.2 * i, "top_k": 20 + 10 * i,
                  "seed": 100 + i} for i in range(3)]
        reqs = [(i, PROMPT + i, specs[i], None) for i in range(3)]
        outs = {}
        for depth in (0, 2):
            eng = make_engine(model_and_params, depth=depth)
            outs[depth] = run_fleet(eng, reqs, max_new=18)
            eng.destroy()
        assert outs[2] == outs[0]

    def test_constrained_sampled_streams_match_depth0(self, model_and_params):
        outs = {}
        for depth in (0, 2):
            eng = make_engine(model_and_params, depth=depth,
                              structured=True)
            vocab = byte_vocab(eng.structured.vocab_size)
            compiled = CompiledSchema(SCHEMA, vocab, eos_token_id=EOS)
            reqs = [(i, PROMPT + i,
                     {"temperature": 1.2, "top_k": 30, "seed": 50 + i},
                     compiled) for i in range(3)]
            outs[depth] = run_fleet(eng, reqs, max_new=64, eos=EOS,
                                       retire=True)
            eng.destroy()
        assert outs[2] == outs[0]
        # the schema's finite language terminated every lane at EOS —
        # i.e. EOS landed mid-pipeline and the drain settled it
        for toks in outs[2].values():
            assert toks[-1] == EOS

    def test_spec_decode_partial_acceptance_matches(self, model_and_params):
        # The rule scheduler.step() states: a running pipeline yields to
        # the drafter. It proposes from tokens the host has fetched, so
        # with speculative decoding armed the burst in flight is drained
        # before the next is planned, and the drafter gets exactly the
        # turns it gets at depth 0. Repetitive prompts keep it winning
        # some and losing some — partial acceptance on both engines.
        reqs = [(1, REPETITIVE, None, None), (2, PROMPT, None, None)]
        outs, stats = {}, {}
        for depth in (0, 2):
            eng = make_engine(model_and_params, depth=depth, spec=True)
            sched = DynamicSplitFuseScheduler(eng, token_budget=48, max_burst=8)
            for uid, p, _, _ in reqs:
                sched.add_request(uid, p, max_new_tokens=20)
            dispatched = 0
            while sched.has_work:
                before = eng.spec.stats()["verify_steps"]
                sched.step()
                # never two bursts in flight, and none while the drafter verifies
                assert len(sched._pipeline) <= 1
                if eng.spec.stats()["verify_steps"] > before:
                    assert not sched._pipeline
                dispatched += len(sched._pipeline)
            outs[depth] = {u: r.generated for u, r in sched.requests.items()}
            stats[depth] = eng.spec.stats()
            assert stats[depth]["verify_steps"] > 0
            assert bool(dispatched) == bool(depth)  # the pipelined form did run
            eng.destroy()
        assert outs[2] == outs[0]
        assert stats[2] == stats[0]  # same drafts offered, same accepted

    def test_failover_replay_reproduces_streams(self, model_and_params):
        # the fleet failover contract: a replica rebuilds a mid-flight
        # stream from (seed, position) alone — replaying the same seeded
        # requests on a FRESH pipelined engine (and on a depth-0 one) must
        # reproduce the original streams bit-identically
        spec = {"temperature": 1.3, "top_k": 40, "seed": 777}
        reqs = [(9, PROMPT, spec, None)]
        eng = make_engine(model_and_params, depth=2)
        original = run_fleet(eng, reqs, max_new=24)
        eng.destroy()
        for depth in (2, 0):
            eng = make_engine(model_and_params, depth=depth)
            assert run_fleet(eng, reqs, max_new=24) == original
            eng.destroy()

    def test_prefix_cache_token_log_from_device_ring(self, model_and_params):
        # the trie is built from the token log at retire; with the
        # pipeline on, that log spent its life as pending DEVICE
        # segments — content must come out identical
        outs, matches = [], []
        for depth in (0, 2):
            eng = make_engine(model_and_params, depth=depth,
                              prefix=True)
            out = run_fleet(eng, [(1, REPETITIVE, None, None)], max_new=20)[1]
            hist = list(REPETITIVE) + out
            outs.append(out)
            matches.append(eng.prefix_match_len(hist))
            assert eng.prefix_cache.cached_blocks > 0
            eng.destroy()
        assert outs[0] == outs[1]
        assert matches[0] == matches[1] > 0


# ----------------------------------------------------- EOS / pool accounting
class TestDrainAccounting:

    def test_mid_pipeline_eos_rewinds_and_frees_blocks(self, model_and_params):
        eng = make_engine(model_and_params, depth=2, structured=True)
        free0 = eng.free_blocks
        vocab = byte_vocab(eng.structured.vocab_size)
        compiled = CompiledSchema(SCHEMA, vocab, eos_token_id=EOS)
        reqs = [(i, PROMPT + i,
                 {"temperature": 1.1, "top_k": 25, "seed": 30 + i},
                 compiled) for i in range(2)]
        out = run_fleet(eng, reqs, max_new=64, eos=EOS, retire=True)
        for toks in out.values():
            assert toks[-1] == EOS  # finished mid-burst, not at max_new
        # drain rewound the speculatively-dispatched tail: every block
        # the pipeline reserved past EOS came back
        assert eng.free_blocks == free0
        eng.destroy()

    def test_max_new_exact_under_pipeline(self, model_and_params):
        eng = make_engine(model_and_params, depth=2)
        out = run_fleet(eng, greedy_reqs([1, 2]), max_new=13)
        assert all(len(toks) == 13 for toks in out.values())
        eng.destroy()

    def test_cancel_mid_pipeline_drains_and_survivor_matches(
            self, model_and_params):
        eng_off = make_engine(model_and_params, depth=0)
        want = run_fleet(eng_off, greedy_reqs([2]), max_new=21)[2]
        eng_off.destroy()
        eng = make_engine(model_and_params, depth=2)
        sched = DynamicSplitFuseScheduler(eng, token_budget=48, max_burst=8)
        for uid, p, _, _ in greedy_reqs([1, 2]):
            sched.add_request(uid, p, max_new_tokens=21)
        for _ in range(4):  # prefill + fill the pipeline
            sched.step()
        assert sched._pipeline  # bursts genuinely in flight
        sched.cancel(1)        # must drain, not tear mid-flight state
        out = sched.run_to_completion()
        assert out[2] == want  # survivor's stream untouched by the drain
        eng.destroy()


# ------------------------------------------------------------ token-log fence
class TestTokenLogFencing:

    def test_unfenced_reads_are_typed_errors(self):
        log = TokenLog([1, 2, 3])
        log.append_device(lambda: [4, 5])
        assert log.pending
        for read in (lambda: len(log), lambda: list(log),
                     lambda: log[0], lambda: log + [9]):
            with pytest.raises(UnfencedTokenLogError):
                read()
        log.fence()
        assert not log.pending
        assert list(log) == [1, 2, 3, 4, 5]

    def test_engine_descriptor_log_fences_through_flush(self,
                                                        model_and_params):
        eng = make_engine(model_and_params, depth=2, prefix=True)
        t = int(eng.put([7], [PROMPT], sample="greedy")[0])
        handle = eng.decode_burst_async([7], [[t]], 4)
        desc = eng.state_manager.query(7)
        with pytest.raises(UnfencedTokenLogError):
            len(desc.tokens)  # host read while the burst is in flight
        toks = handle.fetch()
        assert toks.shape == (4, 1)
        desc.tokens.fence()
        # KV content over the burst = entry + first k-1 outputs
        assert list(desc.tokens)[-4:] == [t] + [int(x) for x in toks[:-1, 0]]
        eng.flush(7)
        eng.destroy()

    def test_chain_validation_is_typed(self, model_and_params):
        eng = make_engine(model_and_params, depth=2)
        t1 = int(eng.put([1], [PROMPT], sample="greedy")[0])
        t2 = int(eng.put([2], [PROMPT + 1], sample="greedy")[0])
        h = eng.decode_burst_async([1, 2], [[t1], [t2]], 2)
        with pytest.raises(ValueError, match="uid order"):
            eng.decode_burst_async([2, 1], None, 2, prev=h)
        with pytest.raises(ValueError, match="greedy handle"):
            eng.decode_burst_async(
                [1, 2], None, 2, prev=h,
                sample=[{"temperature": 1.0, "seed": 3}] * 2)
        h2 = eng.decode_burst_async([1, 2], None, 2, prev=h)  # valid chain
        assert h2.fetch().shape == (2, 2)
        for uid in (1, 2):
            eng.flush(uid)
        eng.destroy()


# ------------------------------------------------ one family / the program set
def mixed_run(eng):
    """Greedy, sampled and tapering bursts through the scheduler."""
    run_fleet(eng, greedy_reqs([1, 2]), max_new=21)
    run_fleet(eng, [(3, PROMPT, {"temperature": 1.0, "seed": 5}, None),
                    (4, PROMPT + 1, None, None)], max_new=21)


class TestOneProgramFamily:

    def test_every_depth_runs_one_family(self, model_and_params):
        keys = {}
        for depth in (0, 2):
            eng = make_engine(model_and_params, depth=depth, spec=True)
            mixed_run(eng)
            run_fleet(eng, [(5, REPETITIVE, None, None)], max_new=20)  # verifies
            keys[depth] = set(eng._burst_fns)
            eng.destroy()
            assert {key[0] for key in keys[depth]} == {"burst", "verify"}
        # the pipeline compiles no burst program the fetched form does not
        bursts = {d: {k for k in keys[d] if k[0] == "burst"} for d in keys}
        assert bursts[2] <= bursts[0]
        assert {k[2] for k in bursts[0]} == {None, "sampled"}

    def test_rebinding_decode_burst_on_the_instance_sees_every_burst(
            self, model_and_params):
        # the benchmark's seam (benchmark/harness/spans.py): at depth 0
        # the scheduler reaches the burst through engine.decode_burst,
        # looked up at call time, and each call leaves ONE "burst" record
        # with its four phases closed inside it
        from deepspeed_tpu.utils import tracing
        eng = make_engine(model_and_params, depth=0)
        inner, calls = eng.decode_burst, []

        def counted(batch_uids, batch_tokens, k, *args, **kwargs):
            calls.append((len(batch_uids), k))
            return inner(batch_uids, batch_tokens, k, *args, **kwargs)

        eng.decode_burst = counted
        steps = tracing.RECORDER.steps
        mark = steps[-1].seq if steps else 0
        emitted0 = eng.tokens_emitted
        out = run_fleet(eng, greedy_reqs([1, 2, 3]), max_new=21)
        records = [r for r in tracing.snapshot()["steps"]
                   if r["engine"] == eng.trace_id and r["seq"] > mark]
        bursts = [r for r in records if r["kind"] == "burst"]
        assert calls and [(r["n_seqs"], r["k"]) for r in bursts] == calls
        # (build: after the prompts' step the scheduler had the rung built)
        assert {r["kind"] for r in records} == {"build", "put", "burst"}
        for r in bursts:
            assert [p[0] for p in r["phases"]] == [
                "ds.engine.pack", "ds.engine.dispatch", "ds.engine.fetch",
                "ds.engine.log"]
            assert r["start_ns"] <= r["phases"][0][1] \
                and r["phases"][-1][2] <= r["end_ns"]
            assert r["n_tokens"] == r["n_seqs"] * r["k"] and r["n_ctx_tokens"] > 0
        # every token came through put or a burst the wrapper saw
        puts = sum(r["n_seqs"] for r in records if r["kind"] == "put")
        assert eng.tokens_emitted - emitted0 == puts + sum(n * k for n, k in calls)
        assert sum(len(t) for t in out.values()) == 3 * 21
        eng.destroy()

    @pytest.mark.parametrize("lora,sampled", [(False, False), (False, True),
                                              (True, False), (True, True)])
    def test_burst_layout_is_the_packed_vector(self, lora, sampled):
        # _dispatch_burst asserts that what it concatenates has this size,
        # so every burst in this file holds the engine to the layout
        from deepspeed_tpu.inference.structured.sampling import SAMPLE_META_ROWS
        ms, mb = 4, 16
        lay = _burst_layout(ms, mb, lora=lora, sampled=sampled)
        assert "tokens0" not in lay  # entry tokens are an argument of their own
        want = ["token_seq", "pos0", "tables"] + ["seq_adapters"] * lora \
            + ["sample_meta"] * sampled
        assert list(lay) == want
        spans = list(lay.values())
        assert spans[0][0] == 0 and all(a[1] == b[0] for a, b in zip(spans, spans[1:]))
        assert spans[-1][1] == 2 * ms + (ms + 1) * mb + lora * (ms + 1) \
            + sampled * SAMPLE_META_ROWS * ms


class TestProgramSet:

    def test_pipelined_program_set_evicts_nothing(self, model_and_params):
        # the burst_fn_cache_cap reasoning: a steady pipelined trace
        # (greedy + sampled + constrained, every power-of-two tail)
        # must fit the cache with ZERO evictions — an eviction would
        # retrace a hot program every burst and thrash
        eng = make_engine(model_and_params, depth=2, structured=True)
        vocab = byte_vocab(eng.structured.vocab_size)
        compiled = CompiledSchema(SCHEMA, vocab, eos_token_id=EOS)
        run_fleet(eng, greedy_reqs([1, 2]), max_new=21)
        run_fleet(eng, [(3, PROMPT, {"temperature": 1.0, "seed": 5}, None),
                        (4, PROMPT + 1, None, None)], max_new=21)
        run_fleet(eng, [(5, PROMPT,
                         {"temperature": 1.2, "top_k": 30, "seed": 6},
                         compiled)], max_new=64, eos=EOS, retire=True)
        # repeat the steady mix: every program is now warm
        run_fleet(eng, greedy_reqs([6, 7]), max_new=21)
        run_fleet(eng, [(8, PROMPT, {"temperature": 1.0, "seed": 9}, None)],
                  max_new=21)
        assert eng.burst_fn_evictions == 0
        assert len(eng._burst_fns) <= eng._burst_fn_cap
        eng.destroy()


# ------------------------------------------------------------- sync counter
class TestSyncCounter:

    def test_syncs_per_token_drops_4x(self, model_and_params):
        # the fetched form pays (n+1) host syncs per k-step burst
        # (n entry-token reads + the fetch); the pipeline pays ONE.
        # 6 sequences, bursts of 8: ~7 syncs/burst vs ~1. Prefill puts
        # sync identically at both depths, so the claim is measured over
        # the decode phase — the surface the pipeline optimizes.
        ratios = {}
        for depth in (0, 2):
            eng = make_engine(model_and_params, depth=depth, n_seqs=8)
            sched = DynamicSplitFuseScheduler(eng, token_budget=48,
                                              max_burst=8)
            for uid, p, _, _ in greedy_reqs([1, 2, 3, 4, 5, 6]):
                sched.add_request(uid, p, max_new_tokens=33)
            while any(r.next_token is None
                      for r in sched.requests.values()):
                sched.step()  # prefill (+ first token) via put()
            syncs0, toks0 = eng.host_syncs, eng.tokens_emitted
            sched.run_to_completion()
            decoded = eng.tokens_emitted - toks0
            # SplitFuse mixes a few early decode steps into prefill
            # batches, so a handful of tokens predate the snapshot —
            # the overwhelming majority must still come from bursts
            assert decoded >= 6 * 28
            ratios[depth] = (eng.host_syncs - syncs0) / decoded
            assert eng.syncs_per_generated_token == \
                round(eng.host_syncs / eng.tokens_emitted, 4)
            eng.destroy()
        drop = ratios[0] / ratios[2]
        assert drop >= 4.0, \
            f"pipelined bursts must cut syncs/token >=4x, got {drop:.2f}x"
