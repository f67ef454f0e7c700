"""Structured generation: per-sequence on-device sampling, rejection-
sampled speculative verification, and grammar/JSON-schema constrained
decoding.

Contracts under test:

- the counter-based PRNG keys every sampled token by (request seed,
  absolute position), so the same seed replays bit-identically across
  fresh sequences, fresh engines, step/burst boundaries, and batch
  compositions — and different seeds draw genuinely different streams
  (chi-square sanity against the model's own distribution);
- speculative decoding stays live under sampled traffic: the
  rejection-sampled verify emits streams bit-identical to the spec-off
  sampled run, per seed;
- schema-constrained lanes emit 100% schema-valid JSON under greedy
  and sampled decoding (finite-language schemas terminate regardless
  of model weights);
- the kill switches build the exact pre-structured pipeline: greedy
  traffic compiles the same program keys as before this subsystem
  existed, and DS_CONSTRAINED=0 wins over config.structured.enabled.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.structured.grammar import (CompiledSchema,
                                                        SchemaCompileError,
                                                        byte_vocab, detokenize,
                                                        json_schema_to_regex,
                                                        schema_fingerprint)
from deepspeed_tpu.inference.structured.prng import (base_sampling_key,
                                                     derive_seed, token_keys)
from deepspeed_tpu.inference.structured.store import SchemaCompilerCache
from deepspeed_tpu.inference.v2 import (DSStateManagerConfig,
                                        DynamicSplitFuseScheduler,
                                        InferenceEngineV2,
                                        RaggedInferenceEngineConfig,
                                        SpecDecodeConfig, StructuredConfig)
from deepspeed_tpu.models import build_llama

EOS = 2
# finite-language schema: every field's value set is finite, so the
# token DFA's language is finite and decode MUST reach EOS no matter
# what the (untrained) model's logits prefer — the right pin for
# 100%-validity assertions
SCHEMA = {"type": "object",
          "properties": {"ok": {"type": "boolean"},
                         "mode": {"enum": ["fast", "safe"]}},
          "required": ["ok", "mode"]}


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(model_and_params, structured=False, spec=False, n_seqs=4,
                max_context=128, batch=64):
    model, params = model_and_params
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=8,
        num_kv_blocks=0,
        spec_decode=SpecDecodeConfig(enabled=spec),
        structured=StructuredConfig(enabled=structured),
        state_manager=DSStateManagerConfig(max_ragged_batch_size=batch,
                                           max_ragged_sequence_count=n_seqs,
                                           max_tracked_sequences=n_seqs,
                                           max_context=max_context))
    return InferenceEngineV2(model=model, config=cfg, params=params,
                             dtype=jnp.float32)


def sampled_rollout(engine, uid, prompt, n, spec):
    """Stepwise sampled reference: prefill + n-1 decode steps via put()."""
    t = int(engine.put([uid], [prompt], sample=spec)[0])
    out = [t]
    for _ in range(n - 1):
        t = int(engine.put([uid], [[t]], sample=spec)[0])
        out.append(t)
    return out


PROMPT = (np.arange(1, 17) % 250).astype(np.int32)          # 16 tokens
REPETITIVE = np.tile(np.array([7, 8, 9, 10], np.int32), 6)  # 24 tokens


# -------------------------------------------------------------------- grammar
class TestGrammar:
    """Schema → char regex → token DFA, no engine involved."""

    def test_finite_schema_accepts_its_own_language(self):
        toks = byte_vocab(128)
        c = CompiledSchema(SCHEMA, toks, eos_token_id=EOS)
        text = '{"ok":true,"mode":"fast"}'
        st = c.start
        for ch in text:
            # byte_vocab aliases chars; avoid the EOS id, whose content
            # column is cleared (EOS is control, never content)
            t = next(i for i, s in enumerate(toks) if s == ch and i != EOS)
            st = c.advance(st, t)
        assert c.is_accepting(st)
        # EOS is legal exactly in accepting states, nowhere mid-object
        assert c.mask[st, EOS]
        assert not c.mask[c.start, EOS]

    def test_illegal_token_raises_on_host_advance(self):
        toks = byte_vocab(128)
        c = CompiledSchema(SCHEMA, toks, eos_token_id=EOS)
        with pytest.raises(ValueError):
            c.advance(c.start, toks.index("x"))  # objects open with '{'

    def test_every_reachable_state_allows_something(self):
        """Dead-end detection: a vocab that cannot close the object
        (no '}' token) must be rejected at compile time, never zero a
        softmax row mid-stream."""
        toks = [ch for ch in byte_vocab(128) if ch != "}"]
        with pytest.raises(SchemaCompileError, match="dead-end"):
            CompiledSchema(SCHEMA, toks, eos_token_id=EOS)

    def test_regex_lowering_and_fingerprint_stability(self):
        pat = json_schema_to_regex(SCHEMA)
        assert "true" in pat and "fast" in pat
        assert schema_fingerprint(SCHEMA) == schema_fingerprint(
            json.loads(json.dumps(SCHEMA)))
        assert schema_fingerprint(SCHEMA) != schema_fingerprint(
            {"type": "object", "properties": {}})

    def test_compiler_cache_compiles_once(self):
        cache = SchemaCompilerCache()
        toks = byte_vocab(128)
        a = cache.get_or_compile(SCHEMA, toks, eos_token_id=EOS)
        b = cache.get_or_compile(SCHEMA, toks, eos_token_id=EOS)
        assert a is b
        assert cache.compiles == 1 and cache.hits == 1
        # a different vocab is a different cache entry (different DFA)
        cache.get_or_compile(SCHEMA, byte_vocab(200), eos_token_id=EOS)
        assert cache.compiles == 2


# ----------------------------------------------------------------------- prng
class TestCounterPrng:

    def test_derive_seed_deterministic_and_in_range(self):
        seeds = [derive_seed(0, uid) for uid in range(64)]
        assert seeds == [derive_seed(0, uid) for uid in range(64)]
        assert all(0 <= s < 2 ** 31 for s in seeds)
        assert len(set(seeds)) == 64  # no collisions in a small fleet
        assert derive_seed(1, 0) != derive_seed(0, 0)  # base matters

    def test_token_keys_depend_only_on_seed_and_position(self):
        base = base_sampling_key(0)
        k1 = np.asarray(token_keys(base, jnp.array([5, 5]), jnp.array([3, 4])))
        k2 = np.asarray(token_keys(base, jnp.array([5]), jnp.array([3])))
        assert (k1[0] == k2[0]).all()          # same (seed, pos) → same key
        assert not (k1[0] == k1[1]).all()      # position moves the key
        k3 = np.asarray(token_keys(base, jnp.array([6]), jnp.array([3])))
        assert not (k1[0] == k3[0]).all()      # seed moves the key


# ----------------------------------------------------- seeded determinism
class TestSeededSampling:

    @pytest.fixture(scope="class")
    def engine(self, model_and_params):
        return make_engine(model_and_params)

    def test_same_seed_replays_bit_identically(self, engine):
        spec = {"temperature": 1.2, "top_k": 20, "seed": 41}
        a = sampled_rollout(engine, 900, PROMPT, 12, spec)
        engine.flush(900)
        b = sampled_rollout(engine, 901, PROMPT, 12, spec)
        engine.flush(901)
        assert a == b

    def test_different_seeds_draw_different_streams(self, engine):
        a = sampled_rollout(engine, 902, PROMPT, 12,
                            {"temperature": 1.2, "top_k": 20, "seed": 1})
        engine.flush(902)
        b = sampled_rollout(engine, 903, PROMPT, 12,
                            {"temperature": 1.2, "top_k": 20, "seed": 2})
        engine.flush(903)
        assert a != b

    def test_step_and_burst_paths_agree(self, model_and_params):
        """The burst scan keys token i by pos0 + i + 1 — exactly the
        positions the stepwise path uses — so burst length is not
        observable in the stream."""
        engine = make_engine(model_and_params)
        sampling = {"temperature": 1.3, "top_k": 16}
        runs = {}
        for burst in (1, 4):
            sched = DynamicSplitFuseScheduler(engine, max_burst=burst)
            for u in (0, 1):
                sched.add_request(u, PROMPT + u, max_new_tokens=10,
                                  sample=dict(sampling, seed=100 + u))
            runs[burst] = sched.run_to_completion()
        assert runs[1] == runs[4]
        engine.destroy()

    def test_top_k1_is_greedy(self, engine):
        g = sampled_rollout(engine, 904, PROMPT, 8, "greedy")
        engine.flush(904)
        s = sampled_rollout(engine, 905, PROMPT, 8,
                            {"temperature": 0.7, "top_k": 1, "seed": 9})
        engine.flush(905)
        assert s == g

    def test_ds_seed_anchors_the_fleet_stream(self, model_and_params,
                                              monkeypatch):
        """DS_SEED is the fleet-wide determinism anchor: engines built
        under the same DS_SEED replay a given request seed identically;
        a different DS_SEED moves every stream."""
        spec = {"temperature": 1.2, "top_k": 20, "seed": 17}
        streams = {}
        for ds_seed in ("0", "0", "777"):
            monkeypatch.setenv("DS_SEED", ds_seed)
            engine = make_engine(model_and_params)
            streams.setdefault(ds_seed, []).append(
                sampled_rollout(engine, 1, PROMPT, 10, spec))
            engine.destroy()
        assert streams["0"][0] == streams["0"][1]
        assert streams["0"][0] != streams["777"][0]

    def test_chi_square_sanity_across_seeds(self, engine):
        """Across many seeds the first sampled token must follow the
        model's own (top-k renormalized) distribution — catches a
        sampler that ignores the logits or the seed entirely."""
        logits = np.asarray(engine.put([906], [PROMPT]), np.float32)[0]
        engine.flush(906)
        k = 8
        top = np.argsort(logits)[::-1][:k]
        z = logits[top] - logits[top].max()
        p = np.exp(z) / np.exp(z).sum()
        n = 250
        counts = {int(t): 0 for t in top}
        for seed in range(n):
            tok = int(engine.put([907], [PROMPT],
                                 sample={"temperature": 1.0, "top_k": k,
                                         "seed": seed})[0])
            engine.flush(907)
            assert tok in counts, f"seed {seed} drew outside top-{k}"
            counts[tok] += 1
        exp = n * p
        obs = np.array([counts[int(t)] for t in top], np.float64)
        stat = float(((obs - exp) ** 2 / np.maximum(exp, 1e-9)).sum())
        # dof = 7; p(chi2 > 35) < 1e-5 — deterministic seeds, no flake
        assert stat < 35.0, f"chi-square {stat:.1f} over {dict(counts)}"
        assert (obs > 0).sum() >= k // 2  # genuinely spread, not a point mass


# ------------------------------------------------- rejection-sampled spec
class TestRejectionSampledSpec:

    def test_spec_on_off_sampled_streams_bit_identical(self, model_and_params):
        """Acceptance = exact match against the counter-keyed draw from
        the filtered target — for point-mass n-gram drafts that IS the
        rejection-sampling scheme, and it makes the emitted stream
        bit-identical to the spec-off run per seed.

        The traffic makes the drafter's part certain: at temperature 0.5
        over the top 2 the sampled streams repeat themselves (229 229 84
        229 84 229 91 147 91 147 ... for seed 50), so the n-gram drafter
        finds matches and the verify both accepts and rejects drafts
        (9 drafted, 3 accepted when this was written) — at temperature
        1.1 over the top 24 twelve tokens never repeated and nothing was
        drafted, so nothing was compared."""
        runs = {}
        for spec_on in (False, True):
            engine = make_engine(model_and_params, spec=spec_on)
            sched = DynamicSplitFuseScheduler(engine, max_burst=4)
            for i in range(3):
                sched.add_request(i, REPETITIVE + i, max_new_tokens=12,
                                  sample={"temperature": 0.5, "top_k": 2,
                                          "seed": 50 + i})
            runs[spec_on] = sched.run_to_completion()
            if spec_on:
                st = engine.spec
                assert st.drafted > 0, "spec decode never drafted"
                assert st.accepted >= 1, "no draft was accepted"
                assert st.drafted - st.accepted >= 1, "no draft was rejected"
            engine.destroy()
        assert runs[True] == runs[False]


# ------------------------------------------------------------- constrained
class TestConstrainedDecoding:

    @pytest.fixture(scope="class")
    def engine(self, model_and_params):
        # spec on too: schema rows must bail to plain bursts, not break
        return make_engine(model_and_params, structured=True, spec=True)

    @pytest.fixture(scope="class")
    def vocab(self, engine):
        return byte_vocab(engine.structured.vocab_size)

    def _run(self, engine, vocab, sample_specs):
        compiled = CompiledSchema(SCHEMA, vocab, eos_token_id=EOS)
        sched = DynamicSplitFuseScheduler(engine, max_burst=4,
                                          eos_token_id=EOS)
        for i, spec in enumerate(sample_specs):
            sched.add_request(i, PROMPT + i, max_new_tokens=64,
                              sample=spec, schema=compiled)
        out = sched.run_to_completion()
        for i in out:
            sched.retire(i)
        return out

    def test_sampled_lanes_emit_only_schema_valid_json(self, engine, vocab):
        specs = [{"temperature": 1.2, "top_k": 30, "seed": 7 + i}
                 for i in range(3)]
        out = self._run(engine, vocab, specs)
        assert len(out) == 3
        for i, toks in out.items():
            assert toks[-1] == EOS, f"lane {i} never terminated: {toks}"
            doc = json.loads(detokenize(toks[:-1], vocab))
            assert isinstance(doc["ok"], bool)
            assert doc["mode"] in ("fast", "safe")

    def test_greedy_constrained_lane_valid_too(self, engine, vocab):
        out = self._run(engine, vocab, [None])
        toks = out[0]
        assert toks[-1] == EOS
        doc = json.loads(detokenize(toks[:-1], vocab))
        assert set(doc) == {"ok", "mode"}

    def test_constrained_sampled_replays_per_seed(self, engine, vocab):
        spec = {"temperature": 1.4, "top_k": 40, "seed": 99}
        a = self._run(engine, vocab, [spec])
        b = self._run(engine, vocab, [spec])
        assert a == b

    def test_flush_releases_schema_lease(self, engine, vocab):
        compiled = CompiledSchema(SCHEMA, vocab, eos_token_id=EOS)
        engine.bind_schema(77, compiled)
        assert engine.structured.bound(77)
        engine.put([77], [PROMPT], sample={"temperature": 1.0, "seed": 1})
        engine.flush(77)
        assert not engine.structured.bound(77)


# ------------------------------------------------------------- kill switches
class TestKillSwitches:

    def test_greedy_program_keys_unchanged(self, model_and_params):
        """DS_CONSTRAINED off + sample=None is the exact pre-structured
        pipeline: greedy bursts/verifies compile under the same program
        keys as before this subsystem existed, and no sampled program is
        ever built."""
        engine = make_engine(model_and_params, spec=True)
        sched = DynamicSplitFuseScheduler(engine, max_burst=4)
        for i in range(2):
            sched.add_request(i, REPETITIVE + i, max_new_tokens=10)
        sched.run_to_completion()
        keys = list(engine._burst_fns)
        assert keys, "no burst program compiled"
        for key in keys:
            assert key[0] in ("burst", "verify")
            if key[0] == "burst":
                assert len(key) == 3 and key[2] is None, key
            else:
                assert len(key) == 2, key
        engine.destroy()

    def test_sampled_keys_isolated_from_greedy(self, model_and_params):
        engine = make_engine(model_and_params)
        sched = DynamicSplitFuseScheduler(engine, max_burst=4)
        sched.add_request(0, PROMPT, max_new_tokens=8)
        sched.add_request(1, PROMPT + 1, max_new_tokens=8,
                          sample={"temperature": 1.1, "seed": 3})
        sched.run_to_completion()
        kinds = {key[2] for key in engine._burst_fns if key[0] == "burst"}
        assert kinds == {"sampled"}  # a mixed batch samples every row
        engine.destroy()

    def test_ds_constrained_env_wins_over_config(self, model_and_params,
                                                 monkeypatch):
        monkeypatch.setenv("DS_CONSTRAINED", "0")
        engine = make_engine(model_and_params, structured=True)
        assert engine.structured is None
        with pytest.raises(RuntimeError, match="constrained"):
            engine.bind_schema(1, SCHEMA)
        engine.destroy()
        monkeypatch.setenv("DS_CONSTRAINED", "1")
        engine = make_engine(model_and_params, structured=False)
        assert engine.structured is not None
        engine.destroy()

    def test_schema_on_unstructured_engine_rejected_typed(self,
                                                          model_and_params):
        engine = make_engine(model_and_params)
        sched = DynamicSplitFuseScheduler(engine)
        with pytest.raises(ValueError, match="constrained"):
            sched.add_request(0, PROMPT, schema=SCHEMA)
        engine.destroy()
