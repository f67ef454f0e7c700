"""A program's width does not reach its streams: the same requests
through engines of ``max_ragged_sequence_count`` 8 and 64 - so 5 and 61
padding rows in every decode program, which the paged kernel is told
(``model_runner._live_rows``) and does not run - emit identical streams,
greedy and sampled, through ``put`` and through bursts. The kernel runs
interpreted (``DS_PALLAS=1``) at a head size it takes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, RaggedInferenceEngineConfig,
                                        StructuredConfig)
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.utils import tracing

PROMPTS = {u: ((np.arange(5 + 3 * u) * (u + 3)) % 250).astype(np.int32) for u in (1, 2, 3)}


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug", hidden_size=256, num_attention_heads=2, num_key_value_heads=1)
    assert model.config.head_dim == 128
    return model, model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]


def serve(model_and_params, n_seqs, sample, max_burst):
    """→ ({uid: generated}, the engine's step records)."""
    model, params = model_and_params
    engine = InferenceEngineV2(
        model=model, params=params, dtype=jnp.float32,
        config=RaggedInferenceEngineConfig(
            kv_block_size=8, structured=StructuredConfig(enabled=sample is not None),
            state_manager=DSStateManagerConfig(max_ragged_batch_size=64,
                                               max_ragged_sequence_count=n_seqs,
                                               max_tracked_sequences=n_seqs, max_context=64)))
    sched = DynamicSplitFuseScheduler(engine, token_budget=64, max_burst=max_burst)
    for uid, prompt in PROMPTS.items():
        sched.add_request(uid, prompt, max_new_tokens=9,
                          sample=sample and dict(sample, seed=100 + uid))
    out = sched.run_to_completion()
    records = [r for r in tracing.snapshot()["steps"]
               if r["engine"] == engine.trace_id and r["kind"] != "setup"]
    assert set(engine.attention_impls.values()) == {"pallas_paged"}
    engine.destroy()
    return out, records


@pytest.mark.parametrize("sample", [None, {"temperature": 0.9, "top_k": 40}],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("max_burst", [1, 4], ids=["put", "burst"])
def test_streams_do_not_depend_on_the_programs_width(model_and_params, monkeypatch, sample,
                                                     max_burst):
    monkeypatch.setenv("DS_PALLAS", "1")
    narrow, _ = serve(model_and_params, 8, sample, max_burst)
    wide, records = serve(model_and_params, 64, sample, max_burst)
    assert narrow == wide
    assert all(len(tokens) == 9 for tokens in wide.values())
    # the records say what the program ran beside what was live
    kinds = {r["kind"] for r in records}
    assert kinds == ({"put", "burst"} if max_burst > 1 else {"put"})
    for r in records:
        assert r["n_rows"] >= r["n_tokens"] > 0
        if r["kind"] == "burst":
            assert r["n_rows"] == r["k"] * 64 and r["n_tokens"] == r["k"] * r["n_seqs"]
        else:
            assert r["n_rows"] == int(r["program"])
