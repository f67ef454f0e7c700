"""Tensor/expert-parallel v2 ragged serving.

Capability match for the reference's sharded FastGen path
(``deepspeed/inference/v2/engine_v2.py:30`` over
``model_implementations/sharding/`` — the headline is Llama-2-70B on 4
ranks): the same ragged engine must produce IDENTICAL results when its
weights and KV pool are sharded over a serving mesh. Runs on the
virtual 8-device CPU mesh from conftest."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.models import build_gpt, build_llama

SM = DSStateManagerConfig(max_ragged_batch_size=64, max_ragged_sequence_count=4,
                          max_tracked_sequences=4, max_context=64)


def _cfg(**kw):
    return RaggedInferenceEngineConfig(kv_block_size=8, state_manager=SM, **kw)


def _params(model, seed=0):
    return model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]


def _serve(model, params, engine_cfg, prompts, n_decode=3):
    """Greedy-serve each prompt through a fresh engine; returns
    (per-step last logits list, generated token list)."""
    engine = InferenceEngineV2(model=model, config=engine_cfg, params=params,
                               dtype=jnp.float32)
    logits_trace, generated = [], {}
    uids = list(range(len(prompts)))
    out = engine.put(uids, prompts)
    logits_trace.append(out.copy())
    toks = [int(np.argmax(out[i])) for i in range(len(prompts))]
    generated = {u: [t] for u, t in zip(uids, toks)}
    for _ in range(n_decode - 1):
        out = engine.put(uids, [[generated[u][-1]] for u in uids])
        logits_trace.append(out.copy())
        for i, u in enumerate(uids):
            generated[u].append(int(np.argmax(out[i])))
    return logits_trace, generated


def _assert_same_serving(model, params, sharded_cfg, prompts):
    ref_logits, ref_tokens = _serve(model, params, _cfg(), prompts)
    tp_logits, tp_tokens = _serve(model, params, sharded_cfg, prompts)
    assert tp_tokens == ref_tokens  # identical greedy tokens
    for a, b in zip(ref_logits, tp_logits):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tp", [2, 4])
def test_llama_tp_serving_matches_single_device(tp):
    """GQA Llama (H=4, Hkv=2): heads shard over 'tensor', KV pool shards
    when Hkv divides, and the column/row Megatron pattern reproduces the
    single-device tokens exactly."""
    model = build_llama("debug", remat=False)
    params = _params(model)
    prompts = [(np.arange(9, dtype=np.int32) * 5) % 250,
               (np.arange(12, dtype=np.int32) * 11) % 250]
    _assert_same_serving(model, params, _cfg(tensor_parallel_degree=tp), prompts)


def test_llama_tp_kv_pool_actually_sharded():
    model = build_llama("debug", remat=False)
    engine = InferenceEngineV2(model=model, config=_cfg(tensor_parallel_degree=2),
                               params=_params(model), dtype=jnp.float32)
    # KV pool [L, NB, bs, Hkv*Dh] sharded over 'tensor' on the flattened head dim
    # (Hkv=2: one whole KV head a shard)
    assert len(engine.kv_cache.k.sharding.device_set) == 2
    spec = engine.kv_cache.k.sharding.spec
    assert spec[3] == "tensor"
    assert engine.kv_cache.k.addressable_shards[0].data.shape[3] == engine.kv_cache.head_dim
    # q_proj kernel column-sharded, o_proj row-sharded
    qk = engine.params["model"]["layers"]["self_attn"]["q_proj"]["kernel"]
    ok = engine.params["model"]["layers"]["self_attn"]["o_proj"]["kernel"]
    assert qk.sharding.spec[-1] == "tensor"
    assert ok.sharding.spec[-2] == "tensor"
    # per-device param bytes roughly halve for the sharded leaves
    assert qk.addressable_shards[0].data.shape[-1] == qk.shape[-1] // 2


def test_falcon_mqa_tp_serving_replicated_kv():
    """MQA (Hkv=1) under tp=2: query heads shard, the single KV head
    replicates (reference sharding/attn.py does the same) — results
    must still match exactly."""
    model = build_gpt("falcon-debug", remat=False)
    params = _params(model)
    prompts = [(np.arange(11, dtype=np.int32) * 7) % 250]
    _assert_same_serving(model, params, _cfg(tensor_parallel_degree=2), prompts)


def test_mixtral_ep_serving_matches_single_device():
    """Mixtral-style MoE (E=4) with expert_parallel_degree=2: expert
    weights stay on their shard (manual shard_map grouped GEMM + psum)
    and serving is dropless-exact vs the single-device engine."""
    model = build_llama("mixtral-debug", remat=False, moe_capacity_factor=64.0)
    params = _params(model, seed=2)
    prompts = [(np.arange(10, dtype=np.int32) * 13) % 250,
               (np.arange(7, dtype=np.int32) * 3) % 250]
    _assert_same_serving(model, params, _cfg(expert_parallel_degree=2), prompts)


def test_mixtral_tp_ep_composed_serving():
    """TP x EP composition (tensor=2, expert=2 over 4 devices): expert
    dim AND feature dims shard simultaneously."""
    model = build_llama("mixtral-debug", remat=False, moe_capacity_factor=64.0)
    params = _params(model, seed=3)
    prompts = [(np.arange(8, dtype=np.int32) * 9) % 250]
    _assert_same_serving(
        model, params, _cfg(tensor_parallel_degree=2, expert_parallel_degree=2), prompts)


def test_mixtral_tp4_ep2_full_mesh_serving():
    """World-size-8 composition (tensor=4, expert=2 — every virtual CPU
    device): the widest sharding the debug models support; parity vs the
    single-device engine proves the layout scales past the 4-device
    lanes."""
    model = build_llama("mixtral-debug", remat=False, moe_capacity_factor=64.0)
    params = _params(model, seed=5)
    prompts = [(np.arange(9, dtype=np.int32) * 17) % 250,
               (np.arange(6, dtype=np.int32) * 5) % 250]
    _assert_same_serving(
        model, params, _cfg(tensor_parallel_degree=4, expert_parallel_degree=2), prompts)


def test_expert_weights_stay_sharded():
    model = build_llama("mixtral-debug", remat=False)
    engine = InferenceEngineV2(model=model, config=_cfg(expert_parallel_degree=2),
                               params=_params(model), dtype=jnp.float32)
    w1 = engine.params["model"]["layers"]["moe_mlp"]["deepspeed_moe"]["experts_w1"]
    assert w1.sharding.spec[1] == "expert"  # [L, E, D, F] expert-sharded
    assert w1.addressable_shards[0].data.shape[1] == w1.shape[1] // 2


def test_suspend_resume_under_tp():
    """KV host swapping composes with a tensor-sharded pool: offload
    gathers the sharded slices, restore's donated scatter re-shards —
    continuation matches the uninterrupted run."""
    model = build_llama("debug")
    params = _params(model)
    engine = InferenceEngineV2(model=model, config=_cfg(tensor_parallel_degree=2),
                               params=params, dtype=jnp.float32)
    prompt = (np.arange(12, dtype=np.int32) * 7) % 250
    tok = int(engine.put([1], [prompt], sample="greedy")[0])
    ref = int(engine.put([1], [[tok]], sample="greedy")[0])
    engine.flush(1)
    tok2 = int(engine.put([2], [prompt], sample="greedy")[0])
    assert tok2 == tok
    engine.suspend(2)
    engine.put([3], [np.arange(30, dtype=np.int32)])  # trample freed blocks
    engine.flush(3)
    engine.resume(2)
    assert int(engine.put([2], [[tok2]], sample="greedy")[0]) == ref
