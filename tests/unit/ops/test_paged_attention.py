"""Paged decode-attention kernel tests (interpret mode on CPU), vs the
XLA gather reference — analogue of reference
tests/unit/inference/v2/kernels/ragged_ops/."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention, xla_paged_attention


def _case(T=5, H=4, Hkv=2, Dh=16, NB=12, bs=8, MB=3, seed=0, L=1, layer=0):
    """→ (q, K pool, V pool, tables, positions, layer): the pools in the
    stored layout [L, NB, bs, Hkv*Dh]."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(T, H, Dh).astype(np.float32))
    kc = jnp.asarray(rng.randn(L, NB, bs, Hkv * Dh).astype(np.float32))
    vc = jnp.asarray(rng.randn(L, NB, bs, Hkv * Dh).astype(np.float32))
    tabs = jnp.asarray(rng.randint(1, NB, size=(T, MB)).astype(np.int32))
    pos = jnp.asarray(rng.randint(0, MB * bs, size=(T,)).astype(np.int32))
    return q, kc, vc, tabs, pos, jnp.int32(layer)


def test_kernel_matches_xla_reference():
    q, kc, vc, tabs, pos, layer = _case()
    ref = xla_paged_attention(q, kc, vc, tabs, pos, layer)
    got = paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_kernel_gqa_groups():
    q, kc, vc, tabs, pos, layer = _case(H=8, Hkv=2, seed=3)
    ref = xla_paged_attention(q, kc, vc, tabs, pos, layer)
    got = paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_kernel_mha_no_groups():
    q, kc, vc, tabs, pos, layer = _case(H=4, Hkv=4, seed=4)
    ref = xla_paged_attention(q, kc, vc, tabs, pos, layer)
    got = paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("Hkv,G", [(1, 4), (6, 2), (12, 1), (20, 1)])
def test_kernel_odd_kv_head_counts(Hkv, G):
    """Head counts that used to crash Mosaic (round 4 restriction:
    Hkv % 8, plus 2 and 4): the flattened-pool DMA supports ANY count —
    measured compiling and matching on a real v5e for 1/6/12/20."""
    from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported
    assert kernel_supported(128, 16, Hkv)
    q, kc, vc, tabs, pos, layer = _case(H=Hkv * G, Hkv=Hkv, Dh=128, bs=16, seed=Hkv)
    ref = xla_paged_attention(q, kc, vc, tabs, pos, layer)
    got = paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_position_zero_attends_only_first():
    """pos=0 must attend exactly one key (itself at position 0)."""
    q, kc, vc, tabs, _, layer = _case(T=1, seed=5)
    pos = jnp.asarray([0], jnp.int32)
    got = paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True)
    first_v = vc[0, tabs[0, 0], 0].reshape(-1, q.shape[2])  # [Hkv, Dh]
    want = jnp.repeat(first_v, q.shape[1] // first_v.shape[0], axis=0)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_xla_reference_against_dense_softmax():
    """The gather reference itself vs a hand-built dense computation."""
    q, kc, vc, tabs, pos, layer = _case(T=3, seed=6)
    T, H, Dh = q.shape
    Hkv = kc.shape[3] // Dh
    outs = []
    for t in range(T):
        ks = np.asarray(kc)[0][np.asarray(tabs)[t]].reshape(-1, Hkv, Dh)
        vs = np.asarray(vc)[0][np.asarray(tabs)[t]].reshape(-1, Hkv, Dh)
        n = int(pos[t]) + 1
        ks, vs = ks[:n], vs[:n]
        ks = np.repeat(ks, H // Hkv, axis=1)
        vs = np.repeat(vs, H // Hkv, axis=1)
        s = np.einsum("hd,khd->hk", np.asarray(q)[t], ks) / np.sqrt(Dh)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("hk,khd->hd", p, vs))
    ref = xla_paged_attention(q, kc, vc, tabs, pos, layer)
    np.testing.assert_allclose(np.asarray(ref), np.stack(outs), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("path", ["kernel", "xla"])
@pytest.mark.parametrize("Hkv,G", [(1, 4), (6, 2), (8, 4)])
def test_layer_index_reads_that_layer_bit_for_bit(path, Hkv, G):
    """The whole 3-layer pool with a layer index gives, for every layer,
    exactly what the call on that layer's own one-layer pool gives —
    ragged positions, and a padded token (null block 0, position 0)
    among them. Traced layer index, as the layer scan passes it."""
    q, kc, vc, tabs, pos, _ = _case(T=6, H=Hkv * G, Hkv=Hkv, Dh=128, NB=10, bs=16, MB=3,
                                    seed=10 + Hkv, L=3)
    tabs = tabs.at[-1].set(0)
    pos = pos.at[-1].set(0).at[0].set(3 * 16 - 1).at[1].set(0)
    if path == "kernel":
        fn = lambda *a: paged_decode_attention(*a, interpret=True)
    else:
        fn = xla_paged_attention
    by_index = jax.jit(fn)
    for layer in range(3):
        got = by_index(q, kc, vc, tabs, pos, jnp.int32(layer))
        want = fn(q, kc[layer:layer + 1], vc[layer:layer + 1], tabs, pos, jnp.int32(0))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the layers do differ: the index is not ignored
    assert not np.array_equal(np.asarray(by_index(q, kc, vc, tabs, pos, jnp.int32(0))),
                              np.asarray(by_index(q, kc, vc, tabs, pos, jnp.int32(2))))


# ------------------------------------------------- ragged_forward, end to end
def _stacked_layer_scan(real_scan, used):
    """The form the layer scan had before the pool became its carry, as a
    drop-in for ``jax.lax.scan``: every layer scatters into a copy of the
    pool, its own slice is cut out of that, and the slices are stacked
    into a new pool. Same layer function and the same layer index (the
    step also finds its experts by it), the other data flow; any other
    scan goes to the real one."""
    def scan(step, carry, xs, *args, **kwargs):
        is_layer_scan = (isinstance(carry, tuple) and len(carry) == 3
                         and carry[0].ndim == 2 and carry[1].ndim == 4)
        if not is_layer_scan:
            return real_scan(step, carry, xs, *args, **kwargs)
        h, kc, vc = carry
        ks, vs = [], []
        used.append(kc.shape)
        for layer in range(kc.shape[0]):
            (h, k1, v1), _ = step((h, kc, vc), jax.tree.map(lambda a: a[layer], xs))
            ks.append(k1[layer:layer + 1])
            vs.append(v1[layer:layer + 1])
        return (h, jnp.concatenate(ks), jnp.concatenate(vs)), None
    return scan


def _serve_chunks_then_burst(build, preset, tp, monkeypatch=None):
    """Prefill two prompts in chunks, then a 4-step greedy burst →
    (first tokens, burst tokens, K pool, V pool)."""
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    used = []
    if monkeypatch is not None:
        monkeypatch.setattr(jax.lax, "scan", _stacked_layer_scan(jax.lax.scan, used))
    model = build(preset, remat=False)
    params = model.init(jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngineV2(
        model=model, params=params, dtype=jnp.float32,
        config=RaggedInferenceEngineConfig(
            kv_block_size=8, tensor_parallel_degree=tp,
            state_manager=DSStateManagerConfig(max_ragged_batch_size=32,
                                               max_ragged_sequence_count=4,
                                               max_tracked_sequences=4, max_context=64)))
    a = (np.arange(13, dtype=np.int32) * 7) % 250
    b = (np.arange(6, dtype=np.int32) * 11 + 3) % 250
    tok_b = int(engine.put([1, 2], [a[:8], b], sample="greedy")[1])
    tok_a = int(engine.put([1], [a[8:]], sample="greedy")[0])
    burst = engine.decode_burst([1, 2], [tok_a, tok_b], 4)
    if monkeypatch is not None:
        monkeypatch.undo()
        assert len(used) == 2, used  # the step program and the burst were traced this way
    return (tok_a, tok_b), np.asarray(burst), np.asarray(engine.kv_cache.k), \
        np.asarray(engine.kv_cache.v)


@pytest.mark.parametrize("family,preset,tp", [
    ("llama", "debug", 1), ("gpt", "gpt2-debug", 1), ("gpt", "bloom-debug", 1),
    ("llama", "mixtral-debug", 1), ("llama", "debug", 2)])
def test_ragged_forward_matches_stacked_form(family, preset, tp, monkeypatch):
    """Prefill in chunks, then a burst: the scan that carries the pool
    gives the same tokens and the same pool contents, bit for bit, as the
    per-layer-slice form it replaced — Llama family, GPT family (learned
    positions; ALiBi), Mixtral's MoE block, and TP = 2 on the CPU's
    virtual devices."""
    from deepspeed_tpu.models import build_gpt, build_llama
    build = build_llama if family == "llama" else build_gpt
    first, burst, k, v = _serve_chunks_then_burst(build, preset, tp)
    first0, burst0, k0, v0 = _serve_chunks_then_burst(build, preset, tp, monkeypatch)
    assert first == first0
    np.testing.assert_array_equal(burst, burst0)
    assert k.shape == k0.shape and k.ndim == 4 and np.abs(k[:, 1:]).max() > 0
    np.testing.assert_array_equal(k, k0)
    np.testing.assert_array_equal(v, v0)
