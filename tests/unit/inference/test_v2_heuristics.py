"""v2 kernel-implementation registry (reference
inference/v2/modules/heuristics.py: config-driven selection)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.modules import implementations, instantiate_attn
from deepspeed_tpu.models import build_llama


def test_registry_lists_implementations():
    # in priority order; the two of the latent state kind are only ever offered to it
    assert implementations("attention") == ["pallas_paged", "pallas_paged_sharded",
                                            "xla_gather", "pallas_paged_mla", "xla_gather_mla"]


def test_auto_selection_without_pallas_falls_back_to_xla(monkeypatch):
    # kernels disabled (as on the CPU backend) → gather path wins
    monkeypatch.setenv("DS_PALLAS", "0")
    name, fn = instantiate_attn(None, 128, 16, (4, 8, 128), (2, 8, 16, 2 * 128), None,
                                max_blocks=4)
    assert name == "xla_gather" and callable(fn)


def test_alibi_always_xla():
    alibi = jnp.ones(4)
    name, _ = instantiate_attn(None, 128, 16, (4, 4, 128), (2, 8, 16, 4 * 128), alibi,
                               max_blocks=4)
    assert name == "xla_gather"


def test_override_pins_implementation():
    name, _ = instantiate_attn(None, 128, 16, (4, 8, 128), (2, 8, 16, 2 * 128), None,
                               max_blocks=4, override="xla_gather")
    assert name == "xla_gather"
    with pytest.raises(ValueError, match="no attention implementation"):
        instantiate_attn(None, 128, 16, (4, 8, 128), (2, 8, 16, 2 * 128), None,
                         max_blocks=4, override="nonexistent")


def test_pinned_kernel_raises_instead_of_degrading(monkeypatch):
    """A pinned ``pallas_paged`` that cannot run is an error naming the
    reason, never the gather reference under the kernel's name: no
    Mosaic on this backend, and (kernels forced on) a block table past
    the SMEM budget — which ``supports()`` sees, so an unpinned engine
    visibly selects ``xla_gather`` there."""
    args = (None, 128, 16, (4, 8, 128), (2, 8, 16, 2 * 128), None)
    with pytest.raises(ValueError, match="pinned attention='pallas_paged'.*backend='cpu'"):
        instantiate_attn(*args, max_blocks=4, override="pallas_paged")
    monkeypatch.setenv("DS_PALLAS", "1")
    assert instantiate_attn(*args, max_blocks=4)[0] == "pallas_paged"
    wide = (None, 128, 16, (768, 8, 128), (2, 8, 16, 2 * 128), None)
    assert instantiate_attn(*wide, max_blocks=512)[0] == "xla_gather"
    with pytest.raises(ValueError, match="tokens=768, max_blocks=512"):
        instantiate_attn(*wide, max_blocks=512, override="pallas_paged")


def test_kernel_entry_refuses_rather_than_falls_back():
    """Called compiled (``interpret=False``) on a shape Mosaic cannot
    take, the kernel entry raises — it used to return the reference."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention
    tab = jnp.zeros((4, 2), jnp.int32)
    # a head of 32, and one head of 64 (two make a 128-lane slice: PR 41; one does not)
    for head_dim, kv_heads in ((32, 2), (64, 1)):
        q = jnp.zeros((4, 4, head_dim))
        kc = jnp.zeros((1, 8, 16, kv_heads * head_dim))
        with pytest.raises(ValueError, match="head_dim % 128"):
            paged_decode_attention(q, kc, kc, tab, jnp.zeros(4, jnp.int32), 0, interpret=False)


def test_engine_config_override_serves_correctly():
    """implementation_overrides flows from the engine config into the
    ragged step and still produces correct logits."""
    model = build_llama("debug", remat=False)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=8,
        implementation_overrides={"attention": "xla_gather"},
        state_manager=DSStateManagerConfig(max_ragged_batch_size=64,
                                           max_ragged_sequence_count=4,
                                           max_tracked_sequences=4, max_context=64))
    engine = InferenceEngineV2(model=model, config=cfg, params=params,
                               dtype=jnp.float32)
    ids = (np.arange(9, dtype=np.int32) * 5) % 250
    out = engine.put([1], [ids])
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    want = np.asarray(model.apply({"params": p32}, jnp.asarray(ids)[None, :]))[0, -1]
    np.testing.assert_allclose(out[0], want, rtol=2e-4, atol=2e-4)
    # the engine reports what its one traced program (nine tokens: the 32-row rung) selected
    assert engine.put_buckets == (4, 32, 64)
    assert engine.attention_impls == {32: "xla_gather"}
