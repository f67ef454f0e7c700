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


# ------------------------------------------------------------- tiles of blocks
BS, DH, MAX_BLOCKS = 16, 128, 40


def _tiled_case(positions, dtype, Hkv=2, G=2, MB=MAX_BLOCKS, seed=0, poison=None):
    """One sequence a token, each with a block table of its own drawn
    without replacement from a shuffled pool (permuted, non-contiguous);
    a position of None is a padding row: position 0 on the null block's
    table of zeros. ``poison``: "unnamed" fills every block no table names
    with NaN; "past_context" also points every table entry past its
    token's context at one NaN block (what the reference may then not be
    given: it gathers them)."""
    from deepspeed_tpu.ops.pallas.paged_attention import tile_blocks
    rng = np.random.RandomState(seed)
    T, NB = len(positions), len(positions) * MB + 2
    assert tile_blocks(BS, Hkv * DH * jnp.dtype(dtype).itemsize,
                       jnp.dtype(dtype).itemsize, MB) in (8, 16)  # the cases below are about tiles
    q = rng.randn(T, Hkv * G, DH).astype(np.float32)
    kc = rng.randn(2, NB, BS, Hkv * DH).astype(np.float32)
    vc = rng.randn(2, NB, BS, Hkv * DH).astype(np.float32)
    tabs = rng.permutation(np.arange(2, NB))[:T * MB].reshape(T, MB).astype(np.int32)
    pos = np.zeros(T, np.int32)
    for t, p in enumerate(positions):
        if p is None:
            tabs[t] = 0
        else:
            pos[t] = p
    clean_k, clean_v = kc.copy(), vc.copy()
    if poison:
        used = np.zeros(NB, bool)
        for t in range(T):
            live = pos[t] // BS + 1
            if poison == "past_context":
                tabs[t, live:] = 1
            used[tabs[t, :live if poison == "past_context" else MB]] = True
        kc[:, ~used] = np.nan
        vc[:, ~used] = np.nan
        clean_k[:, ~used] = 0
        clean_v[:, ~used] = 0
    cast = lambda a: jnp.asarray(a, dtype)
    return (cast(q), cast(kc), cast(vc), jnp.asarray(tabs), jnp.asarray(pos), jnp.int32(1),
            cast(clean_k), cast(clean_v))


def _float32_reference(q, kc, vc, tabs, pos, layer):
    f32 = lambda a: a.astype(jnp.float32)
    return np.asarray(xla_paged_attention(f32(q), f32(kc), f32(vc), tabs, pos, layer))


# float32 pools keep Precision.HIGHEST: 1e-5, as every float32 case above.
# A bf16 pool's kernel rounds the probabilities to bf16 for the second
# product (as the reference path does) and its output to bf16: 2**-9 of
# values up to ~3 each, against the float32 reference on the same rounded
# inputs. xla_paged_attention in bf16 also rounds its *scores* to bf16
# before the softmax (2**-9 of |s| up to ~5, so ~2 % of a probability),
# which the kernel does not: that comparison gets 6e-2.
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}
TOL_BF16_REFERENCE = 6e-2

TILE = 16 * BS  # a tile of the cases' shape: 16 blocks (8 where 20 KV heads fill the slots)
END = MAX_BLOCKS * BS  # 2.5 tiles
CONTEXTS = {
    "ends_inside_a_tile": [TILE + 5 * BS + 3, 3 * BS + 1, 2 * TILE + 7],
    "ends_on_a_tile_edge": [TILE - 1, TILE, 2 * TILE - 1, 2 * TILE],
    "one_block": [0, 3, BS - 1, BS],
    "at_max_blocks": [END - 1, END - 2, 5],
    "padding_beside_long": [None, 2 * TILE + 40, None, None, 11, None, END - BS, None, None],
    "one_tile_then_many": [7, END - 1, 2, TILE + 1, 1],
    "sixteen_rows": [TILE + 9, None, None, 3, 2 * TILE - 1, None, 40, 2 * TILE,
                       None, None, TILE, 5, None, END - BS + 2, None, None],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(CONTEXTS))
def test_tiled_contexts_match_reference(name, dtype):
    """Contexts that end inside a tile, on a tile's edge, after one block
    and at ``max_blocks``, and padding rows beside long rows, over
    permuted non-contiguous tables, through a traced layer index."""
    q, kc, vc, tabs, pos, layer, _, _ = _tiled_case(CONTEXTS[name], dtype, seed=len(name))
    got = jax.jit(lambda *a: paged_decode_attention(*a, interpret=True))(q, kc, vc, tabs, pos, layer)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _float32_reference(q, kc, vc, tabs, pos, layer),
                               rtol=TOL[dtype], atol=TOL[dtype])


def test_shared_single_block_is_fetched_once_and_read_right():
    """Neighbouring tokens whose whole context is the same one block
    (padding rows; a prompt's first tokens) reuse the fetched block, at
    positions of their own; a token on another block in between fetches."""
    q, kc, vc, tabs, pos, layer, _, _ = _tiled_case([3, 4, 5, 9, 0, 2], jnp.float32, seed=9)
    tabs = tabs.at[1].set(tabs[0]).at[2].set(tabs[0]).at[5].set(tabs[4])
    got = paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True)
    np.testing.assert_allclose(np.asarray(got), _float32_reference(q, kc, vc, tabs, pos, layer),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("poison", ["unnamed", "past_context"])
def test_stale_rows_of_a_tile_never_reach_the_output(poison, dtype):
    """The stale-row guard. A tile's blocks past the context's last are
    not fetched, so their rows are what the slot held: with every block
    the tables do not name filled with NaN - and, harder, every table
    entry past a context pointing at a NaN block - the output is finite
    and is the reference's on the pool without the NaNs."""
    positions = [TILE + 2 * BS + 5, 3, None, 2 * TILE - 1, BS, END - 1, 0]
    q, kc, vc, tabs, pos, layer, clean_k, clean_v = _tiled_case(positions, dtype, seed=21,
                                                               poison=poison)
    assert np.isnan(np.asarray(kc, np.float32)).any()
    got = np.asarray(paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True),
                     np.float32)
    assert np.isfinite(got).all()
    if poison == "unnamed":  # the reference itself reads no NaN here: literally the same call
        want = xla_paged_attention(q.astype(jnp.float32), kc.astype(jnp.float32),
                                   vc.astype(jnp.float32), tabs, pos, layer)
        np.testing.assert_allclose(got, np.asarray(want), rtol=TOL[dtype], atol=TOL[dtype])
    np.testing.assert_allclose(got, _float32_reference(q, clean_k, clean_v, tabs, pos, layer),
                               rtol=TOL[dtype], atol=TOL[dtype])


@pytest.mark.parametrize("Hkv,G", [(1, 4), (6, 2), (8, 4), (12, 1), (20, 1)])
def test_bf16_pool_matches_xla_reference(Hkv, G):
    """A bf16 pool goes to the MXU as it lies (float32 accumulation, the
    probabilities rounded to bf16): against the reference path in bf16
    and against float32 on the same inputs."""
    positions = [TILE + 37, 5, 2 * TILE, None, 3 * BS - 1]
    q, kc, vc, tabs, pos, layer, _, _ = _tiled_case(positions, jnp.bfloat16, Hkv=Hkv, G=G,
                                                   MB=36, seed=40 + Hkv)
    got = np.asarray(paged_decode_attention(q, kc, vc, tabs, pos, layer, interpret=True),
                     np.float32)
    ref = np.asarray(xla_paged_attention(q, kc, vc, tabs, pos, layer), np.float32)
    np.testing.assert_allclose(got, ref, rtol=TOL_BF16_REFERENCE, atol=TOL_BF16_REFERENCE)
    np.testing.assert_allclose(got, _float32_reference(q, kc, vc, tabs, pos, layer),
                               rtol=TOL[jnp.bfloat16], atol=TOL[jnp.bfloat16])


@pytest.mark.parametrize("bs,row_bytes,itemsize,max_blocks,want", [
    (16, 2048, 2, 361, 16),    # the cells: 8 KV heads x 128, bf16: 256 rows, 2 MiB of slots
    (16, 2048, 2, 96, 16),
    (16, 2048, 2, 3, 3),       # never more than the table has
    (16, 4096, 2, 64, 16),     # 16 KV heads, bf16: 4 MiB, the budget
    (16, 8192, 2, 64, 8),      # 32 KV heads: halved to fit
    (16, 16384, 4, 64, 4),     # the same in float32: halved again
    (8, 128, 4, 3, 3),         # the float32 debug pools
    (8, 2048, 2, 64, 1),       # a bf16 block of half a sublane tile: a slot of its own
    (16, 1024, 1, 64, 1),      # a one-byte pool wants 32-row blocks
    (32, 2048, 2, 64, 8),
    (128, 2048, 2, 64, 2),
    (256, 2048, 2, 64, 1),
])
def test_tile_blocks_follows_from_the_shapes(bs, row_bytes, itemsize, max_blocks, want):
    from deepspeed_tpu.ops.pallas.paged_attention import (TILE_VMEM_BYTES, kernel_supported,
                                                          tile_blocks)
    n = tile_blocks(bs, row_bytes, itemsize, max_blocks)
    assert n == want
    assert kernel_supported(128, bs)  # every supported shape has some n
    assert n == 1 or 4 * n * bs * row_bytes <= TILE_VMEM_BYTES


@pytest.mark.parametrize("bs,row_bytes,itemsize,max_blocks,want", [
    (64, 256, 2, 64, 32),      # the selecting layers: one KV head a pool layer, bf16: 512 KB a slot
    (64, 512, 4, 64, 16),      # the same in float32
    (64, 256, 2, 6, 6),        # never more than the table has
    (16, 2048, 2, 64, 16),     # rows of 8 heads: 256 rows are 512 KB already
    (16, 8192, 2, 64, 8),      # and the budget still halves
    (8, 256, 2, 64, 1),        # half a sublane tile stays a slot of its own
])
def test_a_selection_s_tile_holds_a_slot_of_bytes_too(bs, row_bytes, itemsize, max_blocks, want):
    """``selected=True`` (``SELECTED_SLOT_BYTES``) widens the tile of narrow
    rows alone; no other call passes it, so theirs is the table above."""
    from deepspeed_tpu.ops.pallas.paged_attention import (SELECTED_SLOT_BYTES, TILE_VMEM_BYTES,
                                                          tile_blocks)
    n = tile_blocks(bs, row_bytes, itemsize, max_blocks, SELECTED_SLOT_BYTES)
    assert n == want
    assert n >= tile_blocks(bs, row_bytes, itemsize, max_blocks)
    assert n == 1 or 4 * n * bs * row_bytes <= TILE_VMEM_BYTES


@pytest.mark.parametrize("n", [1, 2, 8, 32])
def test_any_tile_size_gives_the_same_answer(n):
    """``n`` is a matter of speed alone (the census sweeps it): n = 1 is
    a block a step with the copy ahead, 32 is wider than the rule's 16."""
    from deepspeed_tpu.ops.pallas.paged_attention import _paged_call
    q, kc, vc, tabs, pos, layer, _, _ = _tiled_case(CONTEXTS["padding_beside_long"],
                                                   jnp.float32, seed=3)
    got = _paged_call(q, kc, vc, tabs, pos, layer, n, True)
    np.testing.assert_allclose(np.asarray(got), _float32_reference(q, kc, vc, tabs, pos, layer),
                               rtol=1e-5, atol=1e-5)


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
