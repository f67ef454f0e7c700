"""Pallas block-sparse attention: parity vs masked-dense + work-ratio.

Mirrors the reference's tests/unit/ops/sparse_attention/ (triton SDD/DSD
kernel checks): the block-skipping kernels must match the masked-dense
path exactly (block-granular semantics) and must visit only ~density of
the dense block grid at BigBird sparsity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.block_sparse_attention import (block_sparse_attention,
                                                             grid_fraction,
                                                             layout_to_indices)
from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                FixedSparsityConfig,
                                                SparseSelfAttention)
from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import layout_to_mask
from deepspeed_tpu.models.llama import einsum_attention


def _qkv(B, S, H, D, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    return mk(), mk(), mk()


def _dense_ref(q, k, v, layout, block):
    mask = layout_to_mask(layout, block, q.shape[1])[None]
    return einsum_attention(q, k, v, causal=False, mask=mask)


@pytest.mark.parametrize("cfg_cls,kw", [
    (BigBirdSparsityConfig, dict(num_random_blocks=1, num_sliding_window_blocks=3,
                                 num_global_blocks=1)),
    (FixedSparsityConfig, dict(num_local_blocks=4, num_global_blocks=1)),
    (FixedSparsityConfig, dict(num_local_blocks=4, num_global_blocks=1,
                               attention="unidirectional")),
])
def test_forward_matches_masked_dense(cfg_cls, kw):
    B, S, H, D, block = 2, 128, 2, 32, 16
    cfg = cfg_cls(num_heads=H, block=block, **kw)
    layout = cfg.make_layout(S)
    q, k, v = _qkv(B, S, H, D)
    out = block_sparse_attention(q, k, v, layout, block, interpret=True)
    want = _dense_ref(q, k, v, layout, block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_per_head_layouts():
    B, S, H, D, block = 1, 64, 3, 16, 16
    cfg = BigBirdSparsityConfig(num_heads=H, block=block, different_layout_per_head=True,
                                num_random_blocks=1, num_sliding_window_blocks=1,
                                num_global_blocks=1)
    layout = cfg.make_layout(S)
    assert not (layout[0] == layout[1]).all() or not (layout[0] == layout[2]).all()
    q, k, v = _qkv(B, S, H, D, seed=3)
    out = block_sparse_attention(q, k, v, layout, block, interpret=True)
    want = _dense_ref(q, k, v, layout, block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_backward_matches_masked_dense():
    B, S, H, D, block = 1, 64, 2, 16, 16
    cfg = BigBirdSparsityConfig(num_heads=H, block=block, num_random_blocks=1,
                                num_sliding_window_blocks=3, num_global_blocks=1)
    layout = cfg.make_layout(S)
    q, k, v = _qkv(B, S, H, D, seed=1)
    co = jnp.asarray(np.random.RandomState(2).randn(B, S, H, D).astype(np.float32))

    def loss_kernel(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, layout, block, interpret=True) * co)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_ref(q, k, v, layout, block) * co)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-5, atol=5e-5,
                                   err_msg=f"d{name}")


def test_bigbird_4k_work_ratio():
    """At S=4k BigBird sparsity the kernels must do ~density of the dense
    work (the reference's whole point — matmul.py:819 skips blocks; the
    masked-dense path burns 100%). Counted via the grid: one step per
    admitted (head, q-block, k-block) pair, global rows included."""
    S, block = 4096, 64
    cfg = BigBirdSparsityConfig(num_heads=1, block=block, num_random_blocks=1,
                                num_sliding_window_blocks=3, num_global_blocks=1)
    layout = cfg.make_layout(S)
    density = layout.mean()
    assert density < 0.15, f"BigBird@4k should be sparse, got {density:.3f}"
    # the fori_loop bound per row is its admitted count, so total executed
    # block pairs == admitted pairs == density x dense, exactly
    k_idx, k_nnz, q_idx, q_nnz = layout_to_indices(layout)
    H, nq, nk = layout.shape
    assert int(k_nnz.sum()) == int(layout.sum()) == int(q_nnz.sum())
    assert grid_fraction(layout) == pytest.approx(density)


def test_ragged_rows_and_empty_row():
    """Rows with very different admitted counts must each accumulate
    exactly their own pairs; a row with NO admitted blocks outputs zeros
    (and contributes zero dk/dv) instead of garbage."""
    B, S, H, D, block = 1, 64, 1, 16, 16
    layout = np.zeros((1, 4, 4), bool)
    layout[0, 0] = [True, True, True, True]   # row 0: all 4
    layout[0, 1] = [False, True, False, False]  # row 1: only block 1
    layout[0, 2] = [False, False, False, False]  # row 2: EMPTY
    layout[0, 3] = [False, False, False, True]
    q, k, v = _qkv(B, S, H, D, seed=5)
    out = np.asarray(block_sparse_attention(q, k, v, layout, block, interpret=True))
    want = np.asarray(_dense_ref(q, k, v, layout, block))
    np.testing.assert_allclose(out[:, :32], want[:, :32], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out[:, 48:], want[:, 48:], rtol=2e-5, atol=2e-5)
    assert np.all(out[:, 32:48] == 0.0)  # empty row → zeros


def test_sparse_self_attention_dispatches_kernel(monkeypatch):
    monkeypatch.setenv("DS_PALLAS", "1")  # no Mosaic here: ask for the interpreted kernel
    B, S, H, D, block = 1, 64, 2, 16, 16
    cfg = FixedSparsityConfig(num_heads=H, block=block, num_local_blocks=2,
                              num_global_blocks=1)
    q, k, v = _qkv(B, S, H, D, seed=7)
    dense = SparseSelfAttention(cfg, force_kernel=False)(q, k, v)
    kern = SparseSelfAttention(cfg, force_kernel=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_indices_structure():
    layout = np.zeros((2, 3, 3), bool)
    layout[0, 0, [0, 2]] = True
    layout[0, 1, 1] = True
    layout[1, 2, [0, 1, 2]] = True
    k_idx, k_nnz, q_idx, q_nnz = layout_to_indices(layout)
    assert k_nnz[0].tolist() == [2, 1, 0] and k_idx[0, 0, :2].tolist() == [0, 2]
    assert k_nnz[1].tolist() == [0, 0, 3]
    # transpose: head 1's key-block 0 admitted by query-block 2
    assert q_nnz[1].tolist() == [1, 1, 1] and q_idx[1, 0, 0] == 2


class TestSparseAttentionUtils:
    """ds_config parsing + pad/unpad + position extension (reference
    sparse_attention_utils.py + runtime/config.py get_sparse_attention)."""

    def test_config_modes(self):
        from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig,
                                                        get_sparse_attention_config)
        ds = {"sparse_attention": {"mode": "bigbird", "block": 32,
                                   "num_random_blocks": 2,
                                   "num_sliding_window_blocks": 3,
                                   "num_global_blocks": 1}}
        cfg = get_sparse_attention_config(ds, num_heads=4)
        assert isinstance(cfg, BigBirdSparsityConfig)
        assert cfg.block == 32 and cfg.num_random_blocks == 2 and cfg.num_heads == 4
        assert get_sparse_attention_config({}, num_heads=4) is None
        with pytest.raises(NotImplementedError, match="sparsity mode"):
            get_sparse_attention_config({"sparse_attention": {"mode": "nope"}}, 4)

    def test_build_and_run_from_ds_config(self):
        from deepspeed_tpu.ops.sparse_attention import build_sparse_self_attention
        attn = build_sparse_self_attention(
            {"sparse_attention": {"mode": "fixed", "block": 16,
                                  "num_local_blocks": 2, "num_global_blocks": 1}},
            num_heads=2)
        q, k, v = _qkv(1, 64, 2, 16, seed=11)
        out = attn(q, k, v)
        assert out.shape == (1, 64, 2, 16)

    def test_pad_unpad_roundtrip(self):
        from deepspeed_tpu.ops.sparse_attention import SparseAttentionUtils
        ids = np.arange(2 * 45).reshape(2, 45)
        pad_len, pids, mask, *_ = SparseAttentionUtils.pad_to_block_size(
            16, ids, pad_token_id=9)
        assert pad_len == 3 and pids.shape == (2, 48)
        assert (pids[:, -3:] == 9).all() and (mask[:, -3:] == 0).all()
        seq_out = np.random.RandomState(0).randn(2, 48, 8)
        unp = SparseAttentionUtils.unpad_sequence_output(pad_len, seq_out)
        assert unp.shape == (2, 45, 8)
        assert SparseAttentionUtils.unpad_sequence_output(0, seq_out).shape == (2, 48, 8)

    def test_extend_position_embedding(self):
        from deepspeed_tpu.ops.sparse_attention import SparseAttentionUtils
        params = {"model": {"embed_positions": np.arange(12.0).reshape(6, 2),
                            "layers": {"w": np.ones((2, 2))}}}
        out = SparseAttentionUtils.extend_position_embedding(params, 15)
        table = out["model"]["embed_positions"]
        assert table.shape == (15, 2)
        np.testing.assert_array_equal(table[6:12], table[:6])  # tiled
        np.testing.assert_array_equal(out["model"]["layers"]["w"], np.ones((2, 2)))
