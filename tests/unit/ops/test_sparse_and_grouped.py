"""Sparse attention + grouped MoE GEMM tests (analogue of reference
tests/unit/ops/sparse_attention/ and MoE gemm coverage)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.grouped_gemm import (dense_reference_mlp, grouped_gemm, moe_grouped_mlp,
                                            sort_by_expert)
from deepspeed_tpu.ops.sparse_attention import (BigBirdSparsityConfig, BSLongformerSparsityConfig,
                                                DenseSparsityConfig, FixedSparsityConfig,
                                                SparseSelfAttention, layout_to_mask)


class TestSparsityConfigs:

    def test_dense_layout(self):
        layout = DenseSparsityConfig(num_heads=2, block=8).make_layout(64)
        assert layout.shape == (2, 8, 8) and layout.all()

    def test_fixed_layout_local_and_global(self):
        cfg = FixedSparsityConfig(num_heads=2, block=8, num_local_blocks=2,
                                  num_global_blocks=1)
        layout = cfg.make_layout(64)
        assert layout[0, 0, 0] and layout[0, 0, 1]   # own local window
        assert layout[0, 0, 3].any() or layout[0, 3, 1]  # global connectivity
        assert (layout[0] == layout[1]).all()        # propagated head layout
        uni = FixedSparsityConfig(num_heads=1, block=8, num_local_blocks=2,
                                  attention="unidirectional").make_layout(64)
        assert not uni[0][np.triu_indices(8, 1)].any()

    def test_bigbird_has_window_random_global(self):
        cfg = BigBirdSparsityConfig(num_heads=1, block=8, num_random_blocks=1,
                                    num_sliding_window_blocks=3, num_global_blocks=1)
        layout = cfg.make_layout(128)
        n = layout.shape[1]
        for q in range(1, n - 1):
            assert layout[0, q, q - 1] and layout[0, q, q] and layout[0, q, q + 1]
        assert layout[0, :, 0].all() and layout[0, 0, :].all()

    def test_longformer_global_indices(self):
        cfg = BSLongformerSparsityConfig(num_heads=1, block=8,
                                         num_sliding_window_blocks=1,
                                         global_block_indices=[2])
        layout = cfg.make_layout(64)
        assert layout[0, :, 2].all() and layout[0, 2, :].all()

    def test_seq_len_must_divide(self):
        with pytest.raises(ValueError):
            DenseSparsityConfig(num_heads=1, block=16).make_layout(40)


class TestSparseSelfAttention:

    def test_dense_config_matches_full_attention(self):
        from deepspeed_tpu.models.llama import einsum_attention
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, 32, 2, 16).astype(np.float32))
        attn = SparseSelfAttention(DenseSparsityConfig(num_heads=2, block=8))
        out = attn(q, q, q)
        ref = einsum_attention(q, q, q, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_blocked_mask_zeroes_disallowed(self):
        """A layout with NO cross-window blocks: tokens in window A must
        be unaffected by values in window B."""
        cfg = FixedSparsityConfig(num_heads=1, block=8, num_local_blocks=1,
                                  num_global_blocks=0)
        # num_global_blocks=0 -> pure block-diagonal
        attn = SparseSelfAttention(cfg)
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 16, 1, 8).astype(np.float32))
        v1 = jnp.asarray(rng.randn(1, 16, 1, 8).astype(np.float32))
        v2 = v1.at[:, 8:].set(999.0)  # perturb only window B values
        o1 = attn(q, q, v1)
        o2 = attn(q, q, v2)
        np.testing.assert_array_equal(np.asarray(o1[:, :8]), np.asarray(o2[:, :8]))


class TestGroupedGemm:

    def test_sort_and_grouped_matches_dense(self):
        rng = np.random.RandomState(0)
        T, D, F, E = 24, 8, 16, 3
        x = jnp.asarray(rng.randn(T, D).astype(np.float32))
        idx = jnp.asarray(rng.randint(0, E, T).astype(np.int32))
        wg = jnp.asarray(rng.randn(E, D, F).astype(np.float32))
        wu = jnp.asarray(rng.randn(E, D, F).astype(np.float32))
        wd = jnp.asarray(rng.randn(E, F, D).astype(np.float32))
        got = moe_grouped_mlp(x, idx, wg, wu, wd, E)
        want = dense_reference_mlp(x, idx, wg, wu, wd)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_grouped_gemm_ragged_groups(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(10, 4).astype(np.float32))
        w = jnp.asarray(rng.randn(3, 4, 6).astype(np.float32))
        sizes = jnp.asarray([2, 0, 8], jnp.int32)  # one EMPTY expert
        out = grouped_gemm(x, w, sizes)
        np.testing.assert_allclose(np.asarray(out[:2]), np.asarray(x[:2] @ w[0]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out[2:]), np.asarray(x[2:] @ w[2]), rtol=1e-5)

    def test_pallas_gmm_branch_matches_ragged(self):
        """The Pallas grouped-matmul training path (tile-aligned padded
        layout, rank-based routing — ops/pallas/grouped_matmul.py) must
        reproduce the ragged_dot fallback exactly: forward AND grads
        through all three GEMMs. Runs in interpret mode on CPU."""
        import deepspeed_tpu.ops.grouped_gemm as gg
        rng = np.random.RandomState(3)
        T, D, F, E = 256, 128, 256, 4
        x = jnp.asarray(rng.randn(T, D).astype(np.float32))
        idx = jnp.asarray(rng.randint(0, E, T).astype(np.int32))
        wg = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.05)
        wu = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.05)
        wd = jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.05)

        def loss(args):
            x, wg, wu, wd = args
            return (moe_grouped_mlp(x, idx, wg, wu, wd, E).astype(jnp.float32) ** 2).sum()

        want, want_g = jax.value_and_grad(loss)((x, wg, wu, wd))
        gg.FORCE_INTERPRET = True
        try:
            got, got_g = jax.value_and_grad(loss)((x, wg, wu, wd))
        finally:
            gg.FORCE_INTERPRET = False
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    def test_pallas_gmm_empty_expert(self):
        """An expert with zero routed rows must produce zero dw and not
        poison the others (uninitialized-output masking in the kernel)."""
        import deepspeed_tpu.ops.grouped_gemm as gg
        rng = np.random.RandomState(4)
        T, D, F, E = 64, 64, 128, 4
        x = jnp.asarray(rng.randn(T, D).astype(np.float32))
        idx = jnp.asarray((rng.randint(0, E - 1, T)).astype(np.int32))  # expert 3 empty
        wg = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.05)
        wu = jnp.asarray(rng.randn(E, D, F).astype(np.float32) * 0.05)
        wd = jnp.asarray(rng.randn(E, F, D).astype(np.float32) * 0.05)
        gg.FORCE_INTERPRET = True
        try:
            out = moe_grouped_mlp(x, idx, wg, wu, wd, E)
            g = jax.grad(lambda w: (moe_grouped_mlp(x, idx, w, wu, wd, E) ** 2).sum())(wg)
        finally:
            gg.FORCE_INTERPRET = False
        want = dense_reference_mlp(x, idx, wg, wu, wd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-4)
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g[3]), 0.0)

    @pytest.mark.parametrize("rows,path", [(3, "gathered"), (96, "ragged"), (64, "pallas")])
    def test_a_table_of_groups_is_this_calls_experts_cut_out(self, rows, path):
        """``first_group``: the stacks are a table of more groups than the
        call's experts (every layer's, say). Each dispatch - chosen on the
        call's own expert count, as without a table - gives what it gives
        on the call's experts cut out of the table; ``GMM_STATS`` says
        which calls indexed the table where it lies (Pallas cuts out)."""
        import deepspeed_tpu.ops.grouped_gemm as gg
        rng = np.random.RandomState(7)
        D, F, E, layers, layer = 64, 128, 4, 3, 1
        x = jnp.asarray(rng.randn(rows, D).astype(np.float32))
        idx = jnp.asarray(rng.randint(0, E, rows).astype(np.int32))
        wg, wu, wd = (jnp.asarray(rng.randn(layers * E, *shape).astype(np.float32) * 0.05)
                      for shape in ((D, F), (D, F), (F, D)))
        cut = [w[layer * E:(layer + 1) * E] for w in (wg, wu, wd)]
        gg.FORCE_INTERPRET = path == "pallas"
        gg.GMM_STATS.reset()
        try:
            want = moe_grouped_mlp(x, idx, *cut, E)
            got = jax.jit(lambda first: moe_grouped_mlp(x, idx, wg, wu, wd, E,
                                                        first_group=first))(jnp.int32(layer * E))
        finally:
            gg.FORCE_INTERPRET = False
        assert gg.GMM_STATS.snapshot() == ({path: 2} if path == "pallas"
                                           else {path: 1, path + "_table": 1})
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense_reference_mlp(x, idx, *cut)),
                                   rtol=1e-4, atol=1e-4)

    def test_grouped_under_jit_and_grad(self):
        rng = np.random.RandomState(2)
        T, D, F, E = 16, 8, 8, 2
        x = jnp.asarray(rng.randn(T, D).astype(np.float32))
        idx = jnp.asarray(rng.randint(0, E, T).astype(np.int32))
        w = jnp.asarray(rng.randn(E, D, F).astype(np.float32))

        @jax.jit
        def loss(w):
            xs, sizes, unsort = sort_by_expert(x, idx, E)
            return grouped_gemm(xs, w, sizes).sum()

        g = jax.grad(loss)(w)
        assert np.isfinite(np.asarray(g)).all()
        assert np.abs(np.asarray(g)).max() > 0
