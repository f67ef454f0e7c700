"""Sparse attention + grouped MoE GEMM tests (analogue of reference
tests/unit/ops/sparse_attention/ and MoE gemm coverage)."""

import functools

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


@functools.lru_cache(maxsize=None)
def _compacted_share(gated, pallas, zero):
    """→ (the jitted ``expert_share_ffn`` of one form - gated or not, its
    dispatch (the caller forces it while it traces), with or without
    zero-compute columns - the stacks, the activation, a pass's rows) at
    32 tokens x 4 picks over experts 2..5 of 16."""
    from deepspeed_tpu.ops.grouped_gemm import ExpertShare, expert_share_ffn, share_pass_rows
    act = jax.nn.silu if gated else lambda v: jnp.square(jax.nn.relu(v))
    rng = np.random.RandomState(7 + zero)
    T, k, D, F, held = 32, 4, 128, 128, 4
    share = ExpertShare(first=2, held=held, routed=16, zero=zero)
    w1, w3, w2 = (jnp.asarray(rng.randn(held, *shape).astype(np.float32) * 0.05)
                  for shape in ((D, F), (D, F), (F, D)))
    if not gated:
        w3 = None
    run = jax.jit(lambda x, idx, vals: expert_share_ffn(x, idx, vals, w1, w3, w2, share,
                                                        activation=act))
    return run, (w1, w3, w2), act, share_pass_rows(T, k, share, jnp.float32)


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
        which calls indexed the table where it lies (every dispatch)."""
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
        assert gg.GMM_STATS.snapshot() == {path: 1, path + "_table": 1}
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense_reference_mlp(x, idx, *cut)),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("rows,path", [(3, "gathered"), (96, "ragged"), (64, "pallas")])
    def test_an_ungated_expert_of_two_matrices_through_every_dispatch(self, rows, path):
        """``w_up=None`` and the activation as an argument: ``act(x w1) w2``
        - here ``relu(.)^2`` - through the gathered, the ragged and the
        Pallas dispatch, over a table of groups, against
        ``dense_reference_mlp``'s ungated twin."""
        import deepspeed_tpu.ops.grouped_gemm as gg
        relu2 = lambda v: jnp.square(jax.nn.relu(v))  # noqa: E731
        rng = np.random.RandomState(11)
        D, F, E, layers, layer = 64, 128, 4, 2, 1
        x = jnp.asarray(rng.randn(rows, D).astype(np.float32))
        idx = jnp.asarray(rng.randint(0, E, rows).astype(np.int32))
        w1, w2 = (jnp.asarray(rng.randn(layers * E, *shape).astype(np.float32) * 0.05)
                  for shape in ((D, F), (F, D)))
        gg.FORCE_INTERPRET = path == "pallas"
        gg.GMM_STATS.reset()
        try:
            got = jax.jit(lambda first: moe_grouped_mlp(
                x, idx, w1, None, w2, E, activation=relu2, first_group=first))(
                    jnp.int32(layer * E))
        finally:
            gg.FORCE_INTERPRET = False
        assert gg.GMM_STATS.snapshot() == {path + "_table": 1}
        cut = slice(layer * E, (layer + 1) * E)
        want = dense_reference_mlp(x, idx, w1[cut], None, w2[cut], activation=relu2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)
        gated = dense_reference_mlp(x, idx, w1[cut], w1[cut], w2[cut], activation=relu2)
        assert np.abs(np.asarray(gated) - np.asarray(want)).max() > 1e-3

    @pytest.mark.parametrize("pallas", [False, True])
    def test_an_ungated_share_sums_the_held_picks_alone(self, pallas):
        """``dropless_moe_ffn(w3=None, share=...)``: the held picks' ``w_j
        relu(x w1_j)^2 w2_j`` and nothing for the picks held elsewhere or a
        padding token's (-1); a sharded mesh refuses an ungated expert."""
        import deepspeed_tpu.ops.grouped_gemm as gg
        from deepspeed_tpu.ops.grouped_gemm import ExpertShare, dropless_moe_ffn
        relu2 = lambda v: jnp.square(jax.nn.relu(v))  # noqa: E731
        rng = np.random.RandomState(5)
        T, k, D, F, routed, first, held = 24, 3, 128, 128, 8, 2, 4
        x = jnp.asarray(rng.randn(T, D).astype(np.float32) * 0.5)
        idx = np.stack([rng.permutation(routed)[:k] for _ in range(T)]).astype(np.int32)
        idx[5] = -1
        vals = jnp.asarray(rng.rand(T, k).astype(np.float32))
        w1, w2 = (jnp.asarray(rng.randn(held, *shape).astype(np.float32) * 0.05)
                  for shape in ((D, F), (F, D)))
        share = ExpertShare(first=first, held=held, routed=routed)
        gg.FORCE_INTERPRET = pallas
        gg.GMM_STATS.reset()
        try:
            got = dropless_moe_ffn(x, jnp.asarray(idx), vals, w1, None, w2, num_experts=routed,
                                   share=share, activation=relu2)
        finally:
            gg.FORCE_INTERPRET = False
        assert gg.GMM_STATS.snapshot() == {("pallas" if pallas else "ragged") + "_share": 1}
        want = np.zeros((T, D), np.float32)
        for t in range(T):
            for j in range(k):
                e = idx[t, j] - first
                if idx[t, j] >= 0 and 0 <= e < held:
                    want[t] += float(vals[t, j]) * np.asarray(relu2(x[t] @ w1[e]) @ w2[e])
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
        assert np.abs(want[5]).max() == 0.0

    @pytest.mark.parametrize("load", ["typical", "every_pick", "none", "a_pass_full",
                                      "a_pass_and_one"])
    @pytest.mark.parametrize("zero", [0, 8])
    @pytest.mark.parametrize("pallas", [False, True])
    @pytest.mark.parametrize("gated", [True, False])
    def test_a_compacted_share_is_the_held_picks_through_the_reference(self, gated, pallas,
                                                                       zero, load):
        """``expert_share_ffn`` over passes of ``share_pass_rows`` rows: the
        held picks' ``w_j E_j(x)`` by ``dense_reference_mlp`` (plus the
        zero-compute picks' ``w x``) at a typical load (one pass), with every
        pick held (``T k / cap`` passes: nothing is dropped), with none held
        and padding tokens' picks of -1 (no pass, no row out), and with
        exactly a pass's rows held and one more; the passes returned are
        ``ceil(held picks / cap)``. One program a form serves its five loads."""
        import deepspeed_tpu.ops.grouped_gemm as gg
        T, k, D, first, held, routed = 32, 4, 128, 2, 4, 16
        run, (w1, w3, w2), act, cap = _compacted_share(gated, pallas, zero)
        assert cap < T * k and cap % 16 == 0
        rng = np.random.RandomState(53)
        n_held = {"typical": cap // 2 - 3, "every_pick": T * k, "none": 0, "a_pass_full": cap,
                  "a_pass_and_one": cap + 1}[load]
        elsewhere = [c for c in range(routed + zero) if not first <= c < first + held]
        idx = rng.choice(elsewhere, (T, k)).astype(np.int32)
        if load == "none":
            idx[[3, 17, 31]] = -1
        flat = idx.reshape(-1)
        flat[rng.permutation(T * k)[:n_held]] = first + rng.randint(0, held, n_held)
        x = jnp.asarray(rng.randn(T, D).astype(np.float32) * 0.5)
        vals = rng.rand(T, k).astype(np.float32)
        gg.FORCE_INTERPRET = pallas
        try:
            got, passes = run(x, jnp.asarray(idx), jnp.asarray(vals))
        finally:
            gg.FORCE_INTERPRET = False
        assert int(passes) == -(-n_held // cap)
        tok, j = np.nonzero((idx >= first) & (idx < first + held))
        assert len(tok) == n_held
        want = np.zeros((T, D), np.float32)
        if n_held:
            rows = dense_reference_mlp(x[tok], jnp.asarray(idx[tok, j] - first), w1, w3, w2,
                                       activation=act)
            np.add.at(want, tok, vals[tok, j][:, None] * np.asarray(rows))
        want += np.where(idx >= routed, vals, 0).sum(-1, keepdims=True) * np.asarray(x)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)
        if load == "none":
            assert not np.asarray(got)[[3, 17, 31]].any()

    def test_a_share_builds_no_array_of_every_pick_by_the_width(self):
        """The point of PR 53: behind a share that holds fewer columns than the
        router has, nothing of ``T k`` rows by the model's width is built -
        no repeat, no take, no einsum over every pick; the share of every
        column still lays every pick (the programs of the kinds that pass it
        are unchanged), which is also what shows that the search sees."""
        from deepspeed_tpu.ops.grouped_gemm import (ExpertShare, expert_share_ffn,
                                                    share_pass_rows)
        T, k, D, F, held, routed = 64, 4, 128, 256, 4, 32
        stacks = [jnp.zeros((held,) + s, jnp.float32) for s in ((D, F), (D, F), (F, D))]

        def wide_rows(share):
            jaxpr = jax.make_jaxpr(lambda x, idx, vals: expert_share_ffn(
                x, idx, vals, *stacks, share))(
                    jnp.zeros((T, D)), jnp.zeros((T, k), jnp.int32), jnp.zeros((T, k)))
            found, todo = [], [jaxpr.jaxpr]
            while todo:
                inner = todo.pop()
                for eqn in inner.eqns:
                    found += [v.aval.shape for v in eqn.outvars
                              if getattr(v.aval, "shape", ())[:1] == (T * k,)
                              and set(v.aval.shape[1:]) & {D, F}]
                    todo += [getattr(sub, "jaxpr", sub) for sub in jax.core.jaxprs_in_params(
                        eqn.params)]
            return found

        share = ExpertShare(0, held, routed)
        cap = share_pass_rows(T, k, share, jnp.float32)
        assert cap + held * 16 < T * k      # a pass's layout is not T k rows by chance
        assert wide_rows(share) == []
        assert (T * k, D) in wide_rows(ExpertShare(0, held, held))

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


def _skewed(rows, groups, seed, empty=()):
    """Group sizes that sum to ``rows``: random, with ``empty`` groups at 0."""
    rng = np.random.RandomState(seed)
    live = [g for g in range(groups) if g not in empty]
    sizes = np.zeros(groups, np.int64)
    np.add.at(sizes, rng.choice(live, rows), 1)
    return sizes


# (rows, groups, K, N, dtype, sizes, weight-block bytes): the four shape classes the
# benchmark's cells serve, scaled down - the rows a group and the factors of N kept -
# and the layouts that go wrong first
GMM_CASES = {
    "moonlight-decode-12-rows-a-group-N-11x128": (96, 8, 256, 1408, jnp.bfloat16, None, None),
    "moonlight-chunk-48-rows-a-group": (384, 8, 256, 1408, jnp.bfloat16, None, None),
    "moonlight-down-K-11x128": (96, 8, 1408, 256, jnp.bfloat16, None, None),
    "mixtral-decode-16-rows-a-group-N-split": (64, 4, 256, 1792, jnp.bfloat16, None,
                                               256 * 896 * 2),
    "mixtral-chunk-128-rows-a-group-N-split": (512, 4, 256, 1792, jnp.bfloat16, None,
                                               256 * 256 * 2),
    "mixtral-down-K-14x128": (64, 4, 1792, 256, jnp.bfloat16, None, 1792 * 128 * 2),
    "empty-groups": (96, 8, 128, 256, jnp.float32, _skewed(96, 8, 1, empty=(0, 3, 7)), None),
    "every-row-in-one-group": (96, 8, 128, 256, jnp.float32, np.eye(8, dtype=np.int64)[5] * 96,
                               None),
    "rows-not-a-multiple-of-the-tile": (100, 8, 128, 256, jnp.float32, None, None),
    "training-tile-256": (1024, 2, 128, 256, jnp.float32, None, None),
}


class TestGmmReadsTheTable:
    """The Pallas grouped matmul on a table of ``L x E`` groups with a
    traced ``first_group`` and a row tile fitted to the rows a group,
    against ``lax.ragged_dot`` on the call's groups cut out: f32
    accumulation, one rounding to the activation dtype. Interpret mode."""

    @pytest.mark.parametrize("case", sorted(GMM_CASES))
    def test_matches_ragged_dot_on_the_groups_cut_out(self, case, monkeypatch):
        from deepspeed_tpu.ops.pallas import grouped_matmul as gm
        rows, groups, K, N, dtype, sizes, block_bytes = GMM_CASES[case]
        if block_bytes is not None:
            monkeypatch.setattr(gm, "_WEIGHT_BLOCK_BYTES", block_bytes)
            assert 128 <= gm.col_tile(K, N, jnp.dtype(dtype).itemsize) < N
        sizes = _skewed(rows, groups, 11) if sizes is None else sizes
        layers, layer = 3, 2
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(rows, K), dtype)
        table = jnp.asarray(rng.randn(layers * groups, K, N) * K ** -0.5, dtype)
        tm = gm.row_tile(rows, groups, dtype)
        sub = 32 // x.dtype.itemsize
        assert tm == min(256, -(-(2 * rows // groups) // sub) * sub)

        @jax.jit
        def kernel(x, table, sizes, first):
            starts, te, Mp, tiles = gm.tile_layout(sizes, rows, tm)
            assert Mp <= rows + tm - 1 + groups * tm
            group = jnp.repeat(jnp.arange(groups), sizes, total_repeat_length=rows)
            dst = starts[group] + jnp.arange(rows) - (jnp.cumsum(sizes) - sizes)[group]
            xp = jnp.zeros((Mp, K), x.dtype).at[dst].set(x)
            out = gm.gmm(xp, table, te, tm, True, first_group=first, num_tiles=tiles)
            pad = jnp.ones(Mp, bool).at[dst].set(False)
            return out[dst], jnp.abs(jnp.where(pad[:, None], out, 0).astype(jnp.float32)).max()

        sizes = jnp.asarray(sizes, jnp.int32)
        got, pad_max = kernel(x, table, sizes, jnp.int32(layer * groups))
        want = jax.lax.ragged_dot(x, table[layer * groups:(layer + 1) * groups], sizes,
                                  preferred_element_type=jnp.float32).astype(dtype)
        assert got.dtype == x.dtype and float(pad_max) == 0.0
        tol = 2 ** -7 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("rows,groups,dtype,tile", [
        (768, 64, jnp.bfloat16, 32), (3072, 64, jnp.bfloat16, 96), (128, 8, jnp.bfloat16, 32),
        (1024, 8, jnp.bfloat16, 256), (65536, 8, jnp.bfloat16, 256), (24, 3, jnp.float32, 16),
        (3, 8, jnp.bfloat16, 16)])
    def test_the_row_tile_is_twice_the_rows_a_group(self, rows, groups, dtype, tile):
        from deepspeed_tpu.ops.pallas.grouped_matmul import row_tile
        assert row_tile(rows, groups, dtype) == tile

    @pytest.mark.parametrize("K,N,tile", [
        (2048, 1408, 1408), (1408, 2048, 2048),      # Moonlight: the whole N, both ways
        (4096, 14336, 3584), (14336, 4096, 1024),    # Mixtral: rows of 7 KiB and 2 KiB
        (4096, 4096 * 7 + 64, None)])                # no 128-multiple divides N
    def test_a_weight_block_is_wide(self, K, N, tile):
        from deepspeed_tpu.ops.pallas.grouped_matmul import col_tile
        assert col_tile(K, N, 2) == tile

    def test_grad_through_the_table(self):
        """dx and dw of the table form: dw lands in the call's groups of
        the table and is zero in every other."""
        from deepspeed_tpu.ops.pallas import grouped_matmul as gm
        rows, groups, K, N, tm = 48, 3, 64, 128, 16
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(rows, K), jnp.float32)
        table = jnp.asarray(rng.randn(3 * groups, K, N) * 0.1, jnp.float32)
        sizes = jnp.asarray([16, 0, 32], jnp.int32)
        dst, te, Mp = gm.pad_groups_to_tiles(sizes, rows, tm)

        def kernel(x, table):
            xp = jnp.zeros((Mp, K), x.dtype).at[dst].set(x)
            return (gm.gmm(xp, table, te, tm, True, first_group=jnp.int32(groups))[dst] ** 2).sum()

        def ragged(x, table):
            return (jax.lax.ragged_dot(x, table[groups:2 * groups], sizes) ** 2).sum()

        for got, want in zip(jax.grad(kernel, (0, 1))(x, table), jax.grad(ragged, (0, 1))(x, table)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

