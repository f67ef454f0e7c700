"""Pallas kernels vs XLA references (interpreter mode on CPU).

Mirrors the reference's kernel-vs-reference numerics tests
(tests/unit/ops/*): each Pallas kernel must match its XLA reference
within dtype tolerance, forward and backward.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.flash_attention import (_flash, _k_blocks, _q_blocks, _reference,
                                                       flash_attention, flash_schedule,
                                                       piece_classes)
from deepspeed_tpu.ops.pallas.fused_norms import fused_layer_norm, fused_rms_norm
from deepspeed_tpu.ops.pallas.quantization import dequantize_int8, quantize_int8


def _qkv(b=2, s=128, h=2, d=32, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32), dtype=dtype)
    return mk(), mk(), mk()


class TestFlashAttention:

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True, force_pallas=True)
        ref = flash_attention(q, k, v, causal=causal, force_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_ragged_seq_len(self):
        # seq not a multiple of the block: exercises padding + masking
        q, k, v = _qkv(s=100)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True, force_pallas=True)
        ref = flash_attention(q, k, v, causal=True, force_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients_match_reference(self, causal):
        q, k, v = _qkv(s=64, d=16)

        def loss_pallas(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                                interpret=True, force_pallas=True)
            return jnp.sum(o * jnp.cos(o))

        def loss_ref(q, k, v):
            o = flash_attention(q, k, v, causal=causal, force_pallas=False)
            return jnp.sum(o * jnp.cos(o))

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)

    def test_bf16_io(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True, force_pallas=True)
        assert out.dtype == jnp.bfloat16
        ref = flash_attention(q, k, v, causal=True, force_pallas=False)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                                   atol=3e-2, rtol=3e-2)


def _dense_mask(seq_len, s_pad, window, causal):
    """Every pair of the padded square that counts, as the reference masks it."""
    q, k = np.arange(s_pad)[:, None], np.arange(s_pad)[None, :]
    valid = np.broadcast_to(k < seq_len, (s_pad, s_pad)).copy()
    if causal:
        valid &= k <= q
    if window is not None:
        valid &= k > q - window
    return valid


# (seq_len, window, causal, block_q, block_k, grain): sequence lengths that are no multiple of a
# block, windows of 1, of no multiple, of the block and longer than the sequence, pieces that
# divide a block in 1, 2, 4 and unequally
SCHEDULES = [
    (64, None, True, 16, 16, (4, 4)),
    (61, None, True, 16, 16, (8, 4)),
    (61, None, False, 16, 32, (8, 8)),
    (64, 1, True, 16, 16, (4, 4)),
    (61, 1, True, 16, 32, (16, 8)),
    (64, 16, True, 16, 16, (4, 4)),
    (61, 23, True, 16, 16, (4, 8)),
    (50, 23, True, 32, 16, (8, 4)),
    (61, 100, True, 16, 16, (4, 4)),
    (40, 7, True, 16, 16, (16, 16)),
    (2000, 300, True, 512, 512, "forward"),      # the derived pieces: the forward's a block,
    (2000, 300, True, 512, 512, "backward"),     # the backward's 256 at a block of 512,
    (2000, None, True, 1024, 512, "backward"),   # 512 x 256,
    (300, 70, True, 256, 256, "backward"),       # 128 at a block of 256;
    (100, 30, True, 64, 64, "backward"),         # a small block is one piece
]


def _grain_argument(grain):
    """A case's pieces: given, or a kernel's own."""
    return dict(backward=grain == "backward") if isinstance(grain, str) else dict(grain=grain)


class TestFlashSchedule:
    """The classification the kernels decide by, against the dense mask."""

    @pytest.mark.parametrize("seq_len,window,causal,block_q,block_k,grain", SCHEDULES)
    def test_classes_against_the_dense_mask(self, seq_len, window, causal, block_q, block_k, grain):
        grain = _grain_argument(grain)
        classes, (gq, gk) = piece_classes(seq_len, window, causal, block_q, block_k, **grain)
        s_pad = classes.shape[0] * gq
        assert classes.shape[1] * gk == s_pad
        valid = _dense_mask(seq_len, s_pad, window, causal)
        seen = {0: 0, 1: 0, 2: 0}
        for i in range(classes.shape[0]):
            for j in range(classes.shape[1]):
                piece = valid[i * gq:(i + 1) * gq, j * gk:(j + 1) * gk]
                kind = int(classes[i, j])
                seen[kind] += 1
                if kind == 0:
                    assert not piece.any(), (i, j)
                elif kind == 1:
                    assert piece.all(), (i, j)
                else:       # crossed: an edge runs through it, so the mask is not for nothing
                    assert piece.any() and not piece.all(), (i, j)
        counts = flash_schedule(seq_len, window, causal, block_q, block_k, **grain)
        assert counts["pairs"] == {"skipped": seen[0] * gq * gk, "whole": seen[1] * gq * gk,
                                   "crossed": seen[2] * gq * gk}
        assert sum(counts["pairs"].values()) == s_pad * s_pad
        assert counts["needed"] == int(valid[:seq_len].sum())
        # (a padded query row's pairs are computed with its block's and needed by nobody)
        assert counts["pairs"]["whole"] <= int(valid.sum()) \
            <= counts["pairs"]["whole"] + counts["pairs"]["crossed"]

    @pytest.mark.parametrize("seq_len,window,causal,block_q,block_k,grain", SCHEDULES)
    def test_the_grids_visit_every_block_that_holds_a_pair(self, seq_len, window, causal, block_q,
                                                           block_k, grain):
        """Both walks (a query block's keys, a key block's queries) name exactly
        the blocks with a pair the positions allow; the blocks between are the
        ones ``flash_schedule`` counts as ``tiles``."""
        grain = _grain_argument(grain)
        classes, (gq, gk) = piece_classes(seq_len, window, causal, block_q, block_k, **grain)
        s_pad = classes.shape[0] * gq
        block_q, block_k = min(block_q, seq_len), min(block_k, seq_len)
        n_q, n_k = s_pad // block_q, s_pad // block_k
        valid = _dense_mask(s_pad, s_pad, window, causal)    # positions alone: no padded end
        holds = valid.reshape(n_q, block_q, n_k, block_k).any(axis=(1, 3))
        by_q = np.zeros_like(holds)
        by_k = np.zeros_like(holds)
        for i in range(n_q):
            first, last = _k_blocks(i, block_q, block_k, n_k, causal, window)
            by_q[i, first:last + 1] = True
        for j in range(n_k):
            first, last = _q_blocks(j, block_q, block_k, n_q, causal, window)
            by_k[first:last + 1, j] = True
        np.testing.assert_array_equal(by_q, holds)
        np.testing.assert_array_equal(by_k, holds)
        assert flash_schedule(seq_len, window, causal, block_q, block_k, **grain)["tiles"] == holds.sum()

    def test_the_cells_schedules(self):
        """What the two training cells' calls compute over what they need: the
        band, Mellum's full layer and Mistral's triangle. The forward walks a
        block a piece (the band's two blocks a query block are both crossed:
        twice its pairs, as before there were classes; a triangle's blocks
        under the diagonal are whole), the backward kernels pieces of 512."""
        band, band_back = flash_schedule(8192, 1024), flash_schedule(8192, 1024, backward=True)
        assert band["tiles"] == band_back["tiles"] == 15
        assert (band["piece"], band_back["piece"]) == ([1024, 1024], [512, 512])
        assert round(band["computed_over_needed"], 2) == 2.0 and band["masked_over_computed"] == 1.0
        assert round(band_back["computed_over_needed"], 2) == 1.5
        assert round(band_back["masked_over_computed"], 3) == round(2 / 3, 3)
        full, mistral = flash_schedule(8192), flash_schedule(4096)
        assert (full["tiles"], mistral["tiles"]) == (36, 10)
        assert round(mistral["computed_over_needed"], 2) == 1.25
        assert mistral["masked_over_computed"] == 0.4           # 4 of 10 blocks: 1.0 before
        back = flash_schedule(4096, backward=True)
        assert round(back["computed_over_needed"], 3) == round(36 * 512 * 512 / mistral["needed"], 3)
        assert round(back["masked_over_computed"], 3) == round(8 / 36, 3)


# (seq_len, window, causal, block_q, block_k): blocks of whole lane tiles, so the backward kernels
# walk pieces, and every class occurs in one call: empty, whole and crossed pieces (the diagonal,
# the window's far edge, the padded end) and whole blocks
PIECEWISE = {
    "causal": (600, None, True, 256, 256),
    "causal-unequal-blocks": (600, None, True, 256, 512),
    "band": (600, 300, True, 256, 256),
    "band-of-one": (300, 1, True, 256, 256),
    "band-longer-than-the-sequence": (520, 1000, True, 512, 256),
    "not-causal": (600, None, False, 256, 256),
}


class TestFlashPiecewise:

    @pytest.mark.parametrize("segments", [False, True], ids=["no-ids", "segment-ids"])
    @pytest.mark.parametrize("case", sorted(PIECEWISE))
    def test_forward_and_gradients_match_reference(self, case, segments):
        seq_len, window, causal, block_q, block_k = PIECEWISE[case]
        pairs = flash_schedule(seq_len, window, causal, block_q, block_k, backward=True)["pairs"]
        assert pairs["crossed"] and (pairs["whole"] or window == 1)
        assert pairs["skipped"]
        rng = np.random.default_rng(seq_len)
        q, k, v = (jnp.asarray(rng.standard_normal((1, seq_len, 2, 32)), jnp.float32)
                   for _ in range(3))
        seg = jnp.asarray(np.sort(rng.integers(0, 3, (1, seq_len)), axis=1), jnp.int32) \
            if segments else None

        def run(force_pallas):
            def loss(q, k, v):
                out = flash_attention(q, k, v, causal=causal, window=window, block_q=block_q,
                                      block_k=block_k, segment_ids=seg, interpret=True,
                                      force_pallas=force_pallas)
                return jnp.sum(out * jnp.cos(out)), out
            return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

        (_, got), got_grads = run(True)
        (_, want), want_grads = run(False)
        np.testing.assert_allclose(got, want, atol=5e-6)
        for g, w in zip(got_grads, want_grads):
            np.testing.assert_allclose(g, w, atol=2e-5)

    @pytest.mark.parametrize("segments", [False, True], ids=["no-ids", "segment-ids"])
    def test_no_segment_ids_no_segment_operands(self, segments):
        """``segment_ids=None`` hands the kernels q, k, v alone and no piece
        compares ids; with ids every computed piece is masked."""
        q = jnp.zeros((1, 512, 1, 32), jnp.float32)
        seg = jnp.zeros((1, 512), jnp.int32) if segments else None
        jaxpr = jax.make_jaxpr(lambda q: jax.grad(lambda q: jnp.sum(flash_attention(
            q, q, q, block_q=256, block_k=256, segment_ids=seg, interpret=True,
            force_pallas=True)))(q))(q)
        calls = {str(eqn.params["name"]): eqn for eqn in _eqns(jaxpr.jaxpr)
                 if eqn.primitive.name == "pallas_call"}
        operands = {"flash_attention_fwd": 3, "flash_attention_dkv": 6, "flash_attention_dq": 6}
        assert set(calls) == set(operands)
        for name, eqn in calls.items():
            assert len(eqn.invars) == operands[name] + 2 * segments
            body = {e.primitive.name for e in _eqns(eqn.params["jaxpr"])}
            assert ("transpose" in body) == segments       # the ids' comparison, and no other
            assert "iota" in body and "exp" in body


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


class TestFusedNorms:

    def test_rms_norm_forward(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 96, 256).astype(np.float32))
        scale = jnp.asarray(rng.randn(256).astype(np.float32))
        out = fused_rms_norm(x, scale, 1e-5, True)
        rstd = jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-5)
        ref = x * rstd * scale
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)

    def test_rms_norm_grad(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
        scale = jnp.asarray(1.0 + 0.1 * rng.randn(128).astype(np.float32))

        def f_kernel(x, s):
            return jnp.sum(jnp.square(fused_rms_norm(x, s, 1e-5, True)))

        def f_ref(x, s):
            rstd = jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-5)
            return jnp.sum(jnp.square(x * rstd * s))

        gk = jax.grad(f_kernel, argnums=(0, 1))(x, scale)
        gr = jax.grad(f_ref, argnums=(0, 1))(x, scale)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)

    def test_layer_norm_forward_and_grad(self):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(16, 128).astype(np.float32))
        scale = jnp.asarray(1.0 + 0.1 * rng.randn(128).astype(np.float32))
        bias = jnp.asarray(0.1 * rng.randn(128).astype(np.float32))

        def f_kernel(x, s, b):
            return jnp.sum(jnp.abs(fused_layer_norm(x, s, b, 1e-5, True)))

        def f_ref(x, s, b):
            mean = jnp.mean(x, -1, keepdims=True)
            xc = x - mean
            rstd = jax.lax.rsqrt(jnp.mean(jnp.square(xc), -1, keepdims=True) + 1e-5)
            return jnp.sum(jnp.abs(xc * rstd * s + b))

        np.testing.assert_allclose(np.asarray(fused_layer_norm(x, scale, bias, 1e-5, True)),
                                   np.asarray((x - x.mean(-1, keepdims=True))
                                              * jax.lax.rsqrt(x.var(-1, keepdims=True) + 1e-5)
                                              * scale + bias), atol=1e-4, rtol=1e-4)
        gk = jax.grad(f_kernel, argnums=(0, 1, 2))(x, scale, bias)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, scale, bias)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


class TestQuantization:

    def test_roundtrip_error_bound(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(1000).astype(np.float32))
        v, s, shape = quantize_int8(x, group_size=256, interpret=True)
        assert v.dtype == jnp.int8
        # fp32 explicitly: the default dequant dtype is bf16 (serving)
        # whose rounding would swamp the int8 bound below
        back = dequantize_int8(v, s, shape, dtype=jnp.float32, interpret=True)
        # max error per group is scale/2 = absmax/254
        bound = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(back - x))) <= bound

    def test_default_dequant_dtype_is_bf16(self):
        x = jnp.asarray(np.random.RandomState(9).randn(64).astype(np.float32))
        v, s, shape = quantize_int8(x, group_size=64, interpret=True)
        assert dequantize_int8(v, s, shape, interpret=True).dtype == jnp.bfloat16

    def test_matches_xla_reference(self):
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(16, 64).astype(np.float32))
        vk, sk, _ = quantize_int8(x, group_size=64, interpret=True)
        vr, sr, _ = quantize_int8(x, group_size=64, interpret=None)
        # identical math → identical outputs (CPU default path is XLA)
        np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
        np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)

    def test_zero_tensor(self):
        x = jnp.zeros(128)
        v, s, shape = quantize_int8(x, group_size=64, interpret=True)
        back = dequantize_int8(v, s, shape, interpret=True)
        np.testing.assert_array_equal(np.asarray(back), np.zeros(128, np.float32))


class TestFlashSegmentsAndBias:
    """VERDICT weak-edge: packed sequences (segment ids) and additive
    bias in the attention API."""

    def test_segment_ids_match_per_sequence_attention(self):
        import numpy as np
        import jax, jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        rng = np.random.RandomState(0)
        B, S, H, D = 2, 128, 2, 32
        q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        # two packed sequences: [0]*64 + [1]*64
        seg = jnp.asarray(np.repeat([[0, 1]], 64, axis=1).reshape(1, S).repeat(B, 0))
        packed = flash_attention(q, k, v, causal=True, segment_ids=seg,
                                 force_pallas=True, interpret=True, block_q=64, block_k=64)
        # reference: run each 64-token segment independently
        for lo, hi in ((0, 64), (64, 128)):
            part = flash_attention(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], causal=True,
                                   force_pallas=False)
            np.testing.assert_allclose(np.asarray(packed[:, lo:hi]), np.asarray(part),
                                       rtol=2e-5, atol=2e-5)

    def test_segment_ids_xla_path_matches_kernel(self):
        import numpy as np
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        rng = np.random.RandomState(1)
        B, S, H, D = 1, 96, 2, 16
        q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        seg = jnp.asarray(rng.randint(0, 3, size=(B, S)).astype(np.int32))
        a = flash_attention(q, q, q, causal=False, segment_ids=seg,
                            force_pallas=True, interpret=True, block_q=32, block_k=32)
        b = flash_attention(q, q, q, causal=False, segment_ids=seg, force_pallas=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)

    def test_bias_differentiable(self):
        import numpy as np
        import jax, jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        rng = np.random.RandomState(2)
        B, S, H, D = 1, 32, 2, 16
        q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        bias = jnp.asarray(rng.randn(B, 1, S, S).astype(np.float32) * 0.1)

        def loss(bias):
            return flash_attention(q, q, q, causal=True, bias=bias).sum()

        g = jax.grad(loss)(bias)
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).max()) > 0

    def test_segment_grads_respect_boundaries(self):
        import numpy as np
        import jax, jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        rng = np.random.RandomState(3)
        B, S, H, D = 1, 64, 1, 16
        q = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
        seg = jnp.asarray(np.repeat([[0, 1]], 32, axis=1).reshape(1, S))

        def loss_first_half(kv):
            k2, v2 = kv
            out = flash_attention(q, k2, v2, causal=True, segment_ids=seg,
                                  force_pallas=True, interpret=True,
                                  block_q=32, block_k=32)
            return out[:, :32].astype(jnp.float32).sum()

        gk, gv = jax.grad(loss_first_half)((k, v))
        # second segment's k/v must get zero gradient from the first's loss
        assert float(jnp.abs(gk[:, 32:]).max()) == 0.0
        assert float(jnp.abs(gv[:, 32:]).max()) == 0.0
        assert float(jnp.abs(gk[:, :32]).max()) > 0
