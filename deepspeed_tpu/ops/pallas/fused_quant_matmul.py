"""Fused dequantize-matmul Pallas kernels (the FP6-LLM execution model).

TPU-native form of the reference's TC-FPx / FP6-LLM GEMM
(``csrc/fp6_llm/``, ``inference/v2/.../quantized_linear.py``): compute
``x @ dequant(values, scales)`` while the weight matrix only ever exists
in HBM as its quantized carrier bytes. Each grid step streams one
``[bk, bn]`` weight tile into VMEM, dequantizes it in registers (fp6
additionally bit-unpacks its packed uint8 bytes in-kernel), applies the
per-(row, group) scale, and feeds the MXU — the bf16 weight matrix is
never materialized beyond one tile set, so quantized serving pays
quantized HBM bandwidth instead of dequant-then-matmul's full-precision
round trip.

Layout contract (the ``QuantizedWeight(layout='grouped')`` storage):
for a ``[K, N]`` kernel, int8/fp8 carriers are ``values [K, N]``, fp6
carriers are packed ``values [K, N*3//4]`` uint8 (4 e3m2 codes per 3
bytes, group-aligned because groups are multiples of 4), and scales are
fp32 ``[K, ng]`` with group width ``g = N // ng``. The scale varies per
``(k, n-group)`` so dequantization cannot be factored out of the K sum;
it must be applied to the weight tile *before* the dot, which is
exactly what this kernel does per tile.

Dispatch follows the package policy (``use_pallas()``): the kernel runs
on shard-local operands on TPU or under ``interpret=True`` (CPU tests);
everywhere else — including under a live multi-device mesh, where
``pallas_call`` has no GSPMD rule — ``quant_matmul`` lowers to the pure
jnp reference ``x @ dequantize_grouped(...)``, which XLA shards with
the carriers' own PartitionSpecs, so TP sharding of quantized weights
keeps working unchanged. Mosaic caveats (minor-dim reshapes in the fp6
unpack / scale expansion) are exercised in interpret mode by the parity
suite, the same verification contract as the other kernels here.

The public entry is differentiable via ``jax.custom_vjp``: the backward
pass computes ``dx = g @ dequant(W).T`` from the carriers (weights are
frozen — integer carriers get float0 cotangents), which is what
``OptimizedLinear`` LoRA training over a quantized base needs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.grouped_matmul import _fit_tile

# VMEM is ~16MB/core; leave headroom for Mosaic's own buffers.
_VMEM_BUDGET = 8 * 1024 * 1024


# ---------------------------------------------------------------------------
# reference dequantization (canonical grouped-layout decode)
# ---------------------------------------------------------------------------

def dequantize_grouped(values, scales, scheme, dtype=jnp.bfloat16):
    """Grouped-layout dequantize: shapes derive from the CARRIERS (never
    stored metadata) so a per-layer slice of an ``nn.scan`` stacked leaf
    decodes correctly — grouped layout has no padding, so the original
    last dim is ``ng * g`` codes (= packed_last * 4/3 for fp6)."""
    ng = scales.shape[-1]
    grouped = values.reshape(values.shape[:-1] + (ng, values.shape[-1] // ng))
    if scheme == "fp6":
        from deepspeed_tpu.ops.fp_quantizer.quantize import _decode_e3m2, unpack_fp6
        vals = _decode_e3m2(unpack_fp6(grouped))
    else:
        vals = grouped.astype(jnp.float32)
    out = vals * scales[..., None]
    return out.reshape(out.shape[:-2] + (-1,)).astype(dtype)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _unpack_fp6_tile(v):
    """uint8 byte tile [bk, 3n] → int32 codes [bk, 4n], in registers.

    Equivalent to ``unpack_fp6``: each 3-byte triple is one little-endian
    24-bit word holding 4 six-bit codes at bit offsets 0/6/12/18.
    """
    bk, b3 = v.shape
    b = v.reshape(bk, b3 // 3, 3).astype(jnp.int32)
    u = b[:, :, 0] | (b[:, :, 1] << 8) | (b[:, :, 2] << 16)
    codes = jnp.stack([(u >> s) & 0x3F for s in (0, 6, 12, 18)], axis=-1)
    return codes.reshape(bk, b3 // 3 * 4)


def _qmm_kernel(x_ref, v_ref, s_ref, o_ref, acc_ref, *, scheme, group, n_k,
                dequant_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = v_ref[...]
    if scheme == "fp6":
        from deepspeed_tpu.ops.fp_quantizer.quantize import _decode_e3m2
        w = _decode_e3m2(_unpack_fp6_tile(v))
    else:
        w = v.astype(jnp.float32)
    bk, bn = w.shape
    # per-(row, group) scales: expand [bk, bn//g] over each group of g lanes
    s = s_ref[...]
    w = (w.reshape(bk, bn // group, group) * s[:, :, None]).reshape(bk, bn)
    # MXU wants matching operand dtypes; promote explicitly (the jnp
    # fallback's implicit x @ w promotion does the same).
    ct = jnp.result_type(x_ref.dtype, dequant_dtype)
    acc_ref[...] += jnp.dot(x_ref[...].astype(ct),
                            w.astype(dequant_dtype).astype(ct),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def _pick_tiles(M, K, N, g, scheme, x_dtype, v_dtype):
    """→ (bm, bk, bn) fitting the VMEM budget, or None when no legal
    tiling exists (caller falls back to the jnp reference). bn is a
    multiple of g so every tile sees whole scale groups."""
    bm = min(128, -(-M // 8) * 8)
    ng = N // g
    # candidate bn = t*g with t | ng, preferring ~512 lanes; if a single
    # group is already wider than that, the tile is one group.
    ts = sorted([t for t in _divisors(ng) if t * g <= 512], reverse=True) or [1]
    bks = set()
    for c in (512, 256, 128, 64, 32, 16, 8):
        try:
            bks.add(_fit_tile(c, K))
        except ValueError:
            pass  # no ladder tile under this cap divides K
    if not bks:
        return None  # pathological K: jnp reference path
    bks = sorted(bks, reverse=True)

    def vmem_bytes(bk, bn):
        xb = bm * bk * jnp.dtype(x_dtype).itemsize
        vb = bk * (bn * 3 // 4 if scheme == "fp6" else bn) * jnp.dtype(v_dtype).itemsize
        sb = bk * (bn // g) * 4
        # acc scratch + out tile + dequant temporaries (fp6 unpack holds
        # a few int32 intermediates per lane)
        work = bm * bn * 8 + bk * bn * (12 if scheme == "fp6" else 4)
        return xb + vb + sb + work

    for t in ts:
        for bk in bks:
            if vmem_bytes(bk, t * g) <= _VMEM_BUDGET:
                return bm, bk, t * g
    return None


def _qmm_pallas(x2, values, scales, scheme, dequant_dtype, out_dtype, interpret):
    """Tiled fused kernel over 2-D ``x2 [M, K]``; → [M, N] or None when
    the shapes admit no legal tiling."""
    M, K = x2.shape
    ng = scales.shape[-1]
    N = values.shape[-1] * 4 // 3 if scheme == "fp6" else values.shape[-1]
    if values.shape[0] != K or ng == 0 or N % ng:
        return None
    g = N // ng
    if scheme == "fp6" and (g % 4 or values.shape[-1] * 4 != N * 3):
        return None
    tiles = _pick_tiles(M, K, N, g, scheme, x2.dtype, values.dtype)
    if tiles is None:
        return None
    bm, bk, bn = tiles
    mp = -(-M // bm) * bm
    if mp != M:
        x2 = jnp.pad(x2, ((0, mp - M), (0, 0)))
    vbn = bn * 3 // 4 if scheme == "fp6" else bn
    n_k = K // bk
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, scheme=scheme, group=g, n_k=n_k,
                          dequant_dtype=dequant_dtype),
        grid=(mp // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, vbn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn // g), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x2, values, scales)
    return out[:M] if mp != M else out


# ---------------------------------------------------------------------------
# differentiable public entry
# ---------------------------------------------------------------------------

def _qmm_impl(x, values, scales, scheme, dequant_dtype, out_dtype, interpret,
              force_pallas):
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    use_kernel = (force_pallas is True or interpret is True
                  or (force_pallas is not False and use_pallas()))
    if interpret is None:
        interpret = default_interpret()
    lead, k_dim = x.shape[:-1], x.shape[-1]
    if use_kernel and values.ndim == 2 and scales.ndim == 2:
        out = _qmm_pallas(x.reshape(-1, k_dim), values, scales, scheme,
                          dequant_dtype, out_dtype, interpret)
        if out is not None:
            return out.reshape(lead + (out.shape[-1],))
    if force_pallas is True:
        raise ValueError(
            f"quant_matmul(force_pallas=True): the fused kernel admits no legal tiling for "
            f"x{tuple(x.shape)}, values{tuple(values.shape)}, scales{tuple(scales.shape)} "
            f"({scheme}); only the dequantize-then-matmul reference can take these shapes")
    w = dequantize_grouped(values, scales, scheme, dequant_dtype)
    return jnp.matmul(x, w).astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _qmm(x, values, scales, scheme, dequant_dtype, out_dtype, interpret,
         force_pallas):
    return _qmm_impl(x, values, scales, scheme, dequant_dtype, out_dtype,
                     interpret, force_pallas)


def _qmm_fwd(x, values, scales, scheme, dequant_dtype, out_dtype, interpret,
             force_pallas):
    y = _qmm_impl(x, values, scales, scheme, dequant_dtype, out_dtype,
                  interpret, force_pallas)
    # residuals must be JAX types: carry x's dtype as a 0-size array
    return y, (values, scales, jnp.zeros((0,), x.dtype))


def _zero_carrier_cotangent(v):
    if jnp.issubdtype(v.dtype, jnp.floating):  # fp8 carriers
        return jnp.zeros(v.shape, v.dtype)
    return np.zeros(v.shape, jax.dtypes.float0)  # int8/uint8 carriers


def _qmm_bwd(scheme, dequant_dtype, out_dtype, interpret, force_pallas, res, g):
    values, scales, x_proto = res
    w = dequantize_grouped(values, scales, scheme, jnp.float32)
    dx = jnp.matmul(g.astype(jnp.float32), w.T).astype(x_proto.dtype)
    return dx, _zero_carrier_cotangent(values), jnp.zeros_like(scales)


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def quant_matmul(x, values, scales, scheme, *, dequant_dtype=jnp.bfloat16,
                 out_dtype=None, interpret=None, force_pallas=None):
    """Fused ``x[..., K] @ dequant(values, scales) → [..., N]``.

    ``values``/``scales`` are grouped-layout carriers for a ``[K, N]``
    weight (see module docstring). Output dtype defaults to
    ``result_type(x.dtype, dequant_dtype)`` — identical to the unboxed
    ``x @ w_dequant`` it replaces, so the two paths are numerically
    interchangeable. ``interpret=True`` forces the kernel in interpreter
    mode (CPU tests); ``force_pallas`` overrides the ``use_pallas()``
    dispatch in both directions. Differentiable in ``x`` only (carriers
    are frozen weights).
    """
    dequant_dtype = jnp.dtype(dequant_dtype)
    out_dtype = jnp.dtype(out_dtype or jnp.result_type(x.dtype, dequant_dtype))
    return _qmm(x, values, scales, scheme, dequant_dtype, out_dtype, interpret,
                force_pallas)
