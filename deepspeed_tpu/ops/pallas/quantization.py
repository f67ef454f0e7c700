"""Block quantization kernels (int8, symmetric, per-group scales).

TPU-native equivalent of the reference's quantization CUDA kernels
(``csrc/quantization/quantize.cu``, ``dequantize.cu``,
``fake_quantizer.cu``): group-wise symmetric int8 with fp32 scales,
used by ZeRO++-style compressed collectives (qwZ weight all-gather,
qgZ gradient all-to-all — see ``deepspeed_tpu/runtime/comm``) and by
weight-only inference quantization.

Layout: the tensor is flattened and viewed as [num_groups, group_size];
each group gets one scale = absmax/127. On TPU a Pallas kernel does the
absmax + scale + round in one VMEM pass (optionally with hardware
stochastic rounding); the XLA fallback is the same math.

Consumers: ZeRO++-style compressed collectives (qwZ/qgZ) and 1-bit
optimizers wire these in as those subsystems land; until then the ops
stand alone behind the kernel registry.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quant_kernel(seed_ref, x_ref, v_ref, s_ref, *, stochastic):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    scaled = x / scale
    if stochastic:
        # Mix the caller's step-varying seed with the block index so the
        # rounding pattern differs per step AND per block.
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.uint32)
        # uint32→f32 is unsupported on Mosaic; shift into int31 first
        frac = pltpu.bitcast(bits >> 9, jnp.int32).astype(jnp.float32) / jnp.float32(1 << 23)
        low = jnp.floor(scaled)
        scaled = low + (frac < (scaled - low)).astype(jnp.float32)
    else:
        scaled = jnp.round(scaled)
    v_ref[:] = jnp.clip(scaled, -127, 127).astype(jnp.int8)
    s_ref[:] = scale  # [block, 1] (scales kept 2-D for TPU layout)


def _dequant_kernel(v_ref, s_ref, o_ref):
    o_ref[:] = (v_ref[:].astype(jnp.float32) * s_ref[:]).astype(o_ref.dtype)


def _group_view(x, group_size):
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % group_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, group_size), n


def quantize_int8(x, group_size=2048, stochastic=False, seed=0, interpret=None):
    """→ (values int8 [G, group], scales fp32 [G], orig_shape). Groups are
    taken over the flattened tensor; pads to a group multiple. Pass a
    step-varying ``seed`` when ``stochastic`` so rounding averages out
    over steps."""
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    use_kernel = use_pallas() or interpret is True
    if interpret is None:
        interpret = default_interpret()
    groups, _ = _group_view(x, group_size)
    g = groups.shape[0]

    if use_kernel:
        block = min(256, g)
        padg = (-g) % block
        gp = jnp.pad(groups, ((0, padg), (0, 0))) if padg else groups
        seed_arr = jnp.asarray([seed], jnp.int32)
        values, scales = pl.pallas_call(
            functools.partial(_quant_kernel, stochastic=stochastic),
            grid=(gp.shape[0] // block,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block, group_size), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block, group_size), lambda i: (i, 0)),
                pl.BlockSpec((block, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(gp.shape, jnp.int8),
                jax.ShapeDtypeStruct((gp.shape[0], 1), jnp.float32),
            ],
            interpret=interpret,
        )(seed_arr, gp)
        values, scales = values[:g], scales[:g, 0]
    else:
        x32 = groups.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
        scales = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
        scaled = x32 / scales
        if stochastic:
            frac = jax.random.uniform(jax.random.PRNGKey(seed), scaled.shape)
            low = jnp.floor(scaled)
            scaled = low + (frac < (scaled - low)).astype(jnp.float32)
        else:
            scaled = jnp.round(scaled)
        values = jnp.clip(scaled, -127, 127).astype(jnp.int8)
        scales = scales[:, 0]
    return values, scales, x.shape


def dequantize_int8(values, scales, orig_shape, dtype=None, interpret=None):
    """Inverse of :func:`quantize_int8`. ``dtype`` defaults to bf16 — the
    serving dequant dtype — so a caller that forgets to thread its
    ``dequant_dtype`` through cannot silently upcast to fp32 and double
    the transient footprint; pass ``dtype=jnp.float32`` explicitly where
    full precision matters (round-trip bounds, LoRA fuse math)."""
    if dtype is None:
        dtype = jnp.bfloat16
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    use_kernel = use_pallas() or interpret is True
    if interpret is None:
        interpret = default_interpret()
    g, group_size = values.shape
    if use_kernel:
        block = min(256, g)
        padg = (-g) % block
        vp = jnp.pad(values, ((0, padg), (0, 0))) if padg else values
        sp = jnp.pad(scales, (0, padg)) if padg else scales
        sp = sp[:, None]  # 2-D for TPU layout
        out = pl.pallas_call(
            _dequant_kernel,
            grid=(vp.shape[0] // block,),
            in_specs=[
                pl.BlockSpec((block, group_size), lambda i: (i, 0)),
                pl.BlockSpec((block, 1), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block, group_size), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(vp.shape, dtype),
            interpret=interpret,
        )(vp, sp)[:g]
    else:
        out = (values.astype(jnp.float32) * scales[:, None]).astype(dtype)
    n = 1
    for s in orig_shape:
        n *= s
    return out.reshape(-1)[:n].reshape(orig_shape)
