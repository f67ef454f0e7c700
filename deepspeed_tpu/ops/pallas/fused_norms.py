"""Fused RMSNorm / LayerNorm Pallas kernels.

TPU-native equivalent of the reference's fused normalization CUDA
kernels (``csrc/includes/normalize_layer.h``, ``rms_norm.cu`` under
``csrc/transformer/inference/csrc/``): a single VMEM pass computes the
fp32 statistics and the normalized output per row tile. The backward
pass is left to XLA (an elementwise chain the fuser handles well) via
``jax.custom_vjp`` with closed-form gradients, so no fp32 activations
are saved beyond the inputs.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rms_fwd_kernel(x_ref, scale_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[:] = (x * rstd * scale_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_fwd_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o_ref[:] = (xc * rstd * scale_ref[:].astype(jnp.float32)
                + bias_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _row_call(kernel, name, x2d, others, out_dtype, block_rows, interpret):
    rows, d = x2d.shape
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    x_p = jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d
    grid = (x_p.shape[0] // block_rows,)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))]
        + [pl.BlockSpec((d,), lambda i: (0,)) for _ in others],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x_p.shape, out_dtype),
        interpret=interpret,
        name=name,
    )(x_p, *others)
    return out[:rows] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm(x, scale, eps=1e-5, interpret=None):
    """RMSNorm over the last dim; fp32 statistics, any float dtype in/out."""
    out, _ = _rms_fwd(x, scale, eps, interpret)
    return out


def _rms_fwd(x, scale, eps, interpret):
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    # interpret=True forces the kernel (tests); interpret=False or None
    # off-TPU takes the XLA fallback.
    use_kernel = use_pallas() or interpret is True
    if interpret is None:
        interpret = default_interpret()
    shape = x.shape
    if use_kernel:
        x2d = x.reshape(-1, shape[-1])
        out = _row_call(functools.partial(_rms_fwd_kernel, eps=eps), "fused_rms_norm", x2d,
                        (scale,), x.dtype, 256, interpret).reshape(shape)
    else:
        x32 = x.astype(jnp.float32)
        rstd = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
        out = (x32 * rstd * scale.astype(jnp.float32)).astype(x.dtype)
    return out, (x, scale)


def _rms_bwd(eps, interpret, res, g):
    x, scale = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    s32 = scale.astype(jnp.float32)
    d = x.shape[-1]
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    gs = g32 * s32
    dx = rstd * gs - x32 * (rstd ** 3 / d) * jnp.sum(gs * x32, axis=-1, keepdims=True)
    dscale = jnp.sum((g32 * x32 * rstd).reshape(-1, d), axis=0)
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


fused_rms_norm.defvjp(lambda x, scale, eps, interpret: _rms_fwd(x, scale, eps, interpret),
                      _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_layer_norm(x, scale, bias, eps=1e-5, interpret=None):
    """LayerNorm over the last dim; fp32 statistics."""
    out, _ = _ln_fwd(x, scale, bias, eps, interpret)
    return out


def _ln_fwd(x, scale, bias, eps, interpret):
    from deepspeed_tpu.ops.pallas import default_interpret, use_pallas
    # interpret=True forces the kernel (tests); interpret=False or None
    # off-TPU takes the XLA fallback.
    use_kernel = use_pallas() or interpret is True
    if interpret is None:
        interpret = default_interpret()
    shape = x.shape
    if use_kernel:
        x2d = x.reshape(-1, shape[-1])
        out = _row_call(functools.partial(_ln_fwd_kernel, eps=eps), "fused_layer_norm", x2d,
                        (scale, bias), x.dtype, 256, interpret).reshape(shape)
    else:
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        xc = x32 - mean
        rstd = jax.lax.rsqrt(jnp.mean(jnp.square(xc), axis=-1, keepdims=True) + eps)
        out = (xc * rstd * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)
    return out, (x, scale, bias)


def _ln_bwd(eps, interpret, res, g):
    x, scale, bias = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    s32 = scale.astype(jnp.float32)
    d = x.shape[-1]
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mean
    rstd = jax.lax.rsqrt(jnp.mean(jnp.square(xc), axis=-1, keepdims=True) + eps)
    xhat = xc * rstd
    gs = g32 * s32
    dx = rstd * (gs - jnp.mean(gs, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum((g32 * xhat).reshape(-1, d), axis=0)
    dbias = jnp.sum(g32.reshape(-1, d), axis=0)
    return dx.astype(x.dtype), dscale.astype(scale.dtype), dbias.astype(bias.dtype)


fused_layer_norm.defvjp(lambda x, scale, bias, eps, interpret: _ln_fwd(x, scale, bias, eps, interpret),
                        _ln_bwd)
