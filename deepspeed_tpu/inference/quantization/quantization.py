"""ZeRO-Inference weight-only quantization.

Capability match for the reference's ``deepspeed/inference/quantization/``
(``_init_group_wise_weight_quantization``: swaps Linears for
QuantizedLinear with int-quantized weights, cutting serving memory).
TPU functional form: the params PYTREE is quantized (int8 or fp8 group
storage per leaf) and a transform dequantizes each leaf at use — the
jitted forward consumes the transform's output, so XLA fuses the
dequant into the first matmul and only the quantized bytes live in HBM."""

import math
import re

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.zero.partitioning import path_tree_map
from deepspeed_tpu.utils.env_registry import env_bool


from flax.core import meta as flax_meta


class QuantizedWeight(flax_meta.AxisMetadata):
    """One quantized leaf: int8/fp8/fp6 group values + fp32 scales.
    Registered as a pytree so quantized trees pass straight through jit
    (dequantization then happens inside the compiled serving step and
    XLA fuses it into the first matmul).

    Two storage layouts:

    - ``flat``    — the tensor is flattened to [G, group_size] (legacy;
      compact but erases the dim structure, so it cannot be sharded).
    - ``grouped`` — groups run along the LAST axis only; every leading
      dim is preserved, so the leaf's own PartitionSpec applies to
      ``values`` unchanged (int8/fp8 keep the original shape; fp6 packs
      the last dim to 3/4 size) and to ``scales`` with the group-count
      dim in place of the last dim. This is what lets quantized weights
      compose with TP/EP sharded serving (the reference's FP6-LLM TP2
      headline, inference/v2/modules/implementations/linear/quantized_linear.py).

    The class is also a flax ``AxisMetadata`` box (the ``nn.Partitioned``
    mechanism): flax unboxes at ``self.param`` access, which for an
    ``nn.scan`` layer stack happens INSIDE the scan body on the sliced
    carriers — so any flax model serves quantized trees with only one
    layer's dequantized weights transient (the FP6-LLM fused-dequant-GEMM
    execution model; a ``map_variables`` wrapper instead dequantizes the
    whole stack before the scan, which was measured to OOM a 2.5B model).
    """

    def __init__(self, values, scales, shape, scheme, layout="flat",
                 dequant_dtype=jnp.bfloat16):
        self.values = values
        self.scales = scales
        self.shape = tuple(shape)
        self.scheme = scheme
        self.layout = layout
        self.dequant_dtype = dequant_dtype

    def dequantized(self, dtype=jnp.bfloat16):
        if self.layout == "grouped":
            return _dequantize_grouped(self.values, self.scales, self.scheme, dtype)
        if self.scheme == "fp8":
            from deepspeed_tpu.ops.fp_quantizer.quantize import dequantize_fp8
            return dequantize_fp8(self.values, self.scales, self.shape, dtype=dtype)
        if self.scheme == "fp6":
            from deepspeed_tpu.ops.fp_quantizer.quantize import dequantize_fp6
            return dequantize_fp6(self.values, self.scales, self.shape, dtype=dtype)
        from deepspeed_tpu.ops.pallas.quantization import dequantize_int8
        return dequantize_int8(self.values, self.scales, self.shape, dtype=dtype)

    def matmul(self, x, dtype=None, interpret=None, force_pallas=None):
        """Fused ``x @ dequant(self)`` — the FP6-LLM execution path: on
        TPU the Pallas kernel dequantizes weight tiles in VMEM inside
        the matmul K-loop so the full-precision matrix never hits HBM;
        elsewhere (CPU, or sharded under a live mesh where pallas_call
        has no GSPMD rule) it lowers to the identical-math jnp fallback
        ``x @ self.dequantized(dtype)``. This is what quantized serving
        call sites should use instead of ``unbox()``-then-matmul.

        ``dtype`` overrides the stored ``dequant_dtype``. Only 2-D
        grouped-layout carriers take the fused route (a scan slice of a
        stacked layer leaf is exactly that); everything else — flat
        layout, stacked 3-D carriers, ``DS_FUSED_QMM=0`` — falls back
        to dequantize-then-matmul.
        """
        dd = dtype if dtype is not None else self.dequant_dtype
        if (self.layout == "grouped" and getattr(self.values, "ndim", 0) == 2
                and fused_qmm_enabled()):
            from deepspeed_tpu.ops.pallas.fused_quant_matmul import quant_matmul
            return quant_matmul(x, self.values, self.scales, self.scheme,
                                dequant_dtype=dd, interpret=interpret,
                                force_pallas=force_pallas)
        return x @ self.dequantized(dd)

    def nbytes(self):
        return int(self.values.size * self.values.dtype.itemsize +
                   self.scales.size * self.scales.dtype.itemsize)

    # flax AxisMetadata interface ---------------------------------------
    def unbox(self):
        return self.dequantized(self.dequant_dtype)

    def replace_boxed(self, val):
        # a lifted transform rewrote the value densely; keep it dense
        return _DenseParam(val)

    def add_axis(self, index, params):
        return self  # boxing happens post-init; lifted init never sees us

    def remove_axis(self, index, params):
        return self


class _DenseParam(flax_meta.AxisMetadata):
    """Dense replacement box produced when a transform writes through a
    QuantizedWeight (keeps the AxisMetadata contract without lossy
    re-quantization)."""

    def __init__(self, value):
        self.value = value

    def unbox(self):
        return self.value

    def replace_boxed(self, val):
        return _DenseParam(val)

    def add_axis(self, index, params):
        return self

    def remove_axis(self, index, params):
        return self


jax.tree_util.register_pytree_node(
    QuantizedWeight,
    lambda qw: ((qw.values, qw.scales), (qw.shape, qw.scheme, qw.layout, qw.dequant_dtype)),
    lambda aux, children: QuantizedWeight(children[0], children[1], *aux))
jax.tree_util.register_pytree_node(
    _DenseParam,
    lambda b: ((b.value,), None),
    lambda aux, children: _DenseParam(children[0]))


def _pick_group(last, group_size, multiple=1):
    """Largest group g <= group_size with last % g == 0 and g % multiple
    == 0 (no padding — padding would break positional sharding). None if
    no such divisor exists."""
    last, group_size = int(last), int(group_size)
    if last % group_size == 0 and group_size % multiple == 0:
        return group_size
    best = None
    d = multiple
    while d <= min(last, group_size):
        if last % d == 0:
            best = d
        d += multiple
    return best


def _quantize_grouped(x, scheme, group_size, dequant_dtype=jnp.bfloat16):
    """Structure-preserving group quantization along the last axis.
    → QuantizedWeight(layout='grouped') or the input unchanged when no
    legal group exists (fp6 needs groups of 4 codes)."""
    last = x.shape[-1]
    g = _pick_group(last, group_size, multiple=4 if scheme == "fp6" else 1)
    if g is None:
        return x
    gx = x.astype(jnp.float32).reshape(x.shape[:-1] + (last // g, g))
    if scheme == "fp6":
        from deepspeed_tpu.ops.fp_quantizer.quantize import (FP6_MAX, _encode_e3m2,
                                                             pack_fp6)
        fmax = FP6_MAX
    elif scheme == "fp8":
        fmax = 448.0
    else:
        fmax = 127.0
    absmax = jnp.max(jnp.abs(gx), axis=-1, keepdims=True)
    scales = jnp.where(absmax == 0.0, 1.0, absmax / fmax)
    scaled = gx / scales
    if scheme == "fp6":
        v = pack_fp6(_encode_e3m2(scaled)).reshape(x.shape[:-1] + (last * 3 // 4,))
    elif scheme == "fp8":
        v = scaled.astype(jnp.float8_e4m3fn).reshape(x.shape)
    else:
        v = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8).reshape(x.shape)
    return QuantizedWeight(v, scales[..., 0], x.shape, scheme, layout="grouped",
                           dequant_dtype=dequant_dtype)


def _dequantize_grouped(values, scales, scheme, dtype):
    # Canonical decode lives next to the fused kernel (single source of
    # truth for the grouped layout); shapes derive from the carriers so
    # a slice of a stacked leaf — e.g. one layer's slice inside an
    # ``nn.scan`` body — dequantizes correctly.
    from deepspeed_tpu.ops.pallas.fused_quant_matmul import dequantize_grouped
    return dequantize_grouped(values, scales, scheme, dtype)


def fused_qmm_enabled():
    """Fused dequant-matmul toggle (env ``DS_FUSED_QMM``, default on).
    Read at trace time — flip it and retrace to A/B the unbox path."""
    return env_bool("DS_FUSED_QMM")


def matmul_any(x, w, dtype=None):
    """``x @ w`` for a dense array OR a QuantizedWeight (fused when
    quantized) — the one-liner consumers use so a params leaf can be
    either without branching at every call site."""
    if isinstance(w, QuantizedWeight):
        return w.matmul(x, dtype=dtype)
    return x @ (w.astype(dtype) if dtype is not None else w)


def dequantize_tree(tree, dtype=jnp.bfloat16):
    """Dequantize every QuantizedWeight leaf in a pytree (other leaves
    pass through)."""
    return jax.tree.map(
        lambda x: x.dequantized(dtype) if isinstance(x, QuantizedWeight) else x,
        tree, is_leaf=lambda x: isinstance(x, QuantizedWeight))


def maybe_dequantize(x, dtype=jnp.bfloat16):
    return x.dequantized(dtype) if isinstance(x, QuantizedWeight) else x


def dequantize_tree_except(tree, dtype=jnp.bfloat16, skip_key="layers"):
    """Dequantize every QuantizedWeight leaf EXCEPT those under a
    ``skip_key`` path component — the scanned layer stack stays quantized
    so the scan body can dequantize one layer slice at a time (only O(1
    layer) of full-precision weights is ever live)."""

    def f(path, x):
        if skip_key in path.split("/"):
            return x
        return maybe_dequantize(x, dtype)

    return path_tree_map(f, tree, is_leaf=lambda x: isinstance(x, QuantizedWeight))


def quantize_params_tree(params, scheme, dequant_dtype=jnp.bfloat16, group_size=512,
                         pattern=r"kernel|embed|experts_w"):
    """Traceable whole-tree quantization: >=2-D float leaves matching
    ``pattern`` become grouped-layout QuantizedWeight carriers, other
    float leaves are cast to ``dequant_dtype``. Pure jnp — run it under
    ``jax.jit`` (ideally fused with the param init, or with the source
    tree donated) so XLA frees each full-precision leaf as its carrier
    is produced instead of holding both trees."""
    pat = re.compile(pattern)

    def q_leaf(path, x):
        if (getattr(x, "ndim", 0) >= 2 and jnp.issubdtype(x.dtype, jnp.floating)
                and pat.search(path)):
            q = _quantize_grouped(x, scheme, group_size, dequant_dtype=dequant_dtype)
            if isinstance(q, QuantizedWeight):
                return q
            x = q  # no legal group (fp6, last % 4 != 0): fall through to cast
        if jnp.issubdtype(getattr(x, "dtype", jnp.int32), jnp.floating):
            return x.astype(dequant_dtype)
        return x

    return path_tree_map(q_leaf, params)


def _init_group_wise_weight_quantization(params, ds_config=None, num_bits=8,
                                         group_size=512, modules=None, scheme="int8",
                                         layout="flat", dequant_dtype=jnp.bfloat16):
    """→ (quantized_tree, dequant_transform). ``modules``: regex list of
    leaf paths to quantize (default: every >=2-D float kernel). Pass
    ``layout='grouped'`` for the shardable structure-preserving form;
    ``dequant_dtype`` is what flax unboxing dequantizes to."""
    patterns = [re.compile(m) for m in (modules or [r".*"])]

    def q_leaf(path, x):
        if (getattr(x, "ndim", 0) < 2 or not jnp.issubdtype(x.dtype, jnp.floating)
                or not any(p.search(path) for p in patterns)):
            return x
        if layout == "grouped":
            return _quantize_grouped(x, scheme, group_size, dequant_dtype=dequant_dtype)
        if scheme == "fp8":
            from deepspeed_tpu.ops.fp_quantizer.quantize import quantize_fp8
            v, s, shape = quantize_fp8(x, group_size=group_size)
        elif scheme == "fp6":
            from deepspeed_tpu.ops.fp_quantizer.quantize import quantize_fp6
            v, s, shape = quantize_fp6(x, group_size=group_size)
        else:
            from deepspeed_tpu.ops.pallas.quantization import quantize_int8
            v, s, shape = quantize_int8(x, group_size=group_size)
        return QuantizedWeight(v, s, shape, scheme)

    qtree = path_tree_map(q_leaf, params)
    return qtree, dequantize_tree


def quantized_bytes(qtree):
    total = 0
    for leaf in jax.tree.leaves(qtree, is_leaf=lambda x: isinstance(x, QuantizedWeight)):
        if isinstance(leaf, QuantizedWeight):
            total += leaf.nbytes()
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total
