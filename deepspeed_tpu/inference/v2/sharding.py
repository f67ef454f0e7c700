"""Tensor/expert-parallel sharding for v2 ragged serving.

Capability match for the reference's
``deepspeed/inference/v2/model_implementations/sharding/`` (attn.py:
head sharding, mlp.py: column/row MLP sharding, embedding.py: vocab
sharding) and the TP wiring in ``engine_v2.py:30``. TPU redesign:
instead of slicing torch tensors per rank, every decision is a
``PartitionSpec`` from the model family's ``tp_rule`` — parameters are
``device_put`` once with those shardings, the flat token batch stays
replicated, the blocked KV pool is sharded over whole KV heads, and
GSPMD inserts the Megatron all-reduces inside the jitted ragged step.
"""

import numpy as np

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def tp_rule_for(model_config):
    """The family tp_rule for a ``LlamaConfig`` or ``GPTConfig`` (the
    same rules training's ZeRO sharding policy consumes)."""
    if hasattr(model_config, "position_embedding"):  # GPT family
        from deepspeed_tpu.models.gpt import gpt_tp_rule
        return gpt_tp_rule
    from deepspeed_tpu.models.llama import llama_tp_rule
    return llama_tp_rule


def live_entries(mesh, spec, shape):
    """Resolve a PartitionSpec against a concrete mesh and shape: axes
    of size 1 (or absent) are dropped, and any dim that does not divide
    evenly over its axes falls back to replicated (the reference refuses
    such configs per-shape in sharding/utils.py; serving correctness
    must not depend on divisibility, so replicate instead)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def live(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if sizes.get(a, 1) > 1)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return e if sizes.get(e, 1) > 1 else None

    entries = [live(e) for e in spec]
    for d, e in enumerate(entries):
        if e is None:
            continue
        n = int(np.prod([sizes[a] for a in (e if isinstance(e, tuple) else (e,))]))
        if shape[d] % n != 0:
            entries[d] = None
    return entries


def param_sharding(mesh, rule, path, shape) -> NamedSharding:
    return NamedSharding(mesh, P(*live_entries(mesh, rule(path, shape), shape)))


def shard_params(params, mesh, rule, dtype=None):
    """Cast (optionally) and place a param tree over ``mesh`` per the
    family ``rule``. Used by both the v1 engine and the v2 ragged
    engine — one implementation of the reference's per-rank weight
    slicing.

    ``QuantizedWeight`` leaves (layout='grouped') are placed by applying
    the rule for the ORIGINAL leaf shape to both carriers: ``values``
    keeps the leaf's dim structure (fp6 packs the last dim 4→3 bytes,
    which shards positionally), ``scales`` takes the same spec with the
    group-count dim in place of the last dim; any non-divisible dim
    falls back to replicated via :func:`live_entries`."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    from deepspeed_tpu.runtime.zero.partitioning import path_tree_map

    def place(path, x):
        if isinstance(x, QuantizedWeight):
            if x.layout != "grouped":
                raise ValueError(
                    f"cannot shard flat-layout quantized leaf {path}; quantize "
                    "with layout='grouped' (structure-preserving) to compose "
                    "with tensor/expert parallelism")
            entries = live_entries(mesh, rule(path, x.shape), x.shape)
            v = jax.device_put(x.values, NamedSharding(
                mesh, P(*live_entries(mesh, P(*entries), x.values.shape))))
            s = jax.device_put(x.scales, NamedSharding(
                mesh, P(*live_entries(mesh, P(*entries), x.scales.shape))))
            return QuantizedWeight(v, s, x.shape, x.scheme, x.layout, x.dequant_dtype)
        x = jnp.asarray(x)
        if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(dtype)
        return jax.device_put(x, param_sharding(mesh, rule, path, x.shape))

    return path_tree_map(place, params, is_leaf=lambda x: isinstance(x, QuantizedWeight))


def moe_expert_specs(mesh, w1, w3, w2):
    """Shard plan for stacked MoE expert weights entering the dropless
    shard_map (``ops/grouped_gemm.dropless_moe_ffn``): the expert dim
    over the mesh's 'expert' axis — E/ep carriers per replica — and
    features over 'tensor' when the geometry allows (columns of the
    [E, D, I] gate/up stacks, rows of the [E, I, D] down stack).

    Each weight may be dense or a grouped-layout ``QuantizedWeight``.
    Quantized stacks shard their values AND scales: the scale group axis
    must split evenly over 'tensor' (scales shard along with the
    columns) or be a single group (scales replicate; every column shares
    the one scale, so shard-local dequant still derives the right group
    width); fp6 additionally needs the packed byte dim to split on whole
    4-code triples. When any stack fails its check the plan drops to
    feature-replicated experts with an 'expert'-only psum — summing a
    replicated 'tensor' axis would overcount.

    → ``(w_specs, psum_axes)`` where ``w_specs`` has one spec TUPLE per
    weight, matching that weight's ``_split_stack`` decomposition
    (``(values_spec, scales_spec)`` for quantized, ``(spec,)`` dense).
    """
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("tensor", 1)

    def logical_last(w):
        if w.scheme == "fp6":
            return w.values.shape[-1] * 4 // 3
        return w.values.shape[-1]

    def col_ok(w):  # shard the last (feature) dim of [E, K, N]
        if not isinstance(w, QuantizedWeight):
            return w.shape[-1] % tp == 0
        n, ng = logical_last(w), w.scales.shape[-1]
        if ng == 0 or n % ng or n % tp:
            return False
        if ng % tp and ng != 1:
            return False
        if w.scheme == "fp6" and (w.values.shape[-1] % tp or (n // tp) % 4):
            return False
        return True

    def row_ok(w):  # shard the middle (contraction) dim of [E, I, D]
        dim = w.values.shape[-2] if isinstance(w, QuantizedWeight) else w.shape[-2]
        return dim % tp == 0

    tensor_ok = col_ok(w1) and col_ok(w3) and row_ok(w2)

    def specs(w, kind):
        if tensor_ok:
            val = P("expert", None, "tensor") if kind == "col" else P("expert", "tensor", None)
        else:
            val = P("expert", None, None)
        if not isinstance(w, QuantizedWeight):
            return (val,)
        if kind == "col" and tensor_ok and w.scales.shape[-1] % tp == 0:
            return (val, P("expert", None, "tensor"))
        if kind == "row" and tensor_ok:
            return (val, P("expert", "tensor", None))
        return (val, P("expert", None, None))

    psum_axes = ("expert", "tensor") if tensor_ok else ("expert",)
    return (specs(w1, "col"), specs(w3, "col"), specs(w2, "row")), psum_axes


def kv_pool_spec(mesh, n_kv_heads) -> P:
    """Blocked KV pool [L, NB, bs, Hkv*Dh]: shard the flattened last dim
    over 'tensor' in contiguous groups of whole KV heads (reference
    sharding/attn.py shards KV heads per rank; MQA with Hkv < tp — or
    any Hkv % tp != 0 — replicates, exactly as the reference replicates
    the single KV head), so divisibility is the head count's, not the
    flattened width's."""
    return P(*live_entries(mesh, P(None, None, None, "tensor"),
                           (1, 1, 1, n_kv_heads)))
