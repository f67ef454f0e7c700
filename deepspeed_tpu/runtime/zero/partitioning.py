"""ZeRO stages as sharding rules.

This is the TPU-native replacement for the reference's torch-hook ZeRO
machinery (``stage_1_and_2.py``, ``stage3.py``,
``partition_parameters.py``): instead of partitioning flattened buffers
and intercepting module execution, each ZeRO stage is expressed as a
``PartitionSpec`` policy over the global mesh and XLA schedules the
collectives:

- stage 0: params/grads/optimizer replicated over the zero axes; grad
  all-reduce happens implicitly (psum when grads meet replicated
  optimizer state).
- stage 1: optimizer state (fp32 master + moments) sharded over the
  zero axes → XLA emits reduce-scatter(grads) + all-gather(params)
  around the update, which *is* ZeRO-1/2's communication schedule.
- stage 2: + gradients constrained to the sharded layout as they are
  produced (``with_sharding_constraint`` in the engine's grad
  accumulation), the analogue of IPG bucketing + early reduce-scatter
  (reference stage_1_and_2.py:931).
- stage 3: + parameters themselves sharded; with scan-over-layers a
  layer's parameters are gathered inside the iteration that uses them
  and freed after it. What the compiler makes of that on a TPU is a ring
  of partial matmuls a weight (``collective-permute``), which hides in
  the forward pass and not in the backward. ``overlap_comm`` (true at
  stage 3 unless set false) hands the backward of a layer scan to
  ``runtime/zero/overlap.py``: a layer gathered once, whole, for
  recomputation and differentiation alike, its small gradients summed by
  one asynchronous all-reduce - the prefetch coordinator and
  ``overlap_comm`` of the reference (partitioned_param_coordinator.py:62)
  as a program. It engages where :meth:`ZeroShardingPolicy.zero_sharded`
  holds for a leaf of the stack, the layers are not streamed from the
  host and are recomputed in full; ``overlap_comm: false``, stages 0-2,
  one device, decode and the ``dots`` / ``moe`` remat policies compile
  what they compiled before. ``stage3_prefetch_bucket_size`` and
  ``stage3_max_live_parameters`` are accepted and unread: the depth is
  one layer. Small params below ``param_persistence_threshold`` stay
  replicated, the analogue of persistent params (reference
  parameter_offload.py:242).
"""

from typing import Any, Callable, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import EXPERT_ZERO_AXES, ZERO_AXES


def _axis_sizes(mesh: Mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _spec_used_axes(spec):
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def shard_largest_free_dim(shape, base_spec, axes, mesh, allow_partial=True):
    """Extend ``base_spec`` by sharding the largest unsharded dim over
    ``axes`` (a tuple of mesh axis names). Falls back to a prefix of the
    axes when full divisibility fails; returns ``base_spec`` unchanged if
    nothing divides."""
    sizes = _axis_sizes(mesh)
    axes = tuple(a for a in axes if sizes.get(a, 1) > 1)
    if not axes:
        return base_spec
    base = list(base_spec) + [None] * (len(shape) - len(base_spec))
    used = _spec_used_axes(base)
    axes = tuple(a for a in axes if a not in used)
    if not axes:
        return P(*base)
    # Candidate dims: unsharded, sorted by size descending
    cand = sorted([d for d in range(len(shape)) if base[d] is None], key=lambda d: -shape[d])
    full = int(np.prod([sizes[a] for a in axes]))
    for d in cand:
        if shape[d] % full == 0 and shape[d] > 0:
            base[d] = axes if len(axes) > 1 else axes[0]
            return P(*base)
    if allow_partial:
        # Try shrinking the axis set (drop from the left: outer axes first)
        for k in range(len(axes) - 1, 0, -1):
            sub = axes[-k:]
            subprod = int(np.prod([sizes[a] for a in sub]))
            for d in cand:
                if shape[d] % subprod == 0 and shape[d] > 0:
                    base[d] = sub if len(sub) > 1 else sub[0]
                    return P(*base)
    return P(*base)


def is_expert_param(path: str) -> bool:
    return "expert" in path.lower()


class ZeroShardingPolicy:
    """Computes parameter/optimizer/gradient PartitionSpecs for a config.

    ``tp_rule`` is an optional ``(path, shape) -> PartitionSpec`` giving
    tensor-parallel sharding (from the model or the AutoTP sharder);
    zero sharding composes on top of it.
    """

    def __init__(self, mesh: Mesh, stage: int, tp_rule: Optional[Callable] = None,
                 param_persistence_threshold: int = 0, offload_optimizer: bool = False,
                 offload_param: bool = False, mics_shard_size: int = 0):
        self.mesh = mesh
        self.stage = stage
        self.tp_rule = tp_rule or (lambda path, shape: P())
        self.param_persistence_threshold = param_persistence_threshold
        self.offload_optimizer = offload_optimizer
        self.offload_param = offload_param
        self.mics_shard_size = int(mics_shard_size or 0)
        if self.mics_shard_size > 0:
            self._mics_axes = self._solve_mics_axes(self.mics_shard_size)

    def _solve_mics_axes(self, shard_size):
        """MiCS (reference runtime/zero/mics.py:64): ZeRO-3 partitions
        parameters within a SUB-GROUP of size ``mics_shard_size`` and
        replicates across groups, so the per-layer all-gather stays on
        fast links. On a named mesh the sub-group is a suffix of the
        zero axes (innermost = fastest ICI): pick the innermost zero
        axes whose sizes multiply to the shard size."""
        sizes = _axis_sizes(self.mesh)
        axes = []
        prod = 1
        for a in reversed(ZERO_AXES):  # innermost first
            if sizes.get(a, 1) == 1:
                continue
            if prod == shard_size:
                break
            axes.append(a)
            prod *= sizes[a]
        if prod != shard_size:
            zero_prod = int(np.prod([sizes.get(a, 1) for a in ZERO_AXES]))
            raise ValueError(
                f"mics_shard_size={shard_size} is not an innermost-axes factor of the "
                f"zero axes {ZERO_AXES} with sizes {[sizes.get(a, 1) for a in ZERO_AXES]} "
                f"(full zero world = {zero_prod})")
        return tuple(reversed(axes))

    def _zero_axes_for(self, path):
        return EXPERT_ZERO_AXES if is_expert_param(path) else ZERO_AXES

    def _param_zero_axes(self, path):
        full = self._zero_axes_for(path)
        if self.mics_shard_size > 0 and self.stage >= 3:
            # MiCS: param partitioning restricted to the sub-group; the
            # optimizer/grad sharding keeps the full zero axes (grads are
            # still reduced globally — the hierarchical-allreduce analogue)
            return tuple(a for a in full if a in self._mics_axes)
        return full

    def _base_spec(self, path, shape):
        spec = self.tp_rule(path, shape)
        if is_expert_param(path) and len(shape) >= 1 and "expert" not in _spec_used_axes(spec):
            # No explicit expert placement from the tp_rule: assume the
            # expert dim leads (standalone MOELayer params are (E, ...)).
            sizes = _axis_sizes(self.mesh)
            if sizes.get("expert", 1) > 1 and shape[0] % sizes["expert"] == 0:
                entries = list(spec) + [None] * (len(shape) - len(spec))
                if entries[0] is None:
                    entries[0] = "expert"
                spec = P(*entries)
        return spec

    def param_spec(self, path: str, shape) -> P:
        """Sharding of the compute-dtype parameters."""
        base = self._base_spec(path, shape)
        if self.stage < 3:
            return base
        if int(np.prod(shape)) < self.param_persistence_threshold:
            return base
        return shard_largest_free_dim(shape, base, self._param_zero_axes(path), self.mesh)

    def gathered_spec(self, path: str, shape) -> P:
        """Sharding of a parameter as its arithmetic reads it: the zero axes
        gathered, only the tensor/expert placement left."""
        return self._base_spec(path, shape)

    def zero_sharded(self, path: str, shape) -> bool:
        """Is this parameter split over a zero axis (so that using it takes
        an all-gather)?"""
        return (_spec_used_axes(self.param_spec(path, shape))
                != _spec_used_axes(self.gathered_spec(path, shape)))

    def opt_spec(self, path: str, shape) -> P:
        """Sharding of fp32 master params and optimizer moments."""
        base = self._base_spec(path, shape)
        if self.stage == 0:
            return base
        return shard_largest_free_dim(shape, base, self._zero_axes_for(path), self.mesh)

    def grad_spec(self, path: str, shape) -> P:
        """Layout gradients are constrained to as they are produced.

        Stage ≥2 shards grads like the optimizer state (reduce-scatter as
        early as possible); stage ≤1 keeps them replicated (all-reduce).
        """
        if self.stage >= 2:
            return self.opt_spec(path, shape)
        return self._base_spec(path, shape)

    # NamedSharding helpers -------------------------------------------------
    def _named(self, spec):
        return NamedSharding(self.mesh, spec)

    def tree_param_shardings(self, params):
        return path_tree_map(lambda path, x: self._named(self.param_spec(path, np.shape(x))), params)

    def tree_opt_shardings(self, params):
        return path_tree_map(lambda path, x: self._named(self.opt_spec(path, np.shape(x))), params)

    def tree_grad_shardings(self, params):
        return path_tree_map(lambda path, x: self._named(self.grad_spec(path, np.shape(x))), params)

    def tree_param_specs(self, params):
        return path_tree_map(lambda path, x: self.param_spec(path, np.shape(x)), params)

    def tree_opt_specs(self, params):
        return path_tree_map(lambda path, x: self.opt_spec(path, np.shape(x)), params)

    def tree_grad_specs(self, params):
        return path_tree_map(lambda path, x: self.grad_spec(path, np.shape(x)), params)


def path_tree_map(fn, tree, is_leaf=None):
    """tree_map passing a '/'-joined string path as first argument."""

    def keystr(kp):
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            elif hasattr(k, "name"):
                parts.append(str(k.name))
            else:
                parts.append(str(k))
        return "/".join(parts)

    return jax.tree_util.tree_map_with_path(lambda kp, x: fn(keystr(kp), x), tree,
                                            is_leaf=is_leaf)


def batch_spec(mesh: Mesh, extra_leading=0, shard_sequence=False):
    """PartitionSpec for a [batch, seq, ...] array: batch over data+expert,
    optionally sequence over the sequence axis (Ulysses input layout)."""
    sizes = _axis_sizes(mesh)
    b_axes = tuple(a for a in ("data", "expert") if sizes.get(a, 1) > 1)
    entries = [None] * extra_leading
    entries.append(b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None))
    if shard_sequence and sizes.get("sequence", 1) > 1:
        entries.append("sequence")
    return P(*entries)
