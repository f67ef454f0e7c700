"""``zero_optimization.overlap_comm`` at stage 3, as a program.

Capability match for the reference's prefetch coordinator and its
``overlap_comm`` (``partitioned_param_coordinator.py:62`` fetches the next
sub-modules' partitions on a side stream while the current one runs;
``stage3.py`` reduces a bucket of gradients beside the backward). Here no
hook issues a collective: the schedule is the shape of the program, and
what this module shapes is the **backward pass of a scan over ZeRO-3
layers**.

Left to itself, the compiler meets every matmul of a layer with its
weight still sharded and turns each into a ring of partial matmuls and
``collective-permute`` steps: once for the recomputation, once more for
the input's gradient, and a third ring that reduces the weight's
gradient. In the forward pass the rings hide (a v5e runs a layer's
matmuls at 96 % of its peak through them); in the backward pass they do
not: a step of a small ring ends before its transfer does, and the
device waits (7.6 % of a training step of ``mistral7b-zero3-x4``,
``PERF.md`` section 5).

:func:`overlapped_scan` is that scan with its backward written out as a
``custom_vjp``:

- the forward is the plain scan (the compiler's rings), and saves each
  layer's input and nothing else, which is what full rematerialisation
  saves;
- the backward **gathers a layer once**, as one collective a leaf (a
  sharding constraint onto the layout without the zero axes) that nothing
  in the iteration waits for until its first matmul, and both the
  recomputation and the differentiation read that one copy: whole
  matmuls on whole weights, half the backward's gather traffic, and a
  gather the compiler runs asynchronously beside the arithmetic of the
  layer's neighbours;
- a layer's gradients go to their sharded layout
  (:meth:`ZeroShardingPolicy.grad_spec`) as they are produced. A leaf of
  :data:`RING_REDUCE_MIN_BYTES` or more is left to the compiler's ring,
  whose steps are long enough to hide their transfers; a smaller one is
  first summed whole (an all-reduce the compiler runs asynchronously
  beside the rest of the layer's backward) and then cut.

A gathered layer is never a residual: the backward holds one layer's
gathered parameters and one layer's gradients, whatever
``stage3_prefetch_bucket_size`` and ``stage3_max_live_parameters`` say
(both are accepted and unread; the depth is one layer).

The engine turns it on around the trace of its gradient core
(:func:`overlapping`) when ``zero_optimization.overlap_comm`` is true at
stage 3; a model asks :func:`active` where it scans its layers in
training.
"""

import contextlib
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.zero.partitioning import path_tree_map

# A gradient leaf this large a layer is reduced by the compiler's ring of partial
# matmuls; a smaller one by one asynchronous all-reduce. Measured on a v5e 2x2 at
# 4096 tokens a chip (PERF.md section 6, PR 43): rings for every leaf 671.9 ms a
# step, none 677.8, for leaves over 16 MiB 672.9, over 64 MiB (the MLP's three
# 112 MiB matrices, not attention's 8 and 32 MiB ones) 664.1.
RING_REDUCE_MIN_BYTES = 64 * 2 ** 20

_ACTIVE = threading.local()


class LayerOverlap:
    """What a layer scan needs of the ZeRO policy to gather a layer whole,
    and the count of the layer gathers it did issue so."""

    def __init__(self, policy):
        self.policy = policy
        self.scans = {}  # a scan's path in the model -> whole-layer gathers a pass over it

    @property
    def n_layers_prefetched(self):
        return sum(self.scans.values())

    def layouts(self, prefix, stacked):
        """→ ``(gathered, reduced)``: per leaf of a ``[L, ...]`` stack of
        layers, the sharding of one layer with the zero axes gathered and
        the sharding its gradient is reduced into; ``None`` when no leaf
        of the stack is sharded over a zero axis (nothing to gather)."""
        policy = self.policy
        if not any(jax.tree.leaves(path_tree_map(
                lambda path, x: policy.zero_sharded(f"{prefix}/{path}", x.shape), stacked))):
            return None

        def of_a_layer(spec_of):
            return path_tree_map(
                lambda path, x: NamedSharding(policy.mesh, P(*tuple(spec_of(f"{prefix}/{path}", x.shape))[1:])),
                stacked)

        return of_a_layer(policy.gathered_spec), of_a_layer(policy.grad_spec)


@contextlib.contextmanager
def overlapping(overlap):
    """The engine's: layer scans traced inside take :func:`overlapped_scan`.
    ``None`` leaves every scan as it is."""
    prior = active()
    _ACTIVE.overlap = overlap
    try:
        yield overlap
    finally:
        _ACTIVE.overlap = prior


def active():
    """The :class:`LayerOverlap` of the trace in progress, or None."""
    return getattr(_ACTIVE, "overlap", None)


def _float0_like(tree):
    return jax.tree.map(lambda x: np.zeros(np.shape(x), jax.dtypes.float0), tree)


def overlapped_scan(layer, stacked, carry, consts, per_layer, gathered, reduced):
    """``carry`` folded through ``layer(params_i, carry, consts, per_layer_i)``
    over the L layers of ``stacked`` (leaves ``[L, ...]``, ZeRO-3 sharded).

    ``consts`` and ``per_layer`` (leaves ``[L, ...]``, or None) hold integers
    only (positions, random keys) and are not differentiated. ``gathered`` /
    ``reduced`` are :meth:`LayerOverlap.layouts`. Differentiable in ``stacked``
    and ``carry``; the backward recomputes each layer from its saved input.
    """
    order = jnp.arange(jax.tree.leaves(stacked)[0].shape[0])

    def shard_of(stacked, i):
        return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), stacked)

    def forward(stacked, carry, consts, per_layer):
        def body(carry, xs):
            i, extra = xs
            return layer(shard_of(stacked, i), carry, consts, extra), carry

        return jax.lax.scan(body, carry, (order, per_layer))

    @jax.custom_vjp
    def trunk(stacked, carry, consts, per_layer):
        return forward(stacked, carry, consts, per_layer)[0]

    def trunk_fwd(stacked, carry, consts, per_layer):
        out, inputs = forward(stacked, carry, consts, per_layer)
        return out, (stacked, inputs, consts, per_layer)

    def reduce(grad, whole, cut):
        if grad.size * grad.dtype.itemsize < RING_REDUCE_MIN_BYTES:
            grad = jax.lax.with_sharding_constraint(grad, whole)
        return jax.lax.with_sharding_constraint(grad, cut)

    def trunk_bwd(saved, ct):
        stacked, inputs, consts, per_layer = saved

        def body(ct, xs):
            i, carry, extra = xs
            params = jax.tree.map(jax.lax.with_sharding_constraint, shard_of(stacked, i), gathered)
            _, vjp = jax.vjp(lambda p, c: layer(p, c, consts, extra), params, carry)
            grads, ct = vjp(ct)
            return ct, jax.tree.map(reduce, grads, gathered, reduced)

        ct, grads = jax.lax.scan(body, ct, (order, inputs, per_layer), reverse=True)
        return grads, ct, _float0_like(consts), _float0_like(per_layer)

    trunk.defvjp(trunk_fwd, trunk_bwd)
    return trunk(stacked, carry, consts, per_layer)
