"""Grouped (per-expert) GEMM for MoE.

Capability match for the reference's grouped GEMM usage in MoE inference
kernels (``deepspeed/inference/v2/kernels/cutlass_ops/mixed_gemm`` /
``grouped_gemm``): tokens sorted by expert multiply each expert's weight
without materializing the [E, capacity, ...] dense dispatch tensor.
On TPU the grouped GEMM is the Pallas kernel of
``ops/pallas/grouped_matmul.py`` (row tiles fitted to the rows a group,
each expert's weights streamed once, a table of every layer's experts
read where it lies); ``jax.lax.ragged_dot`` is the same mathematics
wherever the kernel cannot run (:func:`_use_pallas_gmm`).

``moe_grouped_mlp`` is the drop-in computation for a top-1/top-k MoE
FFN over flat tokens; the capacity-based einsum dispatch in
``deepspeed_tpu/moe/sharded_moe.py`` remains the training path (its
fixed shapes compose with GSPMD's expert-parallel all-to-all), while
this grouped path serves inference and single-shard experts where
dropless exactness matters.

Every entry point also accepts grouped-layout ``QuantizedWeight``
expert stacks (the reference's ``mixed_gemm`` next to ``moe_gemm``):
on TPU the stacks feed the fused ``gmm_quant`` kernel, which
dequantizes each expert slab tile-by-tile in VMEM; off TPU the
identical-math fallbacks dequantize either the per-token GATHERED
slabs (decode-scale batches) or inside a frozen-base custom_vjp around
``lax.ragged_dot`` — in no fused path does a full-precision copy of an
expert weight stack materialize in HBM. ``DS_FUSED_GMM=0`` restores
dequantize-at-entry wholesale (the A/B baseline and escape hatch).
"""

import dataclasses
import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map


def fused_gmm_enabled():
    """DS_FUSED_GMM tri-state kill switch for the fused quantized
    grouped-GEMM paths: set wins in both directions (0 restores
    dequantize-at-entry everywhere, 1 forces the boxed dispatch), unset
    defaults to on."""
    from deepspeed_tpu.utils.env_registry import env_opt_bool
    v = env_opt_bool("DS_FUSED_GMM")
    return True if v is None else v


class GroupedGemmStats:
    """Trace-time dispatch telemetry for the grouped GEMM.

    Records which path each ``moe_grouped_mlp`` trace took
    (pallas/gathered/ragged, quantized or dense, and ``_table`` where
    the stacks were a table of groups indexed where it lies: see
    ``first_group``), and which layout a pass of the training exchange got
    (``pallas_exchange`` / ``ragged_exchange``: :func:`_pass_layout`), so
    bench lanes and the parity suite can assert the
    path they think they measured is the one that ran. Serving traces
    from gateway worker threads, so all counter access takes the lock.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = {}

    def count(self, path):
        with self._lock:
            self._counts[path] = self._counts.get(path, 0) + 1

    def snapshot(self):
        with self._lock:
            return dict(self._counts)

    def reset(self):
        with self._lock:
            self._counts.clear()


GMM_STATS = GroupedGemmStats()


def _is_quantized(w):
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    return isinstance(w, QuantizedWeight)


def _stack_dims(w):
    """(K, N) of a stacked [E, K, N] expert weight — dense array or
    grouped-layout QuantizedWeight (whose fp6 carriers pack N into 3/4
    bytes). Shapes derive from the CARRIERS, never stored metadata:
    per-layer slices of nn.scan-stacked leaves carry stale aux shapes."""
    if _is_quantized(w):
        n = w.values.shape[-1] * 4 // 3 if w.scheme == "fp6" else w.values.shape[-1]
        return w.values.shape[-2], n
    return w.shape[-2], w.shape[-1]


def _cast_stack(w, dtype):
    return w if _is_quantized(w) else w.astype(dtype)


def _unbox_stack(w, dtype):
    if not _is_quantized(w):
        return w.astype(dtype)
    from deepspeed_tpu.ops.pallas.fused_quant_matmul import dequantize_grouped
    return dequantize_grouped(w.values, w.scales, w.scheme, dtype)


def grouped_gemm(tokens, expert_weights, group_sizes, preferred_element_type=jnp.float32):
    """tokens: [T, D] sorted by expert; expert_weights: [E, D, F];
    group_sizes: [E] with sum == T → [T, F]."""
    return jax.lax.ragged_dot(tokens, expert_weights, group_sizes.astype(jnp.int32),
                              preferred_element_type=preferred_element_type)


def _ragged_qdot_impl(tokens, values, scales, group_sizes, scheme,
                      dequant_dtype):
    from deepspeed_tpu.ops.pallas.fused_quant_matmul import dequantize_grouped
    w = dequantize_grouped(values, scales, scheme, dequant_dtype)
    return jax.lax.ragged_dot(tokens, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ragged_qdot(tokens, values, scales, group_sizes, scheme, dequant_dtype):
    """ragged_dot over grouped-layout carriers. The forward is literally
    unbox-then-ragged_dot (same ops, same order — bit-identical to the
    pre-fused path), wrapped so the backward keeps the quantized base
    frozen: integer carriers get float0 cotangents and dx dequantizes a
    backward-only transient against the transposed stack."""
    return _ragged_qdot_impl(tokens, values, scales, group_sizes, scheme,
                             dequant_dtype)


def _ragged_qdot_fwd(tokens, values, scales, group_sizes, scheme,
                     dequant_dtype):
    y = _ragged_qdot_impl(tokens, values, scales, group_sizes, scheme,
                          dequant_dtype)
    # residuals must be JAX types: carry tokens' dtype as a 0-size array
    return y, (values, scales, group_sizes, jnp.zeros((0,), tokens.dtype))


def _ragged_qdot_bwd(scheme, dequant_dtype, res, dy):
    values, scales, group_sizes, x_proto = res
    from deepspeed_tpu.ops.pallas.fused_quant_matmul import (
        _zero_carrier_cotangent, dequantize_grouped)
    w = dequantize_grouped(values, scales, scheme, jnp.float32)
    dx = jax.lax.ragged_dot(
        dy.astype(jnp.float32), w.swapaxes(1, 2),
        group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32).astype(x_proto.dtype)
    return dx, _zero_carrier_cotangent(values), jnp.zeros_like(scales), None


_ragged_qdot.defvjp(_ragged_qdot_fwd, _ragged_qdot_bwd)


def grouped_gemm_any(tokens, w, group_sizes):
    """:func:`grouped_gemm` over a dense [E, D, F] stack or a
    grouped-layout ``QuantizedWeight`` stack (dequantized to
    ``tokens.dtype``, matching what dequantize-at-entry produced)."""
    if _is_quantized(w):
        return _ragged_qdot(tokens, w.values, w.scales, group_sizes, w.scheme,
                            jnp.dtype(tokens.dtype))
    return grouped_gemm(tokens, w.astype(tokens.dtype), group_sizes)


def sort_by_expert(x, expert_idx, num_experts):
    """→ (x_sorted [T, D], group_sizes [E], unsort_idx [T]): contiguous
    per-expert grouping of a flat token batch."""
    order = jnp.argsort(expert_idx, stable=True)
    x_sorted = jnp.take(x, order, axis=0)
    group_sizes = jnp.bincount(expert_idx, length=num_experts)
    unsort = jnp.argsort(order, stable=True)
    return x_sorted, group_sizes, unsort


_QUANT_TILE_M = 256  # gmm_quant's row tile at training sizes (no chip has run it: ROADMAP S6)


# Tests set this to run the Pallas branch in interpret mode on CPU.
FORCE_INTERPRET = False


def _use_pallas_gmm(num_rows, num_experts, d_model, d_ff, dtype, quantized=False):
    """Whether the Pallas grouped matmul (``ops/pallas/grouped_matmul.py``)
    runs the three expert GEMMs: on TPU, wherever its tiles are legal.
    Measured against ``ragged_dot`` a call at the four shapes the
    benchmark's cells serve and both directions of the FFN (PERF.md,
    PR 31: ``tools/kernel_census.py``), it streams the expert weights at
    a larger share of the HBM roofline at every one, so ``ragged_dot``
    stays for what the kernel cannot take: no TPU (CPU tests, unless
    FORCE_INTERPRET runs the branch in interpret mode), widths that are
    not lane-aligned, an expert matrix of which not even 128 columns fit
    a weight block, and fewer rows than experts (the gathered path's).

    Both contraction widths must be lane-aligned: the kernel tiles N in
    128-wide lanes, and the gate/up GEMMs have N = d_ff while the down
    GEMM has N = d_model — a 128-aligned d_model with an unaligned d_ff
    (e.g. a debug preset with d_ff=344) would mosaic-fail inside the
    kernel, so gate on both and let ragged_dot take those shapes.

    QUANTIZED stacks take the kernel at any row count: ``gmm_quant`` is
    bandwidth-bound on carrier bytes while every alternative first
    materializes dequantized expert slabs."""
    if FORCE_INTERPRET:
        return True
    if jax.devices()[0].platform != "tpu":
        return False
    if d_model % 128 or d_ff % 128:
        return False
    if quantized:
        return True
    from deepspeed_tpu.ops.pallas.grouped_matmul import col_tile
    itemsize = jnp.dtype(dtype).itemsize
    return (num_rows >= num_experts and col_tile(d_model, d_ff, itemsize) is not None
            and col_tile(d_ff, d_model, itemsize) is not None)


def _gathered_moe_mlp(x, expert_idx, w_gate, w_up, w_down, activation):
    """Decode-scale dispatch (rows < experts): gather each row's expert
    slab and contract per row. With quantized stacks the gather happens
    on the CARRIERS, so only the T selected slabs are ever dequantized —
    the non-Pallas analogue of the fused kernel's no-full-stack
    contract. Gather and grouped dequant commute elementwise, so this
    is bit-identical to dequantize-then-gather; and at tiny T the
    weight traffic is T slabs instead of all E, which is where the
    fused path's CPU/debug speedup comes from."""
    from jax.ad_checkpoint import checkpoint_name

    def take(w):
        if _is_quantized(w):
            from deepspeed_tpu.ops.pallas.fused_quant_matmul import \
                dequantize_grouped
            return dequantize_grouped(jnp.take(w.values, expert_idx, axis=0),
                                      jnp.take(w.scales, expert_idx, axis=0),
                                      w.scheme, x.dtype)
        return jnp.take(w, expert_idx, axis=0).astype(x.dtype)

    gate = checkpoint_name(
        jnp.einsum("td,tdf->tf", x, take(w_gate),
                   preferred_element_type=jnp.float32).astype(x.dtype),
        "moe_gate")
    if w_up is None:
        inter = activation(gate)
    else:
        up = checkpoint_name(
            jnp.einsum("td,tdf->tf", x, take(w_up),
                       preferred_element_type=jnp.float32).astype(x.dtype),
            "moe_up")
        inter = activation(gate) * up
    return jnp.einsum("tf,tfd->td", inter, take(w_down),
                      preferred_element_type=jnp.float32).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _tile_routing(expert_idx, num_experts, tm, live=None):
    """expert_idx [M] → (each row's slot in the tile-aligned layout [M],
    the owning expert per row tile, the tiles the groups fill):
    :func:`tile_layout` on the groups' sizes, and a row's slot is its
    group's first padded row plus its rank in the group (its running
    count down the one-hot's column). Jitted, so programs that serve the
    same number of rows share its trace. ``live`` [M] bool (None: every
    row): the rows that belong to a group; the others count in no group
    and get slots past the layout's last row, each its own."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import tile_layout
    oh = expert_idx[:, None] == jnp.arange(num_experts, dtype=expert_idx.dtype)[None, :]
    if live is not None:
        oh = oh & live[:, None]
    oh = oh.astype(jnp.int32)
    ranks = jnp.cumsum(oh, axis=0)
    padded_starts, te, Mp, num_tiles = tile_layout(ranks[-1], expert_idx.shape[0], tm)
    pdst = jnp.sum(oh * (padded_starts[None, :] + ranks - 1), axis=1)
    if live is not None:
        pdst = jnp.where(live, pdst, Mp + jnp.arange(expert_idx.shape[0], dtype=pdst.dtype))
    return pdst.astype(jnp.int32), te, num_tiles


def _gmm_dispatch(xp, w, te, tm, interp, first_group=None, num_tiles=None):
    """One grouped GEMM on the tile-aligned layout: dense stacks hit
    :func:`gmm` (which reads a table of groups from ``first_group`` and
    skips the layout's tiles past ``num_tiles``), quantized stacks the
    fused :func:`gmm_quant` (dequant target = the activation dtype,
    matching dequantize-at-entry)."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm, gmm_quant
    if _is_quantized(w):
        return gmm_quant(xp, w.values, w.scales, te, w.scheme,
                         jnp.dtype(xp.dtype), tm, 512, 256, interp)
    return gmm(xp, w, te, tm, interp, first_group=first_group, num_tiles=num_tiles)


def moe_grouped_mlp(x, expert_idx, w_gate, w_up, w_down, num_experts, activation=jax.nn.silu,
                    first_group=None, live=None, rows_a_group=None):
    """Dropless top-1 MoE FFN: x [T, D]; expert_idx [T]; weights
    [E, D, F] / [E, D, F] / [E, F, D] → [T, D]. Every token reaches its
    expert (no capacity drops — the grouped-GEMM advantage). Each
    weight may be a dense stack or a grouped-layout ``QuantizedWeight``
    stack (see module docstring). ``w_up=None``: an **ungated** expert of
    two matrices, ``activation(x w_gate) w_down``, through the same
    dispatches with one grouped GEMM fewer.

    On TPU the three GEMMs run in the Pallas grouped matmul
    (``ops/pallas/grouped_matmul.py``) over a tile-aligned padded row
    layout whose row tile is fitted to the rows a group
    (:func:`_use_pallas_gmm` says where); elsewhere ``lax.ragged_dot`` is
    the dispatch, except at decode scale (rows < experts) where the
    gathered per-row contraction is both faster and — for quantized
    stacks — the path that never dequantizes more than the selected
    slabs. The sorted rows and gate/up activations carry
    ``checkpoint_name`` tags: under the ``remat_policy="moe"`` training
    policy exactly these are saved, which is the full residual set the
    backward needs to skip re-running all three grouped GEMMs (``inter``
    rebuilds elementwise from gate/up; the down GEMM's forward is dead
    code in the rebuild).

    ``first_group`` (a traced scalar; None = the stacks are this call's
    ``num_experts``): the stacks are a table of ``G >= num_experts``
    groups — every layer's experts, say — of which this call's are
    ``first_group .. first_group + num_experts``. The dispatch is chosen
    on ``num_experts`` as without a table, and every dense dispatch
    indexes the table where it lies (a layer's experts cut out of a
    stack would be copied first, every call): the Pallas kernel adds
    ``first_group`` in its weight index map, ``ragged_dot`` and the
    gathered contraction see the other groups empty. ``GMM_STATS`` counts
    these as ``pallas_table`` / ``ragged_table`` / ``gathered_table``.
    Only the fused quantized kernel still gets the call's carriers cut
    out.

    ``live`` [T] bool (None: every row): the rows whose expert is among
    this call's ``num_experts`` — an expert-parallel share's held picks
    (:class:`ExpertShare`). The other rows lie **outside every group**:
    no matmul tile runs for them, no expert's weights are read for them,
    and their result rows are zero; their ``expert_idx`` is not looked at.
    ``rows_a_group``: the rows a group expects (the row tile is fitted to
    it; None: ``T / num_experts``). Dense stacks only; ``GMM_STATS``
    counts these with ``_share``."""
    from jax.ad_checkpoint import checkpoint_name
    stacks = tuple(w for w in (w_gate, w_up, w_down) if w is not None)
    quantized = any(_is_quantized(w) for w in stacks)
    if live is not None and quantized:
        raise NotImplementedError("an expert share (live rows) over quantized expert stacks")
    if quantized and not fused_gmm_enabled():
        # DS_FUSED_GMM=0: restore dequantize-then-dispatch wholesale
        w_gate, w_up, w_down = (w if w is None else _unbox_stack(w, x.dtype)
                                for w in (w_gate, w_up, w_down))
        quantized = False
    d_ff = _stack_dims(w_gate)[1]
    use_pallas = _use_pallas_gmm(x.shape[0], num_experts, x.shape[1], d_ff, x.dtype,
                                 quantized=quantized)
    if use_pallas and quantized:
        from deepspeed_tpu.ops.pallas.grouped_matmul import gmm_quant_supported
        use_pallas = all(
            not _is_quantized(w)
            or gmm_quant_supported(w.values, w.scales, w.scheme)
            for w in stacks)
    groups, table = num_experts, ""
    if first_group is not None and use_pallas and quantized:
        w_gate, w_up, w_down = (
            jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, first_group, num_experts), w)
            for w in (w_gate, w_up, w_down))        # (None, an ungated expert's, has no leaves)
        first_group = None
    elif first_group is not None:
        table = "_table"
        if not use_pallas:
            expert_idx = expert_idx + first_group
            groups = jax.tree.leaves(w_gate)[0].shape[0]
    if live is not None:
        table += "_share"
    if use_pallas:
        GMM_STATS.count(("pallas_quant" if quantized else "pallas") + table)
        from deepspeed_tpu.ops.pallas.grouped_matmul import row_tile
        if not quantized:
            tm = row_tile(x.shape[0] if rows_a_group is None else rows_a_group * num_experts,
                          num_experts, x.dtype)
        elif FORCE_INTERPRET:
            tm = min(_QUANT_TILE_M, max(8, x.shape[0] // 8))
        elif x.shape[0] < 8 * _QUANT_TILE_M:
            # decode scale: ~one row tile per routed expert keeps the
            # kernel bound on carrier bytes instead of pad compute
            tm = max(16, -(-x.shape[0] // 8) * 8)
        else:
            tm = _QUANT_TILE_M
        # Rank-based routing — no argsort: each row's slot within its
        # expert's padded tile range is its running count (one-hot
        # cumsum, O(M*E) elementwise — E is small). One scatter builds
        # the tile-aligned layout and one gather undoes it. Tagged so
        # the "moe" remat policy saves the routing instead of
        # recomputing it in the backward.
        pdst, te, num_tiles = _tile_routing(expert_idx, num_experts, tm, live)
        pdst = checkpoint_name(pdst, "moe_routing")
        te = checkpoint_name(te, "moe_tiles")
        # rows land in distinct padded slots: the uniqueness hint keeps
        # XLA's scatter (and its gather/scatter-add transposes) parallel.
        # (A gather-based pack via a slot→row map was measured and is
        # slower — the transposed scatter-add in backward gives the
        # saving back with interest.)
        # (a row outside every group has a slot past the layout: the scatter drops it,
        # and the gather below fills its result row with zeros)
        xp = jnp.zeros((te.shape[0] * tm, x.shape[1]), x.dtype).at[pdst].set(
            x, unique_indices=True)
        xp = checkpoint_name(xp, "moe_xs")
        def matmul(rows, w):
            return _gmm_dispatch(rows, w, te, tm, FORCE_INTERPRET, first_group, num_tiles)

        gate = checkpoint_name(matmul(xp, w_gate), "moe_gate")
        if w_up is None:
            inter = activation(gate)
        else:
            up = checkpoint_name(matmul(xp, w_up), "moe_up")
            inter = activation(gate) * up
        return jnp.take(matmul(inter, w_down), pdst, axis=0, unique_indices=True,
                        fill_value=None if live is None else 0)
    if live is not None:
        # outside every group: past the last group in the sort, in no group's size, and
        # ragged_dot leaves the rows past its groups zero
        expert_idx = jnp.where(live, expert_idx, groups)
    elif x.shape[0] < num_experts:
        GMM_STATS.count(("gathered_quant" if quantized else "gathered") + table)
        return _gathered_moe_mlp(x, expert_idx, w_gate, w_up, w_down,
                                 activation)
    GMM_STATS.count(("ragged_quant" if quantized else "ragged") + table)
    xs, sizes, unsort = sort_by_expert(x, expert_idx, groups)
    xs = checkpoint_name(xs, "moe_xs")
    gate = checkpoint_name(grouped_gemm_any(xs, w_gate, sizes).astype(x.dtype), "moe_gate")
    if w_up is None:
        inter = activation(gate)
    else:
        up = checkpoint_name(grouped_gemm_any(xs, w_up, sizes).astype(x.dtype), "moe_up")
        inter = activation(gate) * up
    out = grouped_gemm_any(inter, w_down, sizes).astype(x.dtype)
    return jnp.take(out, unsort, axis=0)


def _split_stack(w):
    """QuantizedWeight stack → its carrier leaves + a rebuild tag; dense
    stack → a 1-tuple. shard_map broadcasts ONE PartitionSpec over every
    pytree leaf of an operand, and carrier values/scales need different
    specs — so stacks cross the shard_map boundary destructured."""
    if _is_quantized(w):
        return (w.values, w.scales), ("q", w.scheme, w.dequant_dtype)
    return (w,), ("d",)


def _join_stacks(flat, tags):
    """Inverse of :func:`_split_stack` over the flattened operand list —
    rebuilds each QuantizedWeight from its (now shard-local) carriers,
    deriving the logical shape from the carrier shapes (the pre-split
    aux shape would be wrong for an E/ep, feature-sharded slice)."""
    from deepspeed_tpu.inference.quantization import QuantizedWeight
    out, i = [], 0
    for tag in tags:
        if tag[0] == "q":
            v, s = flat[i], flat[i + 1]
            i += 2
            n = v.shape[-1] * 4 // 3 if tag[1] == "fp6" else v.shape[-1]
            out.append(QuantizedWeight(v, s, v.shape[:-1] + (n,), tag[1],
                                       layout="grouped", dequant_dtype=tag[2]))
        else:
            out.append(flat[i])
            i += 1
    return out


@dataclasses.dataclass(frozen=True)
class ExpertShare:
    """Which of a router's columns this process computes: ``held`` routed
    experts from ``first`` (the stacks given to :func:`dropless_moe_ffn`
    are these, in order), out of ``routed``; and, for a router that has
    them (LongCat's; ``zero`` is 0 for every other), ``zero`` zero-compute
    columns after the routed ones, whose expert is the identity. One rank
    of an expert-parallel deployment holds ``routed / ranks`` experts and
    computes the identity part, where there is one, of the tokens that
    live on it."""
    first: int
    held: int
    routed: int
    zero: int = 0

    def parts(self, topk_idx):
        """→ (the picks whose expert is held, the zero-compute picks), bool
        as ``topk_idx``; the rest belong to experts held elsewhere."""
        return ((topk_idx >= self.first) & (topk_idx < self.first + self.held),
                topk_idx >= self.routed)


def share_pass_rows(T, k, share, dtype, multiple=2):
    """The static number of rows one pass of :func:`expert_share_ffn` lays
    out: ``multiple`` times the held picks a step of ``T`` tokens expects if
    the router spreads its ``k`` picks evenly, ``T k held / (routed + zero)``,
    rounded up to the row tile and at least a row a held expert - twice the
    mean, :func:`row_tile`'s rule for a group, holds nearly every step in
    one pass (PERF.md, PR 53). From the call's shapes and the share alone."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import row_tile
    columns = share.routed + share.zero
    tm = row_tile(max(1, T * k // columns) * share.held, share.held, dtype)
    expected = -(-T * k * share.held // columns)
    return -(-max(multiple * expected, share.held) // tm) * tm


def expert_share_ffn(x, topk_idx, topk_vals, w1, w3, w2, share, first_group=None,
                     activation=jax.nn.silu, pass_rows=None):
    """:func:`dropless_moe_ffn` behind an :class:`ExpertShare` (its ``share``
    branch; one device) → (``[T, D]``, the passes it ran, int32).

    **Only the held picks are laid out.** Their flat indices ``t k + j`` are
    compacted in order (a token's picks adjacent, tokens ascending) and go
    through :func:`moe_grouped_mlp` ``cap`` = :func:`share_pass_rows` rows at
    a pass: the pass's token rows gathered from ``x`` (``[cap, D]``), a
    layout of ``cap + held x tile`` rows, and ``weight x result`` summed into
    ``[T, D]`` by token in float32. The passes are one loop whose trip count,
    ``ceil(held picks / cap)``, is read on the device: one in nearly every
    step, none where no pick is held, ``T k / cap`` where every pick of the
    router is held here - no pick is dropped at any load. Nothing of ``T k``
    rows by the model's width is built.

    Where ``cap >= T k`` statically - the share that holds every column,
    which says only that a pick of -1 is no row (``lfm2-24b-rag``'s), or a
    step of few tokens over a large share - the picks are laid out as
    without a share, each a row, the ones not held outside every group: one
    pass, the program those kinds lowered before PR 53. ``pass_rows``
    overrides ``cap`` (``tools/kernel_census.py --share``'s sweep alone)."""
    T, k = topk_idx.shape
    held, zero = share.parts(topk_idx)
    w_zero = None
    if share.zero:
        with jax.named_scope("ds.moe_zero"):
            w_zero = jnp.sum(jnp.where(zero, topk_vals, 0), axis=-1, keepdims=True)
    live, rows_a_group = held.reshape(-1), max(1, T * k // (share.routed + share.zero))
    topk_idx, topk_vals = topk_idx - share.first, jnp.where(held, topk_vals, 0)
    idx_rep = topk_idx.reshape(-1)  # [T*k]
    if not fused_gmm_enabled():
        w1, w3, w2 = (w if w is None else _unbox_stack(w, x.dtype) for w in (w1, w3, w2))

    def tail(rows, idx, live):
        return moe_grouped_mlp(rows, idx, _cast_stack(w1, x.dtype),
                               None if w3 is None else _cast_stack(w3, x.dtype),
                               _cast_stack(w2, x.dtype), num_experts=share.held,
                               activation=activation, first_group=first_group,
                               live=live, rows_a_group=rows_a_group)

    cap = share_pass_rows(T, k, share, x.dtype) if pass_rows is None else pass_rows
    if cap >= T * k:
        # a pick that is not held is a row outside every group, weighted zero
        out_rep = tail(jnp.repeat(x, k, axis=0), idx_rep, live)
        out = jnp.einsum("tk,tkd->td", topk_vals.astype(x.dtype), out_rep.reshape(T, k, -1))
        passes = 1
    else:
        out, passes = _held_picks_in_passes(x, idx_rep, topk_vals, live, cap, tail)
    if w_zero is not None:
        with jax.named_scope("ds.moe_zero"):
            out = out + w_zero.astype(x.dtype) * x
    return out, passes


def _held_picks_in_passes(x, experts, vals, live, cap, tail):
    """The held picks (``live`` [T k] bool, over the picks' ``experts``
    [T k] and ``vals`` [T, k]) through ``tail(rows [cap, D], experts [cap],
    live [cap]) -> [cap, D]``, ``cap`` at a pass → (``sum_j w_j tail_j``
    [T, D] in ``x.dtype``, the passes)."""
    T, k = vals.shape
    n = T * k
    at = jnp.arange(n, dtype=jnp.int32)
    n_held = jnp.sum(live, dtype=jnp.int32)
    # held picks first, in their own order; what follows them is never live
    order, experts, weights = jax.lax.sort(
        (jnp.where(live, at, n + at), experts, vals.reshape(-1)), num_keys=1)
    room = -(-n // cap) * cap - n       # the last pass's slice stays inside
    tokens, experts, weights = (jnp.pad(a, (0, room)) for a in (order // k, experts, weights))
    passes = (n_held + cap - 1) // cap

    def one_pass(p, out):
        here = p * cap + jnp.arange(cap, dtype=jnp.int32) < n_held
        tok, e, w = (jax.lax.dynamic_slice_in_dim(a, p * cap, cap)
                     for a in (tokens, experts, weights))
        tok = jnp.where(here, tok, T)   # past the last token: its row is summed nowhere
        y = tail(jnp.take(x, tok, axis=0, mode="clip"), e, here)
        # by token on the matrix unit: [T, cap] of the picks' weights, a row's at its token
        # (a sorted segment_sum, a scatter-add, read 8-14x this product's time on v5e: PERF.md,
        # PR 53). The product multiplies every token by every row, so a row that is not
        # finite is taken out and its token alone made NaN: a pick never reaches another's
        fine = jnp.all(jnp.isfinite(y), axis=1)
        mine = tok[None, :] == jnp.arange(T, dtype=jnp.int32)[:, None]
        out = out + jnp.dot(jnp.where(mine, w.astype(x.dtype)[None, :], 0),
                            jnp.where(fine[:, None], y, 0), preferred_element_type=jnp.float32)
        return jnp.where(jnp.any(mine & ~fine[None, :], axis=1)[:, None], jnp.nan, out)

    out = jax.lax.fori_loop(0, passes, one_pass, jnp.zeros((T, x.shape[1]), jnp.float32))
    return out.astype(x.dtype), passes


# The rows of one pass of a rank's layout over the held picks an even router gives it
# (``T k held / routed``): half as much again. A router that sends a rank more takes more
# passes - each of this static size, their number read on the device - and never loses a
# pick, but a pass of 81,920 rows costs a layer ~100 ms on a v5e whatever it holds, so the
# margin clears what seeded routers do: at a random initialisation a step of 32768 tokens'
# 8 picks reads 1.11-1.27 of the even share on the fullest rank of four (nine seeds), after
# one Adam step at 3e-4 without warm-up 1.30; at 1.25 a second pass ran in 0-45 of a
# window's ~55 steps by the seed and the rate spread by 7 % (PERF.md, PR 58).
MESH_SHARE_MARGIN = 1.5
# a step of so few picks over the axis (a debug size: eight experts spread a hundred tokens
# far less evenly than the margin) is laid out whole in one pass
MESH_SHARE_SMALL = 8192


def mesh_share_rows(T, k, share, ranks, dtype):
    """The rows one pass of a rank of :func:`expert_share_exchange_ffn` lays
    out for the picks its ``share.held / ranks`` experts hold of the
    axis's ``T`` tokens: :data:`MESH_SHARE_MARGIN` times the even router's
    ``T k held / (ranks columns)``, rounded up to the row tile, at most every
    pick - and every pick where there are :data:`MESH_SHARE_SMALL` or fewer."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import row_tile
    held, columns = share.held // ranks, share.routed + share.zero
    tm = row_tile(max(1, T * k // columns) * held, held, dtype)
    want = T * k if T * k <= MESH_SHARE_SMALL else int(-(-T * k * held * MESH_SHARE_MARGIN // columns))
    return -(-min(max(want, held), T * k) // tm) * tm


def mesh_share_axes(mesh):
    """The mesh axes a step's tokens are split over for
    :func:`expert_share_exchange_ffn` (``data`` and ``expert``, those larger
    than 1), or None where that dispatch does not apply: no expert axis, or a
    ``tensor`` / ``sequence`` / ``pipe`` axis beside it (the masked dispatch's)."""
    if mesh is None or mesh.shape.get("expert", 1) <= 1:
        return None
    if any(mesh.shape.get(a, 1) > 1 for a in ("tensor", "sequence", "pipe")):
        return None
    return tuple(a for a in ("data", "expert") if mesh.shape.get(a, 1) > 1)


def expert_share_exchange_ffn(x, topk_idx, topk_vals, w1, w3, w2, share, mesh,
                              activation=jax.nn.silu):
    """:func:`dropless_moe_ffn` over an expert axis: every rank computes its
    own share and the ranks exchange tokens on either side of it → (``[T,
    D]``, counts ``[copies over data, ranks, 3]`` int32: a rank's held picks,
    the passes they took and the rows its two row kernels copied).
    Differentiable in ``x``, ``topk_vals`` and the stacks.

    ``x`` [T, D] is split by token over ``data`` and ``expert``; the stacks
    ``[share.held, ...]`` by expert over ``expert``. A rank (a) gathers the
    rows, picks and weights of its expert axis's tokens in the compute dtype
    (``T_a = T / data`` rows), (b) lists **the picks its own experts hold**,
    in order, :func:`mesh_share_rows` at a pass - a static size,
    :data:`MESH_SHARE_MARGIN` over an even router's share: one pass then,
    ``ceil(held picks / rows)`` whatever the router does, so **no pick is
    ever dropped** - and gives each its slot of the grouped matmul's
    tile-aligned layout (:func:`_pass_layout`: integers alone), (c) copies
    each held pick's row **once** from its token's row into its slot
    (``ops/pallas/moe_rows.gather_rows``: a kernel that copies whole rows by
    DMA; a tile's padding is written as zeros and read from nowhere), (d)
    runs the three grouped matmuls over the layout, (e) reads the down
    product's row **once** from its slot into its token's weighted sum,
    ``[T_a, D]`` float32 (``gather_sum_rows``: the same kernel transposed;
    a token's picks that are not held here are skipped), and (f)
    reduce-scatters that over the axis, in the compute dtype, onto the rank
    that owns each token. Nothing of ``T k`` rows by the model's width is
    built, summed or sent, and no array in pick order exists.

    **The backward** is the same exchange transposed (a gather of the
    output's cotangent, a reduce-scatter of the rows'), and between them the
    passes again: the number of passes is read on the device, so the loop is
    no ``scan`` to transpose - :func:`_share_passes` is a ``custom_vjp`` whose
    backward is **written out** (:func:`_share_passes_bwd`), not derived. A
    pass makes ``xp``, ``g | u = xp (w1 | w3)`` and ``h = act(g) u`` again (it
    keeps no residual but its inputs: under any ``remat_policy`` the expert
    layer's forward runs once in the forward and once in the backward) but
    **not** ``y = h w2``: the cotangent's rows are copied into the layout
    unweighted (``G``), ``dhu = G w2^T``, and a pick's weight ``v`` - which
    crosses the linear product - is applied, and differentiated, in the hidden
    width: ``dv = <h, dhu>``, ``dh = v dhu`` (pulled through ``activation``'s
    own ``jax.vjp``), ``dw2 = (v h)^T G``; then ``dw1 | dw3 = xp^T (dg | du)``
    and the rows' ``(dg | du) (w1 | w3)^T`` summed by token: three grouped
    products and two calls for the stacks' gradients a pass, where the derived
    transpose ran six and three. **The first pass is the result**, forward and
    backward: the sum by token and every gradient are written by it into
    arrays nobody filled (``jax.lax.empty``), and a later pass adds to them
    inside the kernels that write them (``gather_sum_rows_onto``,
    ``gmm_dw_onto``: the accumulator is an aliased input the first pass does
    not read), so the common step - one pass a layer - writes no zeros and
    adds to none; a rank that holds no pick runs one pass over an empty
    layout, which writes zeros.

    An all-to-all of the held picks would move 0.92 of what the gather moves
    at 8 picks over 4 ranks (a token misses a rank with probability 0.085) and
    needs a listing by destination on the sender beside this one: the gather
    is taken."""
    from jax.sharding import PartitionSpec as P
    T, k = topk_idx.shape
    axes = mesh_share_axes(mesh)
    ep = mesh.shape["expert"]
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    Ta = T // n_shards * ep
    held = share.held // ep
    cap = mesh_share_rows(Ta, k, share, ep, x.dtype)
    rows_a_group = max(1, Ta * k // (share.routed + share.zero))
    dtype = x.dtype

    def body(x_l, idx_l, val_l, w1s, w3s, w2s):
        with jax.named_scope("ds.moe_exchange"):
            x_all, idx_all, val_all = (jax.lax.all_gather(a, "expert", axis=0, tiled=True)
                                       for a in (x_l, idx_l, val_l))
        first = share.first + jax.lax.axis_index("expert") * held
        with jax.named_scope("ds.moe_experts"):
            out, n_held, n_copied = _share_passes(
                x_all, val_all.reshape(-1), w1s.astype(dtype), w3s.astype(dtype),
                w2s.astype(dtype), idx_all.reshape(-1) - first, k, held, cap, rows_a_group,
                activation)
        with jax.named_scope("ds.moe_exchange"):
            out_l = jax.lax.psum_scatter(out.astype(_exchange_dtype(dtype, mesh)), "expert",
                                         scatter_dimension=0, tiled=True).astype(dtype)
        return out_l, jnp.stack([n_held, (n_held + cap - 1) // cap, n_copied])[None]

    tokens = P(axes if len(axes) > 1 else axes[0])
    stacks = P("expert")
    every = P(tuple(mesh.axis_names))
    out, counts = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(tokens, tokens, tokens, stacks, stacks, stacks),
        out_specs=(tokens, every), check_vma=False))(x, topk_idx, topk_vals, w1, w3, w2)
    # one row a device; the ranks of one expert axis differ, its copies over data do not
    return out, counts.reshape(-1, ep, 3)


def _use_pallas_rows(width, dtype):
    """Whether the two row kernels (``ops/pallas/moe_rows.py``) move a pass's
    rows: on TPU (or interpreted, under FORCE_INTERPRET) wherever they can
    address a row; elsewhere their ``jnp`` forms do."""
    from deepspeed_tpu.ops.pallas.moe_rows import rows_kernel_supported
    if not rows_kernel_supported(width, dtype):
        return False
    return FORCE_INTERPRET or jax.devices()[0].platform == "tpu"


class _PassProducts:
    """The grouped products over one pass's layout (:func:`_pass_layout`):
    ``matmul(rows [S, K], w [held, K, N])``, ``matmul_t(rows [S, N], w)`` -
    the same against the transposed stack - and ``dw_onto(acc, fresh, x [S,
    K], dy [S, N])`` - the stack's gradient ``x^T dy`` a group, float32,
    written onto ``acc`` [held, K, N] (``fresh`` [held] bool: the experts whose
    block of ``acc`` holds nothing yet and is not read; another's is added to)
    → (that, ``named`` [held] bool: the experts whose blocks it wrote;
    another's keeps what ``acc`` held)."""

    def __init__(self, matmul, dw_onto):
        self.matmul, self.dw_onto = matmul, dw_onto

    def matmul_t(self, rows, w):
        return self.matmul(rows, w.swapaxes(1, 2))


def _pass_layout(experts, here, picks, n_tokens, k, held, rows_a_group, d_model, d_ff, dtype):
    """Where a pass's picks lie, as integers alone → (``slot_token`` [S]:
    the token whose row each slot of the layout gets, ``n_tokens`` where it
    gets none - a tile's padding, a pick that is no pick -; ``slots``
    [n_tokens, k]: the slot of each token's ``j``-th pick, ``S`` where this
    pass does not hold it; ``slot_pick`` [S]: the flat index ``t k + j`` of
    each slot's pick, ``n_tokens k`` where it has none; the grouped products
    over that layout, :class:`_PassProducts`). ``experts`` / ``here`` /
    ``picks`` [cap]: each listed pick's expert, whether it is one, and its
    flat index ``t k + j``.

    On TPU the layout is the Pallas grouped matmul's - every expert's rows
    padded to whole row tiles (:func:`_tile_routing`), the tiles past the
    groups' skipped -; elsewhere the picks sorted by expert, for
    ``lax.ragged_dot``."""
    cap = experts.shape[0]
    if _use_pallas_gmm(cap, held, d_model, d_ff, dtype):
        from deepspeed_tpu.ops.pallas.grouped_matmul import dw_tiles, gmm_dw_onto, row_tile
        GMM_STATS.count("pallas_exchange")
        tm = row_tile(rows_a_group * held, held, dtype)
        slot, te, num_tiles = _tile_routing(experts, held, tm, here)   # not here: past the layout
        n_slots = te.shape[0] * tm

        def matmul(rows, w):
            return _gmm_dispatch(rows, w, te, tm, FORCE_INTERPRET, None, num_tiles)

        def dw_onto(acc, fresh, x, dy):
            return gmm_dw_onto(acc, fresh, x, dy, te, num_tiles, *dw_tiles(*acc.shape[1:]),
                               FORCE_INTERPRET)
    else:
        GMM_STATS.count("ragged_exchange")
        group = jnp.where(here, experts, held)        # what is no pick sorts behind every group
        slot = jnp.argsort(jnp.argsort(group, stable=True)).astype(jnp.int32)
        sizes = jnp.bincount(group, length=held + 1)[:held]
        n_slots = cap

        def matmul(rows, w):
            return grouped_gemm(rows, w, sizes).astype(rows.dtype)

        def dw_onto(acc, fresh, x, dy):
            # ragged_dot's own transpose in the stack: x^T dy a group, in float32
            dw, = jax.linear_transpose(lambda w: grouped_gemm(x, w, sizes),
                                       jax.ShapeDtypeStruct(acc.shape, x.dtype))(
                dy.astype(jnp.float32))
            return jnp.where(fresh[:, None, None], dw, acc + dw), jnp.ones((held,), bool)

    slot_pick = jnp.full((n_slots,), n_tokens * k, jnp.int32).at[slot].set(
        jnp.where(here, picks, n_tokens * k), mode="drop", unique_indices=True)
    slot_token = jnp.where(slot_pick < n_tokens * k, slot_pick // k, n_tokens)
    slots = jnp.full((n_tokens * k,), n_slots, jnp.int32).at[
        jnp.where(here, picks, n_tokens * k)].set(slot, mode="drop", unique_indices=True)
    return slot_token, slots.reshape(n_tokens, k), slot_pick, _PassProducts(matmul, dw_onto)


def _layout_of_pass(p, order, n_held, n_tokens, k, held, cap, rows_a_group, d_model, d_ff, dtype):
    """:func:`_pass_layout` of pass ``p`` of a rank's held picks: the picks
    ``order[0][p cap : (p + 1) cap]`` (flat indices ``t k + j``, ascending;
    ``order[1]``: their experts), as many as there are."""
    pick, expert = (jax.lax.dynamic_slice_in_dim(a, p * cap, cap) for a in order)
    here = p * cap + jnp.arange(cap, dtype=jnp.int32) < n_held
    return _pass_layout(jnp.where(here, expert, 0), here, jnp.where(here, pick, 0), n_tokens, k,
                        held, rows_a_group, d_model, d_ff, dtype)


def _held_order(experts, held, cap):
    """→ ((the flat indices of the picks whose expert - ``experts`` counts from
    this rank's first - is held here, ascending, then filler; each one's
    expert, sorted along with it), both padded to whole passes; how many are
    held)."""
    n = experts.shape[0]
    live = (experts >= 0) & (experts < held)
    at = jnp.arange(n, dtype=jnp.int32)
    order = jax.lax.sort((jnp.where(live, at, n + at), experts), num_keys=1)
    return tuple(jnp.pad(a, (0, -n % cap + cap)) for a in order), jnp.sum(live, dtype=jnp.int32)


def _n_passes(n_held, cap):
    """The passes a rank's loops run: ``ceil(held picks / cap)``, and one where
    it holds none - the first pass **is** the result (it writes every row and
    every named block, zeros where nothing is held), the others add to it."""
    return jnp.maximum((n_held + cap - 1) // cap, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _share_passes(x_all, vals, w1, w3, w2, experts, k, held, cap, rows_a_group, activation):
    """Every pass of a rank's held picks summed → (``[T_a, D]`` float32, the
    held picks, the rows the row kernels copied - 0 where the ``jnp`` forms
    ran). ``experts`` [T_a k] int32: each pick's expert counted from this
    rank's first (held: ``0 <= e < held``).

    A pass lays its picks out (:func:`_layout_of_pass`), copies each one's row
    from its token's into its slot, multiplies, and reads a token's slots,
    weighted, into its sum: **the first pass writes the result, a later one
    adds to it in the kernel that writes it** (``gather_sum_rows_onto``), so
    the step whose layers each take one pass writes ``[T_a, D]`` once and no
    zeros before it."""
    from deepspeed_tpu.ops.pallas.moe_rows import gather_rows, gather_sum_rows_onto
    Ta, D = x_all.shape
    order, n_held = _held_order(experts, held, cap)
    kernel = _use_pallas_rows(D, x_all.dtype)

    def one(p, carry):
        out, copied = carry
        slot_token, slots, _, on = _layout_of_pass(
            p, order, n_held, Ta, k, held, cap, rows_a_group, D, w1.shape[-1], x_all.dtype)
        xp = gather_rows(x_all, slot_token, None, kernel, FORCE_INTERPRET)
        y = on.matmul(activation(on.matmul(xp, w1)) * on.matmul(xp, w3), w2)
        out = gather_sum_rows_onto(out, p == 0, y, slots, vals.reshape(Ta, k), kernel,
                                   FORCE_INTERPRET)
        return out, copied + _rows_copied(slot_token, slots, Ta, kernel)

    out, copied = jax.lax.fori_loop(0, _n_passes(n_held, cap), one,
                                    (jax.lax.empty((Ta, D), jnp.float32), jnp.int32(0)))
    return out, n_held, copied


def _rows_copied(slot_token, slots, n_tokens, kernel):
    """The rows the two row kernels copy for one pass's layout (0: the ``jnp`` forms)."""
    return (jnp.sum(slot_token < n_tokens, dtype=jnp.int32)
            + jnp.sum(slots < slot_token.shape[0], dtype=jnp.int32)) * int(kernel)


def _share_passes_fwd(x_all, vals, w1, w3, w2, experts, k, held, cap, rows_a_group, activation):
    out = _share_passes(x_all, vals, w1, w3, w2, experts, k, held, cap, rows_a_group, activation)
    return out, (x_all, vals, w1, w3, w2, experts)


def _share_passes_bwd(k, held, cap, rows_a_group, activation, res, cts):
    """The passes' backward, written out. A pass, given the output's cotangent
    ``dout`` [T_a, D]: ``xp`` and ``g | u = xp (w1 | w3)`` again - the two
    stacks side by side, one product - and ``h = act(g) u`` (not ``y = h w2``:
    nothing here needs it); ``G``, the rows of ``dout`` copied into the layout
    by the dispatch's own kernel, **unweighted**, and ``dhu = G w2^T``; then,
    since a pick's weight ``v`` crosses the linear product (``<h w2, G> = <h,
    G w2^T>``), everything that weight touches in the hidden width: the
    weight's own gradient ``<h[s], dhu[s]>`` (float32; a pick lies in one pass,
    so it is set from its slot, not gathered for every pick and added), ``dh =
    v dhu`` pulled through the activation to ``dg | du``, and ``v h`` for ``dw2
    = (v h)^T G``; ``dw1 | dw3 = xp^T (dg | du)``; the rows' ``(dg | du) (w1 |
    w3)^T``, one product and no sum of two, summed by token. Three grouped
    products and two calls for the stacks' gradients, which skip the layout's
    tiles past the groups' as the products do. As in the forward, the first
    pass writes each gradient and a later one adds to it where it is written
    (the stacks' and the rows' in float32, rounded once after the last pass;
    an expert's block of a stack's gradient is written by the first pass that
    holds a pick of it)."""
    from deepspeed_tpu.ops.pallas.moe_rows import gather_rows, gather_sum_rows_onto
    x_all, vals, w1, w3, w2, experts = res
    (Ta, D), f32, dtype = x_all.shape, jnp.float32, x_all.dtype
    dout = cts[0].astype(dtype)             # the rows' own type, as they cross the layout
    order, n_held = _held_order(experts, held, cap)
    kernel = _use_pallas_rows(D, dtype)
    w13, F = jnp.concatenate([w1, w3], axis=2), w1.shape[-1]

    def one(p, carry):
        dx, dvals, dw13, dw2, named = carry
        slot_token, slots, slot_pick, on = _layout_of_pass(
            p, order, n_held, Ta, k, held, cap, rows_a_group, D, F, dtype)
        xp = gather_rows(x_all, slot_token, None, kernel, FORCE_INTERPRET)
        G = gather_rows(dout, slot_token, None, kernel, FORCE_INTERPRET)
        h, pull = jax.vjp(lambda gu: activation(gu[:, :F]) * gu[:, F:], on.matmul(xp, w13))
        dhu = on.matmul_t(G, w2).astype(f32)
        v = jnp.take(vals.astype(f32), slot_pick, mode="fill", fill_value=0)[:, None]
        dv = jnp.sum(h.astype(f32) * dhu, axis=-1)
        dgu, = pull((v * dhu).astype(dtype))
        fresh = ~named
        dw2, wrote = on.dw_onto(dw2, fresh, (v * h.astype(f32)).astype(dtype), G)
        dw13, _ = on.dw_onto(dw13, fresh, xp, dgu)
        dx = gather_sum_rows_onto(dx, p == 0, on.matmul_t(dgu, w13), slots, None, kernel,
                                  FORCE_INTERPRET)
        # a pick lies in one pass: its weight's gradient is set, from its slot, not added
        dvals = dvals.at[slot_pick].set(dv, mode="drop", unique_indices=True)
        return dx, dvals, dw13, dw2, named | wrote

    empty = jax.lax.empty
    dx, dvals, dw13, dw2, named = jax.lax.fori_loop(0, _n_passes(n_held, cap), one, (
        empty((Ta, D), f32), jnp.zeros(vals.shape, f32), empty(w13.shape, f32),
        empty(w2.shape, f32), jnp.zeros((held,), bool)))
    # an expert no pass named holds whatever its block held
    dw1, dw3, dw2 = (jnp.where(named[:, None, None], dw, 0.0).astype(dtype)
                     for dw in (dw13[..., :F], dw13[..., F:], dw2))
    return (dx.astype(dtype), dvals.astype(vals.dtype), dw1, dw3, dw2,
            np.zeros(experts.shape, dtype=jax.dtypes.float0))


_share_passes.defvjp(_share_passes_fwd, _share_passes_bwd)


def _exchange_dtype(dtype, mesh):
    """The reduce-scatter's dtype: the compute dtype, but float32 on a mesh of
    CPU devices, whose XLA CHECK-crashes on a bfloat16 reduction inside a
    shard_map."""
    if jnp.dtype(dtype) == jnp.bfloat16 and mesh.devices.flat[0].platform == "cpu":
        return jnp.float32
    return dtype


def exchanges_shares(mesh, T, share, stacks, first_group=None):
    """Whether :func:`dropless_moe_ffn` takes :func:`expert_share_exchange_ffn`
    for ``T`` tokens behind ``share`` under ``mesh``: an expert axis alone
    beside ``data``, dense gated stacks that are no table, and tokens and held
    experts that divide over the ranks."""
    axes = mesh_share_axes(mesh)
    if axes is None or first_group is not None:
        return False
    if any(w is None or _is_quantized(w) for w in stacks):
        return False
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    return T % n_shards == 0 and share.held % mesh.shape["expert"] == 0


def shards_experts(mesh):
    """Whether :func:`dropless_moe_ffn` runs its experts sharded under
    ``mesh``: an ``expert`` or ``tensor`` axis larger than 1."""
    if mesh is None or mesh.size <= 1:
        return False
    return mesh.shape.get("expert", 1) > 1 or mesh.shape.get("tensor", 1) > 1


def dropless_moe_ffn(x, topk_idx, topk_vals, w1, w3, w2, num_experts, mesh=None,
                     widen_boundary=True, first_group=None, share=None,
                     activation=jax.nn.silu):
    """Post-gate dropless MoE FFN over flat tokens — the one
    implementation behind BOTH v2 ragged serving and dropless training.

    ``x`` [T, D]; ``topk_idx``/``topk_vals`` [T, k] (weights already
    renormalized); ``w1``/``w3`` [E, D, I], ``w2`` [E, I, D] → [T, D]:
    ``sum_j w_j (activation(x w1_j) * (x w3_j)) w2_j``. ``w3=None``: an
    **ungated** expert of two matrices, ``activation(x w1_j) w2_j`` (one
    device only).

    Without a mesh (or expert/tensor axes of size 1): tokens replicate
    k×, sort by expert, and ride one grouped GEMM (``lax.ragged_dot``).

    **With an expert axis** (and no tensor, sequence or pipe axis beside it:
    :func:`mesh_share_axes`), dense stacks, a training caller (``widen_boundary``,
    the default) or a ``share``: :func:`expert_share_exchange_ffn`.
    Every rank gathers its axis's tokens in the compute dtype, lays out and
    multiplies only the picks its own experts hold, sums a token's picks to
    ``[T, D]`` and reduce-scatters that onto the token's rank; the backward is
    the same exchange transposed. Expert weights never leave their rank.

    With a tensor axis, quantized stacks, or a forward-only serving caller
    (``widen_boundary=False``: its programs are what they were): a shard_map
    manual over ONLY the expert and tensor axes - each shard routes every
    token it holds, every pick a row, the picks of experts held elsewhere
    pointed at local expert 0 and zeroed afterwards, and a float32 psum over
    ('expert', 'tensor') combines; ``widen_boundary`` carries x across that
    boundary in float32 (its transposed psum crashed XLA:CPU in bfloat16).
    Other mesh axes stay under automatic partitioning. Differentiable
    end-to-end either way (ragged_dot and the Pallas grouped matmul have grad
    rules; the collectives transpose).

    Expert weights may be grouped-layout ``QuantizedWeight`` stacks.
    Under a mesh they cross the shard_map boundary DESTRUCTURED into
    their carrier leaves (shard_map broadcasts one spec over every leaf
    of an operand, and values/scales need different specs) with the
    shard plan from ``inference/v2/sharding.moe_expert_specs``: E over
    'expert' (E/ep carriers per replica), features over 'tensor' when
    the carrier geometry allows, and the same psum combine either way.

    ``first_group``: the stacks are a table of expert groups of which
    this call's ``num_experts`` start there (:func:`moe_grouped_mlp`);
    without expert/tensor axes only.

    ``share`` (an :class:`ExpertShare`; None: the stacks are every column
    of the router, today's programs unchanged): the stacks are the
    ``share.held`` experts held here and ``topk_idx`` counts over all the
    router's columns (``num_experts`` is not read). → ``sum_j w_j E_j(x)``
    over the held picks, which alone are laid out as rows, plus ``(the
    zero-compute picks' weights) * x``; what experts held elsewhere would
    add is left out, and nothing is multiplied, read or laid out for it:
    :func:`expert_share_ffn`, which also says how many passes the held picks
    took. A pick of -1 is no pick (a padding token's). On a mesh with an
    expert axis the held experts are split over its ranks and exchanged as
    above (:func:`expert_share_exchange_ffn`, dense gated stacks)."""
    T, k = topk_idx.shape
    # the exchange is the training dispatch (``widen_boundary``, the default) and a share's on a
    # mesh; serving's expert-parallel engines (``widen_boundary=False``) keep their program
    if (widen_boundary or share is not None) and exchanges_shares(
            mesh, T, share or ExpertShare(0, num_experts, num_experts), (w1, w3, w2), first_group):
        whole = share or ExpertShare(0, num_experts, num_experts)
        out = expert_share_exchange_ffn(x, topk_idx, topk_vals, w1, w3, w2, whole, mesh,
                                        activation=activation)[0]
        if whole.zero:
            with jax.named_scope("ds.moe_zero"):
                w_zero = jnp.sum(jnp.where(whole.parts(topk_idx)[1], topk_vals, 0), axis=-1,
                                 keepdims=True)
                out = out + w_zero.astype(x.dtype) * x
        return out
    if share is not None:
        if shards_experts(mesh):
            raise NotImplementedError(
                "an expert share on a mesh with a tensor, sequence or pipe axis, over quantized "
                "or ungated stacks, or whose tokens or experts do not divide over the ranks")
        return expert_share_ffn(x, topk_idx, topk_vals, w1, w3, w2, share,
                                first_group=first_group, activation=activation)[0]
    idx_rep = topk_idx.reshape(-1)  # [T*k]
    if not fused_gmm_enabled():
        # DS_FUSED_GMM=0: unbox quantized stacks up front — everything
        # below (including the shard plan) then sees dense stacks, which
        # is exactly the pre-fused execution model.
        w1, w3, w2 = (w if w is None else _unbox_stack(w, x.dtype) for w in (w1, w3, w2))

    if shards_experts(mesh):
        if w3 is None:
            raise NotImplementedError("an ungated expert (w3=None) is not sharded over "
                                      "expert/tensor axes")
        if first_group is not None:
            raise NotImplementedError("a table of expert groups (first_group) is not "
                                      "sharded over expert/tensor axes")
        from jax.sharding import PartitionSpec as P
        E, ep = num_experts, mesh.shape.get("expert", 1)
        from deepspeed_tpu.inference.v2.sharding import moe_expert_specs
        w_specs, psum_axes = moe_expert_specs(mesh, w1, w3, w2)
        if E % ep == 0:
            dtype = x.dtype
            parts, tags, flat_specs = [], [], []
            for w, sp in zip((w1, w3, w2), w_specs):
                ps, tag = _split_stack(w)
                parts.extend(ps)
                tags.append(tag)
                flat_specs.extend(sp)

            def shard_body(x_full, idx, *wflat):
                w1s, w3s, w2s = _join_stacks(wflat, tags)
                e_local = E // ep
                off = jax.lax.axis_index("expert") * e_local
                local = (idx >= off) & (idx < off + e_local)
                lidx = jnp.where(local, idx - off, 0)
                x_rep = jnp.repeat(x_full.astype(dtype), k, axis=0)
                out = moe_grouped_mlp(x_rep, lidx, _cast_stack(w1s, dtype),
                                      _cast_stack(w3s, dtype),
                                      _cast_stack(w2s, dtype),
                                      num_experts=e_local)
                out = jnp.where(local[:, None], out, 0)
                # combine partial expert/feature sums in fp32 (also
                # dodges an XLA:CPU CHECK-crash on bf16 all-reduce
                # inside shard_map)
                return jax.lax.psum(out.astype(jnp.float32),
                                    psum_axes).astype(dtype)

            # Training (widen_boundary=True): x crosses the region
            # boundary in fp32 — the TRANSPOSE of the replicated
            # in_spec is a psum of dx over 'expert', and a bf16 psum
            # there hits the same XLA:CPU CHECK-crash ('Invalid
            # binary instruction opcode copy') the forward psum above
            # dodges; it goes live whenever the layer sits inside
            # lax.scan (the carry keeps dx alive). Compute stays in
            # the caller's dtype; only the boundary is widened.
            # Forward-only serving passes widen_boundary=False and
            # keeps the bf16 (half-traffic) expert-axis gather.
            x_in = x.astype(jnp.float32) if widen_boundary else x
            # under jit also when called op by op: jax 0.9's eager shard_map, manual over
            # some of the mesh's axes, asks itself for out_specs over all of them and refuses
            out_rep = jax.jit(shard_map(
                shard_body, mesh=mesh,
                in_specs=(P(), P(), *flat_specs),
                out_specs=P(), axis_names={"expert", "tensor"},
                check_vma=False))(x_in, idx_rep, *parts)
            out_k = out_rep.reshape(T, k, -1)
            return jnp.einsum("tk,tkd->td", topk_vals.astype(x.dtype), out_k)

    x_rep = jnp.repeat(x, k, axis=0)  # [T*k, D]
    out_rep = moe_grouped_mlp(x_rep, idx_rep, _cast_stack(w1, x.dtype),
                              None if w3 is None else _cast_stack(w3, x.dtype),
                              _cast_stack(w2, x.dtype), num_experts=num_experts,
                              activation=activation, first_group=first_group)
    out_k = out_rep.reshape(T, k, -1)
    return jnp.einsum("tk,tkd->td", topk_vals.astype(x.dtype), out_k)


def dense_reference_mlp(x, expert_idx, w_gate, w_up, w_down, activation=jax.nn.silu):
    """O(T*E) dense check: every token through every expert, select own
    (``w_up=None``: the ungated expert's twin)."""
    gate = jnp.einsum("td,edf->tef", x, w_gate)
    inter = activation(gate) if w_up is None else \
        activation(gate) * jnp.einsum("td,edf->tef", x, w_up)
    out = jnp.einsum("tef,efd->ted", inter, w_down)
    return jnp.take_along_axis(out, expert_idx[:, None, None], axis=1)[:, 0, :].astype(x.dtype)
