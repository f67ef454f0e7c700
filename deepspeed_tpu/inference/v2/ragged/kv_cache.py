"""Blocked (paged) KV cache.

Capability match for the reference's
``deepspeed/inference/v2/ragged/kv_cache.py`` (``BlockedKVCache`` at
kv_cache.py:40): a pool of fixed-size KV blocks shared by all
sequences, fronted by :class:`BlockedAllocator`. TPU design: the pool
is two device arrays ``[num_layers, num_blocks, block_size, n_kv_heads *
head_dim]`` — the layout the paged kernel's block DMA reads, so no
program reshapes it — updated functionally (the engine donates them
through the jitted step, whose layer scan carries them, so XLA updates
in place). What leaves or enters the pool a few blocks at a time
(offload handles) keeps the 5-D wire shape ``[num_layers, n, block_size,
n_kv_heads, head_dim]``: those blocks are reshaped, never the pool.
Block 0 is reserved as the null block — padding tokens scatter there
and no live sequence ever owns it.

What a row of the two arrays holds is the model kind's to say
(``model_runner.kind_of(cfg).state_rows``): keys and values, ``n_kv_heads *
head_dim`` wide each (``state_kind`` ``kv``, the default), or another
state under another name — the ``latent`` kind keeps its normalised
compressed row in ``k`` and its rotated shared key in ``v``, of different
widths. Blocks, allocation and in-place update are the same for every
kind; the offload wire format is the ``kv`` kind's only."""

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator

NULL_BLOCK = 0


class KVCacheHandleError(ValueError):
    """An offload handle does not match this pool's layout — raised on
    the host BEFORE the jitted scatter, instead of a shape/dtype blow-up
    inside compiled code (whose error points at XLA internals, not at
    the corrupt handle)."""


class BlockedKVCache:

    def __init__(self, num_layers, num_blocks, block_size, n_kv_heads, head_dim,
                 dtype=jnp.bfloat16, sharding=None, state_kind="kv", row_widths=None):
        """``sharding``: where the pool lives (a serving mesh shards its
        last dim over whole KV heads); it is allocated there directly,
        never whole on the default device first. ``state_kind`` /
        ``row_widths``: a state other than keys and values, and the widths
        of its two rows (``n_kv_heads`` / ``head_dim`` are then unused)."""
        assert num_blocks >= 2, "need at least one real block beyond the null block"
        self.num_layers = num_layers
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.state_kind = state_kind
        self.row_widths = tuple(row_widths or (n_kv_heads * head_dim,) * 2)
        shape = (num_layers, num_blocks, block_size)
        self.k = jnp.zeros(shape + self.row_widths[:1], dtype, device=sharding)
        self.v = jnp.zeros(shape + self.row_widths[1:], dtype, device=sharding)
        self._allocator = BlockedAllocator(num_blocks)
        self._allocator.allocate(1)  # pin the null block forever

    @property
    def free_blocks(self) -> int:
        return self._allocator.free_blocks

    def reserve(self, num_blocks):
        return self._allocator.allocate(num_blocks)

    def free(self, blocks):
        blocks = list(blocks)  # any iterable, generators included
        if blocks:
            self._allocator.free(blocks)

    def bytes(self) -> int:
        return (self.k.size + self.v.size) * self.k.dtype.itemsize

    def bytes_per_token(self) -> int:
        """What one token holds in the pool, over all layers."""
        return self.num_layers * sum(self.row_widths) * self.k.dtype.itemsize

    def _kv_only(self, what):
        if self.state_kind != "kv":
            raise NotImplementedError(
                f"KV offload ({what}) moves blocks in the [layers, n, block_size, n_kv_heads, "
                f"head_dim] wire format of the 'kv' state; the {self.state_kind!r} state has none")

    # ------------------------------------------------------------------
    # Host offload / restore (the reference declares this surface but
    # raises NotImplementedError, kv_cache.py:166/176 "Offloading is not
    # yet supported"; here it is real — vLLM-style sequence swapping)
    # ------------------------------------------------------------------
    def gather(self, blocks):
        """Copy ``blocks``' KV to host memory WITHOUT freeing them →
        offload handle (the read half of :meth:`offload`; the KV-tier
        demotion path gathers before the trie's ids are freed). The
        gather runs through one cached jitted program per pool with the
        id vector padded to a power of two (repeating the last id), so
        arbitrary batch sizes reuse log2-many compiled programs instead
        of retracing an eager ``jnp.take`` per distinct length."""
        self._kv_only("gather")
        blocks = [int(b) for b in blocks]
        for b in blocks:
            if b < 0 or b >= self.num_blocks:
                raise KVCacheHandleError(f"invalid block id {b} for a "
                                         f"{self.num_blocks}-block pool")
        n = len(blocks)
        wire = (self.num_layers, n, self.block_size, self.n_kv_heads, self.head_dim)
        if n == 0:
            empty = jax.device_get(jnp.zeros(wire, self.dtype))
            return {"k": empty, "v": empty.copy()}
        padded = 1 << (n - 1).bit_length()
        ids = jnp.asarray(blocks + [blocks[-1]] * (padded - n), jnp.int32)
        k_host, v_host = jax.device_get(_gather_blocks(self.k, self.v, ids))
        return {"k": k_host[:, :n].reshape(wire), "v": v_host[:, :n].reshape(wire)}

    def offload(self, blocks, keep=()):
        """Move ``blocks``' KV to host memory and free them for reuse.
        → opaque handle for :meth:`restore`. Blocks listed in ``keep``
        are copied into the handle but NOT freed — the prefix-cache
        suspend path, where a shared prefix block stays owned by the
        radix trie while the suspended sequence carries its own copy.
        ``keep`` must be a subset of ``blocks``: an id outside the
        offload set would silently stay allocated with nobody holding
        it (a permanent pool leak), so it raises instead."""
        blocks = [int(b) for b in blocks]
        keep = {int(b) for b in keep}
        extra = keep - set(blocks)
        if extra:
            raise KVCacheHandleError(
                f"keep ids {sorted(extra)} are not in the offloaded block "
                f"set — each kept block must be part of this offload")
        handle = self.gather(blocks)
        self.free(b for b in blocks if b not in keep)
        return handle

    def _validate_handle(self, handle):
        """Shape/dtype-check an offload handle against the pool layout
        (raises :class:`KVCacheHandleError`) so corruption surfaces as a
        typed host error, never inside the jitted scatter. Accepts both
        plain (pool-dtype) handles and quantized ones (``"quantized":
        True`` — int8 k/v carriers plus per-group fp32 ``k_scales`` /
        ``v_scales`` of shape ``[num_layers, n, groups_per_block]``)."""
        if not isinstance(handle, dict) or "k" not in handle or "v" not in handle:
            raise KVCacheHandleError("offload handle must be a dict with "
                                     "'k' and 'v' arrays")
        quantized = bool(handle.get("quantized"))
        k, v = handle["k"], handle["v"]
        want = (self.num_layers, None, self.block_size, self.n_kv_heads,
                self.head_dim)
        want_dtype = jnp.dtype(jnp.int8) if quantized else jnp.dtype(self.dtype)
        for name, arr in (("k", k), ("v", v)):
            shape = getattr(arr, "shape", None)
            if shape is None or len(shape) != 5 or any(
                    w is not None and s != w for s, w in zip(shape, want)):
                raise KVCacheHandleError(
                    f"handle['{name}'] shape {shape} does not match pool "
                    f"layout [num_layers={self.num_layers}, n, "
                    f"block_size={self.block_size}, n_kv_heads="
                    f"{self.n_kv_heads}, head_dim={self.head_dim}]")
            if jnp.dtype(arr.dtype) != want_dtype:
                raise KVCacheHandleError(
                    f"handle['{name}'] dtype {arr.dtype} does not match "
                    f"{'quantized carrier' if quantized else 'pool'} dtype "
                    f"{want_dtype.name}")
        if k.shape != v.shape:
            raise KVCacheHandleError(
                f"handle k/v shapes disagree: {k.shape} vs {v.shape}")
        if quantized:
            slab = self.block_size * self.n_kv_heads * self.head_dim
            n = k.shape[1]
            for name in ("k_scales", "v_scales"):
                scales = handle.get(name)
                shape = getattr(scales, "shape", None)
                if scales is None or shape is None or len(shape) != 3 or \
                        shape[0] != self.num_layers or shape[1] != n or \
                        shape[2] < 1 or (n and slab % shape[2] != 0):
                    raise KVCacheHandleError(
                        f"quantized handle['{name}'] shape {shape} does not "
                        f"match [num_layers={self.num_layers}, n={n}, "
                        f"groups_per_block dividing {slab}]")
                if jnp.dtype(scales.dtype) != jnp.dtype(jnp.float32):
                    raise KVCacheHandleError(
                        f"quantized handle['{name}'] dtype {scales.dtype} "
                        f"must be float32")

    def restore(self, handle):
        """Bring offloaded KV back into freshly reserved blocks (ids may
        differ from the original ones — callers re-point their block
        tables). The pool arrays are donated through the jitted scatter,
        so the update is in place, not a second pool copy. Quantized
        handles dequantize INSIDE the jitted scatter (int8 carriers +
        scales cross to device; the fp32 expansion never exists on
        host). An empty handle (``n == 0``) is a no-op returning ``[]``
        — no reservation, no zero-block scatter through jit."""
        self._kv_only("restore")
        self._validate_handle(handle)
        n = handle["k"].shape[1]
        if n == 0:
            return []
        blocks = self.reserve(n)
        ids = jnp.asarray(blocks, jnp.int32)
        # the handle's blocks take the pool's flattened layout; the pool is never reshaped
        flat = (self.num_layers, n, self.block_size, self.n_kv_heads * self.head_dim)
        if handle.get("quantized"):
            self.k, self.v = _scatter_blocks_q(
                self.k, self.v, ids,
                jnp.asarray(handle["k"]).reshape(flat), jnp.asarray(handle["v"]).reshape(flat),
                jnp.asarray(handle["k_scales"], jnp.float32),
                jnp.asarray(handle["v_scales"], jnp.float32))
        else:
            self.k, self.v = _scatter_blocks(self.k, self.v, ids,
                                             jnp.asarray(handle["k"], self.dtype).reshape(flat),
                                             jnp.asarray(handle["v"], self.dtype).reshape(flat))
        return blocks


class WindowPool:
    """The host's side of a **second pool**, for the layers that attend to a
    window: the allocator of its blocks and its geometry. A window layer
    needs the last ``window`` positions of a sequence, not its context, so
    its keys and values live apart from the full layers' - device arrays
    ``[Lw, num_blocks, block_size, Hkv * Dh]`` of their own, which the engine
    lays once (:meth:`arrays`) and hands to every program as its ``extra``
    (carried and donated beside the two pools: this object never holds them) -
    under a table of their own a sequence, a **ring** of :attr:`ring`
    columns (``DSStateManager``: the block that holds positions ``b *
    block_size ..`` stands in column ``b % ring``), from which a step's
    blocks that lie wholly behind a sequence's window go back to the
    allocator. Block 0 is the null block, as in :class:`BlockedKVCache`.

    :meth:`bound` is what a sequence holds at the most: ``ceil(window /
    block_size) + 1`` blocks between steps and in a decode step at any
    length, ``ceil((window + k - 1) / block_size) + 1`` inside a step that
    brings it ``k`` rows (the ``window + k - 1`` positions from its first
    row's lower bound to its last row). The ring
    has the columns of the widest step (``max_rows``: the token budget).
    Counters, for whoever sizes the pool (``stats``): blocks in use, their
    high water, blocks released, and how often the admission gate held a
    request back for this pool."""

    def __init__(self, window, block_size, max_rows, num_blocks):
        assert num_blocks >= 2, "need at least one real block beyond the null block"
        self.window, self.block_size, self.num_blocks = int(window), int(block_size), int(num_blocks)
        self.ring = self.bound(max_rows)
        self._allocator = BlockedAllocator(self.num_blocks)
        self._allocator.allocate(1)  # pin the null block forever
        self.released = self.high_water = self.gate_refused = 0

    @staticmethod
    def default_blocks(window, block_size, max_rows, sequences):
        """The pool that never refuses ``sequences`` tracked sequences: the
        null block, every sequence's bound between steps and one more (a
        decode step's, a short burst's), and one step's rows over them all -
        what the admission gate commits (``serving/admission.CapacityGate``)."""
        return 1 + sequences * (-(-window // block_size) + 2) + -(-max_rows // block_size)

    def arrays(self, layers, row_width, dtype):
        """→ the pool's device arrays (zeros), keys and values: the tree a
        kind with window layers is given as its programs' ``extra``."""
        shape = (layers, self.num_blocks, self.block_size, row_width)
        return {"wk": jnp.zeros(shape, dtype), "wv": jnp.zeros(shape, dtype)}

    def bound(self, rows=1):
        """The blocks a sequence may hold inside a step of ``rows`` rows of it."""
        return -(-(self.window + rows - 1) // self.block_size) + 1

    @property
    def free_blocks(self) -> int:
        return self._allocator.free_blocks

    @property
    def in_use(self) -> int:
        return self.num_blocks - 1 - self._allocator.free_blocks

    def reserve(self, n):
        ids = self._allocator.allocate(n)
        self.high_water = max(self.high_water, self.in_use)
        return ids

    def free(self, blocks, behind=False):
        """``behind``: the blocks fell behind their sequence's window (counted
        as released); else they go with the sequence or a rewind."""
        blocks = list(blocks)
        if blocks:
            self._allocator.free(blocks)
            if behind:
                self.released += len(blocks)

    def stats(self):
        return {"blocks": self.num_blocks - 1, "in_use": self.in_use,
                "high_water": self.high_water, "released": self.released,
                "ring_columns": self.ring, "gate_refused": self.gate_refused}


# donated pools: the functional .at[].set aliases in place, no pool copy
_scatter_blocks = jax.jit(
    lambda pk, pv, ids, kv, vv: (pk.at[:, ids].set(kv), pv.at[:, ids].set(vv)),
    donate_argnums=(0, 1))

# cached batched gather for offload/demotion (ids pre-padded to a power
# of two by the caller, bounding the compiled-program set to log2 sizes)
_gather_blocks = jax.jit(
    lambda pk, pv, ids: (jnp.take(pk, ids, axis=1), jnp.take(pv, ids, axis=1)))


def _dequant_blocks(vals, scales, dtype):
    """Per-group int8 dequant of blocks in pool layout ``[L, n, bs,
    Hkv*Dh]`` (traced inside the restore scatter): group ``g`` of block
    ``b`` in layer ``l`` scales by ``scales[l, b, g]``."""
    L, n, bs, HD = vals.shape
    groups = scales.shape[-1]
    gs = (bs * HD) // groups
    deq = vals.astype(jnp.float32).reshape(L, n, groups, gs) * scales[..., None]
    return deq.reshape(vals.shape).astype(dtype)


_scatter_blocks_q = jax.jit(
    lambda pk, pv, ids, kv, vv, ks, vs: (
        pk.at[:, ids].set(_dequant_blocks(kv, ks, pk.dtype)),
        pv.at[:, ids].set(_dequant_blocks(vv, vs, pv.dtype))),
    donate_argnums=(0, 1))
