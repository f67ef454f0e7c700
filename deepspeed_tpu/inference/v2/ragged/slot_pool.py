"""Slot pool: per-sequence state that is not paged rows.

A linear-attention layer keeps one fixed-size state a sequence (a
``[heads, d, d]`` matrix a layer), the same at token 10 and at token
500,000, so it has no rows to page. The device array ``[layers, slots + 1,
...]`` rides the step programs beside the paged pools, in the model
kind's own tree (``model_runner.SalaKind.extra_state``); this class is
the host's side of it: which slot a tracked sequence owns. Slot 0 is
padding's — every batch row without a sequence points there, as padding
tokens point at the null block — and no sequence ever owns it. A slot is
acquired when its sequence is first tracked and released with the
sequence's blocks; it is not cleared in between: the step programs take a
sequence's state as zero at its first rows (position 0).

**A second owner.** With the prefix cache on, for a kind whose slots may be
snapshotted (``ModelKind.snapshots``), the pool is larger than the sequences
that can be tracked and the slots beyond belong to the cache
(``acquire(cached=True)``; ``cached``): each holds a copy of some sequence's
slot as it stood at a block boundary. It is one budget: a sequence that finds
no free slot takes the cache's least recently used one (``reclaim``, the
cache's ``PrefixCacheManager._evict_snapshot``), so cached state never
starves a live sequence, and ``reclaimable_slots`` is what an admission gate
may count on.
"""


class SlotPool:

    def __init__(self, slots, bytes_per_slot):
        assert slots >= 1, "need at least one slot beyond padding's"
        self.slots = int(slots)
        self.bytes_per_slot = int(bytes_per_slot)
        self._free = list(range(self.slots, 0, -1))    # 1 is handed out first
        self.cached = set()     # the slots the prefix cache owns: snapshots
        self.reclaim = None     # () -> bool: the cache released its least recently used slot

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def reclaimable_slots(self) -> int:
        """Free slots and the cache's: what a sequence can be given."""
        return len(self._free) + len(self.cached)

    def acquire(self, cached=False) -> int:
        """→ a slot, the caller's: a sequence's, or (``cached``) the prefix
        cache's. Where none is free the cache gives up its oldest."""
        if not self._free and not (self.reclaim is not None and self.reclaim()):
            raise RuntimeError(f"slot pool exhausted: all {self.slots} slots are owned — "
                               f"flush() sequences first")
        slot = self._free.pop()
        if cached:
            self.cached.add(slot)
        return slot

    def release(self, slot) -> None:
        slot = int(slot)
        if not 1 <= slot <= self.slots or slot in self._free:
            raise ValueError(f"slot {slot} is not an owned slot of this pool")
        self.cached.discard(slot)
        self._free.append(slot)

    def bytes(self) -> int:
        """The device array's size, padding's slot included."""
        return (self.slots + 1) * self.bytes_per_slot
