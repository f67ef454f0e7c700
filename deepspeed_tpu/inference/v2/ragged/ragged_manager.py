"""Sequence state manager.

Capability match for the reference's
``deepspeed/inference/v2/ragged/ragged_manager.py`` (``DSStateManager``
at ragged_manager.py:19): tracks live sequences (uid → descriptor),
owns the KV block allocation for each, and hands out batch slots.

When a :class:`PrefixCacheManager` is attached, sequence creation leases
the prompt's longest cached block-aligned prefix (the descriptor starts
with those blocks in its table and ``seen_tokens`` past them), block
allocation reclaims unreferenced cached blocks under pressure, and
flush retires completed blocks INTO the cache instead of freeing them —
shared prefix blocks are decref'd, never hard-freed.

**The table.** A step's block tables are not written sequence by sequence:
the manager keeps, between steps, one row a tracked sequence - its block
ids padded with the null block (``block_table``) and, for a model kind
with state beyond its blocks, its ``state_row`` (``state_table``) - and a
step gathers its rows with one index (:meth:`gather`). A sequence holds
its row from creation to flush; the row is written only when the
sequence's blocks change (:meth:`extend_blocks`, :meth:`trim_blocks`: the
two places that touch ``desc.blocks``), which a decode row's do once in
``block_size`` steps. The last row is nobody's and stays null: padding's.
``rows_written`` counts the writes (a step record's
``n_table_rows_written`` is its growth over the step).

**The window pool's table.** For a model kind with window layers
(``kv_cache.WindowPool``) the ``state_table`` is that pool's table: a
sequence's row is a **ring** of ``window_pool.ring`` columns, the block of
its positions ``b * block_size ..`` in column ``b % ring``, null where the
sequence holds none - so the row is as short at position 200,000 as at 600
and rides a step's ``seq_state`` as any kind's state row does. A step
reserves both pools' blocks (:meth:`window_need`, :meth:`reserve_window`),
and once its sequences have advanced the blocks that lie wholly behind their
windows go back (:meth:`release_behind`): a sequence holds at most
``window_pool.bound(rows)`` blocks inside a step of ``rows`` rows of it and
``bound(1)`` between steps, whatever its length. :meth:`rewind_sequence`,
:meth:`release_unused_blocks`, :meth:`flush_sequence` and
:meth:`drop_sequence` keep both tables."""

import numpy as np

from deepspeed_tpu.inference.v2.ragged.kv_cache import NULL_BLOCK, BlockedKVCache
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import DSSequenceDescriptor


class DSStateManager:

    def __init__(self, kv_cache: BlockedKVCache, max_tracked_sequences: int,
                 max_blocks_per_seq: int = None, seq_rows: int = 0, window_pool=None):
        """``max_blocks_per_seq``: the table's width, a step's too (the
        pool's size where nobody says: no sequence owns more).
        ``seq_rows``: the length of a sequence's ``state_row`` (0: the
        model kind keeps none, and there is no ``state_table``).
        ``window_pool``: the ``WindowPool`` of a kind with window layers,
        whose ring the ``state_table`` then is (``seq_rows`` its columns)."""
        self.kv_cache = kv_cache
        self.window_pool = window_pool
        if window_pool is not None and seq_rows != window_pool.ring:
            raise ValueError(f"the window pool's table has {window_pool.ring} columns a "
                             f"sequence, not seq_rows={seq_rows}")
        self.max_tracked_sequences = max_tracked_sequences
        self.max_blocks_per_seq = int(max_blocks_per_seq or kv_cache.num_blocks)
        self._seqs = {}  # uid -> descriptor
        self.prefix_cache = None
        rows = max_tracked_sequences + 1    # the last is padding's
        self.block_table = np.full((rows, self.max_blocks_per_seq), NULL_BLOCK, np.int32)
        self.state_table = np.zeros((rows, seq_rows), np.int32) if seq_rows else None
        self._free_rows = list(range(max_tracked_sequences - 1, -1, -1))  # 0 goes first
        self.rows_written = 0

    def attach_prefix_cache(self, prefix_cache) -> None:
        """Route allocation/flush through a radix prefix cache."""
        self.prefix_cache = prefix_cache

    @property
    def n_tracked_sequences(self) -> int:
        return len(self._seqs)

    @property
    def free_blocks(self) -> int:
        return self.kv_cache.free_blocks

    def query(self, uid):
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid, prompt_tokens=None) -> DSSequenceDescriptor:
        """Track ``uid`` (idempotent). With a prefix cache attached and
        ``prompt_tokens`` given, a NEW sequence comes back with its
        longest cached prefix already in its block table: ``seen_tokens``
        (and ``cached_tokens``) point at the first uncached token, so
        prefill starts there."""
        desc = self._seqs.get(uid)
        if desc is not None:
            return desc
        if len(self._seqs) >= self.max_tracked_sequences:
            raise RuntimeError(f"max_tracked_sequences={self.max_tracked_sequences} exceeded")
        desc = DSSequenceDescriptor(uid, self.kv_cache.block_size)
        blocks, cached = [], 0
        if self.prefix_cache is not None and prompt_tokens is not None \
                and len(prompt_tokens) > 0:
            blocks, cached = self.prefix_cache.acquire(uid, prompt_tokens)
        desc.row = self._free_rows.pop()    # nothing can fail from here
        if cached:
            self.extend_blocks(desc, blocks)
            desc.shared_blocks = len(blocks)
            desc.seen_tokens = cached
            desc.cached_tokens = cached
            desc.tokens = [int(t) for t in prompt_tokens[:cached]]
        self._seqs[uid] = desc
        return desc

    # ------------------------------------------------------------ the table
    def extend_blocks(self, desc: DSSequenceDescriptor, block_ids) -> None:
        """Append ``block_ids`` to ``desc``'s blocks and to its row."""
        held = len(desc.blocks)
        block_ids = np.atleast_1d(block_ids)
        if held + len(block_ids) > self.max_blocks_per_seq:
            raise ValueError(f"sequence {desc.uid} owns {held + len(block_ids)} blocks > "
                             f"max_blocks_per_seq={self.max_blocks_per_seq} (context overflow)")
        desc.extend_blocks(block_ids)
        self.block_table[desc.row, held:held + len(block_ids)] = block_ids
        self.rows_written += 1

    def trim_blocks(self, desc: DSSequenceDescriptor, keep: int) -> list:
        """Take the blocks past the first ``keep`` off ``desc`` and null
        their places in its row. → those blocks, the caller's to free or
        to hand on."""
        extra = desc.blocks[keep:]
        if extra:
            del desc.blocks[keep:]
            self.block_table[desc.row, keep:keep + len(extra)] = NULL_BLOCK
            self.rows_written += 1
        return extra

    def set_state_row(self, desc: DSSequenceDescriptor, state_row) -> None:
        """What the model kind keeps a sequence beyond its blocks
        (``kind.seq_state``), on the descriptor and in its row."""
        desc.state_row = state_row
        self.state_table[desc.row] = state_row

    def gather(self, descs, rows=None, out=None):
        """→ ``(block_tables, seq_state)`` of a step whose row ``i`` is
        ``descs[i]``: ``[rows, max_blocks_per_seq]`` and ``[rows,
        seq_rows]`` int32 (None without a ``state_table``), one index of
        the table each. ``rows`` past the sequences (default: none) are
        padding's: null blocks, state 0. ``out``: an array of the block
        tables' shape to lay them in (a wrapper's own), not a new one."""
        index = [desc.row for desc in descs]
        index += [self.max_tracked_sequences] * ((rows or 0) - len(index))   # padding's row
        index = np.fromiter(index, np.intp, len(index))
        # mode: every index is a row of the table, and numpy then writes ``out`` itself
        tables = np.take(self.block_table, index, axis=0, out=out, mode="clip")
        if self.state_table is None:
            return tables, None
        return tables, np.take(self.state_table, index, axis=0, mode="clip")

    def _untrack(self, uid) -> DSSequenceDescriptor:
        """Stop tracking ``uid``: its row is null again and the next new
        sequence's. The descriptor keeps its blocks for whoever frees them."""
        desc = self._seqs.pop(uid, None)
        if desc is None:
            raise KeyError(f"unknown sequence {uid}")
        self.block_table[desc.row, :len(desc.blocks)] = NULL_BLOCK
        if self.state_table is not None:
            self.state_table[desc.row] = 0
        self._free_rows.append(desc.row)
        desc.row = -1
        return desc

    # ------------------------------------------------------------ the pool
    def reserve(self, descs, need) -> None:
        """Reserve ``need[i]`` more blocks for ``descs[i]``: one call to
        the pool for the whole step, dealt out in the batch's order (the
        ids each sequence gets are those of a call a sequence)."""
        total = int(np.sum(need))
        if total:
            pool = self.prefix_cache if self.prefix_cache is not None else self.kv_cache
            ids, at = pool.reserve(total), 0
            for i in np.flatnonzero(need):
                self.extend_blocks(descs[i], ids[at:at + need[i]])
                at += need[i]

    def allocate_for(self, desc: DSSequenceDescriptor, new_tokens: int) -> None:
        self.reserve([desc], [desc.blocks_needed(new_tokens)])
        if self.window_pool is not None:
            self.reserve_window([desc], self.window_need([desc], new_tokens))

    # ------------------------------------------------------ the window pool
    def window_need(self, descs, new_tokens):
        """→ int64 over ``descs`` (None: a sequence not tracked yet): the
        window-pool blocks each lacks to hold ``new_tokens`` (one number, or
        one a sequence) more positions. A sequence whose rewind crossed into
        what its window had released is refused here, before any step."""
        n = len(descs)
        for desc in descs:
            if desc is not None and desc.window_stale:
                raise ValueError(f"sequence {desc.uid} was rewound past the blocks its window "
                                 f"had released: it cannot be continued")
        seen = np.fromiter([0 if d is None else d.seen_tokens for d in descs], np.int64, n)
        have = np.fromiter([0 if d is None else d.window_first + len(d.window_blocks)
                            for d in descs], np.int64, n)
        return np.maximum(0, -(-(seen + new_tokens) // self.window_pool.block_size) - have)

    def reserve_window(self, descs, need) -> None:
        """:meth:`reserve` for the window pool: ``need[i]`` more blocks for
        ``descs[i]``, one call to the allocator a step, each sequence's laid
        in its ring behind the ones it holds."""
        total = int(np.sum(need))
        if not total:
            return
        pool = self.window_pool
        ids, at = pool.reserve(total), 0
        for i in np.flatnonzero(need):
            desc, new = descs[i], ids[at:at + need[i]]
            at += need[i]
            if len(desc.window_blocks) + len(new) > pool.ring:
                raise ValueError(f"sequence {desc.uid} would hold {len(desc.window_blocks)}+"
                                 f"{len(new)} window blocks > the ring's {pool.ring}")
            start = desc.window_first + len(desc.window_blocks)
            self.state_table[desc.row, (start + np.arange(len(new))) % pool.ring] = new
            desc.window_blocks.extend(int(b) for b in new)
            self.rows_written += 1

    def release_behind(self, descs) -> None:
        """After a step's sequences advanced: the window-pool blocks that lie
        wholly before ``seen_tokens - window + 1`` - the lowest position the
        sequence's next row attends to - go back to the allocator, and their
        ring columns are null."""
        pool = self.window_pool
        for desc in descs:
            first = max(0, desc.seen_tokens - pool.window + 1) // pool.block_size
            drop = min(first - desc.window_first, len(desc.window_blocks))
            if drop > 0:
                self.state_table[desc.row,
                                 (desc.window_first + np.arange(drop)) % pool.ring] = NULL_BLOCK
                pool.free(desc.window_blocks[:drop], behind=True)
                del desc.window_blocks[:drop]
                desc.window_first += drop
                self.rows_written += 1

    def _trim_window(self, desc) -> None:
        """The window-pool blocks past ``desc``'s length go back (a rewind, a
        reservation made for rows that were never written)."""
        pool = self.window_pool
        keep = max(0, -(-desc.seen_tokens // pool.block_size) - desc.window_first)
        extra = desc.window_blocks[keep:]
        if extra:
            start = desc.window_first + keep
            self.state_table[desc.row, (start + np.arange(len(extra))) % pool.ring] = NULL_BLOCK
            del desc.window_blocks[keep:]
            pool.free(extra)
            self.rows_written += 1

    def rewind_sequence(self, desc: DSSequenceDescriptor, n_tokens: int) -> None:
        """Drop the last ``n_tokens`` of ``desc``'s KV content: the
        positions past the new length are abandoned in place (the block
        tables make them unreachable — the next tokens overwrite them),
        the token log truncates to match, and trailing blocks beyond the
        new length return to the pool. Never rewinds into cached
        (shared) prefix content — those blocks are the trie's."""
        if n_tokens < 0:
            raise ValueError(f"cannot rewind by {n_tokens} tokens")
        if desc.seen_tokens - n_tokens < desc.cached_tokens:
            raise ValueError(
                f"sequence {desc.uid}: rewinding {n_tokens} of "
                f"{desc.seen_tokens} tokens would cross into the "
                f"{desc.cached_tokens}-token shared prefix")
        if n_tokens:
            desc.rewind(n_tokens)
        pool = self.window_pool
        if pool is not None and desc.window_first * pool.block_size > max(
                0, desc.seen_tokens - pool.window + 1):
            # the next row's window reaches into a block that has gone back: an ending's
            # rewind (the sequence is flushed next) may, a sequence that goes on may not
            desc.window_stale = True
        self.release_unused_blocks(desc)

    def release_unused_blocks(self, desc: DSSequenceDescriptor) -> None:
        """Free trailing blocks past ``desc``'s current length. Burst
        and verify reservations cover the worst case up front; variable
        acceptance and EOS-mid-burst rewinds can leave the tail unused,
        and holding it would charge the pool for KV nobody will write.
        Shared prefix blocks sit at the FRONT of the table and a live
        sequence always spans them (``seen_tokens >= cached_tokens``),
        so a trailing trim can never touch the trie's blocks."""
        needed = -(-desc.seen_tokens // self.kv_cache.block_size)
        needed = max(needed, desc.shared_blocks)
        self.kv_cache.free(self.trim_blocks(desc, needed))
        if self.window_pool is not None:
            self._trim_window(desc)

    def flush_sequence(self, uid) -> None:
        desc = self._untrack(uid)
        if self.prefix_cache is not None:
            self.prefix_cache.release(uid, desc)
        else:
            self.kv_cache.free(desc.blocks)
        if self.window_pool is not None:
            self.window_pool.free(desc.window_blocks)
            desc.window_blocks = []

    def drop_sequence(self, uid) -> DSSequenceDescriptor:
        """Stop tracking ``uid`` WITHOUT freeing or caching its blocks —
        the suspend path, where ownership moves to the host handle (the
        descriptor keeps its window-pool blocks too, for whoever frees them)."""
        return self._untrack(uid)
