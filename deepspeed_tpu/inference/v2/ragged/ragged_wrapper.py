"""Ragged batch assembly.

Capability match for the reference's
``deepspeed/inference/v2/ragged/ragged_wrapper.py``
(``RaggedBatchWrapper``: flat token buffer + per-sequence metadata the
kernels consume). TPU adaptation: every array is padded to the STATIC
shapes (max_tokens, max_seqs, max_blocks_per_seq) so the jitted step
compiles exactly once; padding tokens point at a dedicated pad slot
whose block table is all null blocks."""

import numpy as np

from deepspeed_tpu.inference.v2.ragged.kv_cache import NULL_BLOCK


class RaggedBatchWrapper:

    def __init__(self, max_tokens, max_seqs, max_blocks_per_seq, lora=False, seq_rows=0):
        self.max_tokens = max_tokens
        self.max_seqs = max_seqs
        self.max_blocks = max_blocks_per_seq
        # multi-tenant LoRA: also pack a per-sequence adapter-slot row.
        # Strictly opt-in — off, the packed vector is byte-identical to
        # the pre-LoRA wire format (the DS_LORA=0 kill-switch contract).
        self.lora = bool(lora)
        # a model kind with per-sequence state beyond the block table (its
        # ``seq_rows`` int32 values a sequence: ``desc.state_row``) packs them
        # too; 0 for every other kind, whose vector is unchanged
        self.seq_rows = int(seq_rows)
        # The batch's arrays live as long as the wrapper, and ``clear`` resets in
        # place what the last batch wrote. Not for the allocations' sake: numpy
        # lets go of the interpreter lock around every allocation of a KB or
        # more, every copy or fill of more than 500 elements and every fancy
        # index, and in a serving process the clients the last step woke are
        # all waiting for that lock - each such call in the pump's pack cost
        # 30-60 us on the chip's host, where it costs 1-2 alone (PERF.md, PR 47).
        # So a batch is laid with slices of what is live, and few calls.
        self.token_ids = np.zeros(max_tokens, np.int32)
        # pad tokens live in the extra pad slot (row max_seqs)
        self.token_seq = np.full(max_tokens, max_seqs, np.int32)
        self.token_pos = np.zeros(max_tokens, np.int32)
        self.block_tables = np.full((max_seqs + 1, max_blocks_per_seq), NULL_BLOCK, np.int32)
        self.last_index = np.zeros(max_seqs, np.int32)
        self.seq_valid = np.zeros(max_seqs, bool)
        if self.lora:
            # pad row (max_seqs) stays 0 = the base slot
            self.seq_adapters = np.zeros(max_seqs + 1, np.int32)
        if self.seq_rows:
            # pad row (and every row without a sequence) stays 0: padding's slot
            self.seq_state = np.zeros((max_seqs + 1, self.seq_rows), np.int32)
        self._cursor = 0
        self._order = []  # slots in insertion order

    def clear(self):
        tokens, rows = slice(0, self._cursor), slice(0, max(self._order, default=-1) + 1)
        self.token_ids[tokens] = 0
        self.token_seq[tokens] = self.max_seqs
        self.token_pos[tokens] = 0
        self.block_tables[rows] = NULL_BLOCK
        self.last_index[rows] = 0
        self.seq_valid[rows] = False
        if self.lora:
            self.seq_adapters[rows] = 0
        if self.seq_rows:
            self.seq_state[rows] = 0
        self._cursor = 0
        self._order = []

    @property
    def current_tokens(self):
        return self._cursor

    @property
    def current_sequences(self):
        return len(self._order)

    def insert_batch(self, first, seen, lens, tokens, block_rows=None, adapters=None,
                     seq_state=None):
        """Append a step's sequences at once: sequence ``i`` takes batch
        row ``first + i``, has ``seen[i]`` tokens in the KV cache already
        (its chunk's positions continue from there) and brings
        ``lens[i]`` new tokens; ``tokens``: every sequence's new tokens,
        one after the other. ``block_rows [n, max_blocks]``: each
        sequence's block ids padded with the null block, as the state
        manager's table keeps them; None where the caller has laid them
        in ``block_tables[first:first + n]`` itself (``DSStateManager.gather``
        with ``out``). ``adapters [n]`` / ``seq_state [n, seq_rows]``: what
        a wrapper with ``lora`` / ``seq_rows`` packs besides. Numpy over
        the whole batch: nothing here runs once a sequence."""
        lens = np.asarray(lens, np.int64)
        n, total = len(lens), len(tokens)
        if self._cursor + total > self.max_tokens:
            raise ValueError(f"ragged batch overflow: {self._cursor}+{total} > {self.max_tokens}")
        if first + n > self.max_seqs:
            raise ValueError(f"slot {first + n - 1} out of range")
        ends = self._cursor + np.cumsum(lens)
        sl, rows = slice(self._cursor, self._cursor + total), slice(first, first + n)
        self.token_ids[sl] = tokens
        self.token_seq[sl] = np.repeat(np.arange(first, first + n), lens)
        # a token's position: its sequence's seen tokens + its place in the chunk
        self.token_pos[sl] = np.arange(self._cursor, self._cursor + total) \
            - np.repeat(ends - lens - np.asarray(seen), lens)
        if block_rows is not None:
            self.block_tables[rows] = block_rows
        self.last_index[rows] = ends - 1
        self.seq_valid[rows] = True
        if self.lora:
            self.seq_adapters[rows] = adapters
        if self.seq_rows:
            self.seq_state[rows] = seq_state
        self._cursor += total
        self._order.extend(range(first, first + n))

    def insert_sequence(self, desc, tokens):
        """Append ``tokens`` (this step's chunk) for ``desc``; positions
        continue from the tokens already in the KV cache. A batch of one,
        its block row made here from ``desc.blocks``."""
        blocks = desc.blocks
        if len(blocks) > self.max_blocks:
            raise ValueError(f"sequence {desc.uid} owns {len(blocks)} blocks > "
                             f"max_blocks_per_seq={self.max_blocks} (context overflow)")
        row = np.full((1, self.max_blocks), NULL_BLOCK, np.int32)
        row[0, :len(blocks)] = blocks
        self.insert_batch(desc.slot, [desc.seen_tokens], [len(tokens)],
                          np.asarray(tokens, np.int32), row,
                          adapters=[getattr(desc, "adapter_slot", 0)],
                          seq_state=[desc.state_row] if self.seq_rows else None)

    def finalize(self):
        """→ dict of numpy arrays for the device step: the wrapper's own,
        good until its next ``clear``."""
        return {
            "token_ids": self.token_ids,
            "token_seq": self.token_seq,
            "token_pos": self.token_pos,
            "block_tables": self.block_tables,
            "last_index": self.last_index,
            "num_tokens": np.int32(self._cursor),
        }

    def finalize_packed(self, bucket=None):
        """→ ONE flat int32 vector holding the whole batch's metadata —
        a single host→device transfer per step instead of six (the
        reference keeps its metadata in a pinned host struct copied as
        one buffer, ragged_wrapper.py:292 / csrc fast host descriptors;
        this is the same idea for an RPC/PCIe hop). Unpack on device
        with :func:`unpack_batch`.

        ``bucket`` pads the token arrays to that length instead of
        ``max_tokens`` — shape bucketing: a pure-decode step (≤ max_seqs
        real tokens) compiles to a program ~max_tokens/max_seqs× smaller
        than the prefill-chunk program, so decode rounds don't pay the
        full token budget in MLP flops and KV-gather traffic. Any length
        from the batch's tokens to ``max_tokens`` is a program of its own;
        the engine picks from ``engine_v2.put_ladder``."""
        bucket = self.max_tokens if bucket is None else int(bucket)
        if not self._cursor <= bucket <= self.max_tokens:
            raise ValueError(f"bucket {bucket} must cover the {self._cursor} batched "
                             f"tokens and not exceed max_tokens={self.max_tokens} — "
                             f"a smaller bucket would silently truncate the batch")
        parts = [
            self.token_ids[:bucket], self.token_seq[:bucket], self.token_pos[:bucket],
            self.block_tables.ravel(), self.last_index,
            np.asarray([self._cursor], np.int32)]
        if self.lora:
            parts.append(self.seq_adapters)
        if self.seq_rows:
            parts.append(self.seq_state.ravel())
        return np.concatenate(parts)

    def slots_in_order(self):
        return list(self._order)


def unpack_batch(packed, max_seqs, max_blocks, lora=False, sampled=False, seq_rows=0):
    """Inverse of :meth:`RaggedBatchWrapper.finalize_packed` in traced
    code: static slices of the flat vector back into the step's dict.
    The token-bucket length is derived from the vector's static size, so
    each bucket traces (and compiles) its own specialization. ``lora``
    must match the wrapper's flag: on, the trailing per-sequence
    adapter-slot row is parsed out as ``seq_adapters``. ``sampled``
    parses the per-sequence sampling-spec rows the engine's packed
    sampled step appends AFTER the wrapper's own fields (6 int32 rows of
    ``max_seqs``, see ``inference.structured.sampling``) as
    ``sample_meta`` — strictly opt-in, so the greedy wire format stays
    byte-identical to the pre-sampling one. ``seq_rows``: the wrapper's;
    above 0, ``seq_state [max_seqs + 1, seq_rows]`` is parsed out."""
    ms, mb = max_seqs, max_blocks
    extra = ((ms + 1) if lora else 0) + (ms + 1) * seq_rows
    if sampled:
        extra += 6 * ms
    mt = (packed.shape[0] - (ms + 1) * mb - ms - 1 - extra) // 3
    o = 0
    token_ids = packed[o:o + mt]; o += mt
    token_seq = packed[o:o + mt]; o += mt
    token_pos = packed[o:o + mt]; o += mt
    block_tables = packed[o:o + (ms + 1) * mb].reshape(ms + 1, mb); o += (ms + 1) * mb
    last_index = packed[o:o + ms]; o += ms
    num_tokens = packed[o]
    out = {"token_ids": token_ids, "token_seq": token_seq, "token_pos": token_pos,
           "block_tables": block_tables, "last_index": last_index,
           "num_tokens": num_tokens}
    if lora:
        o += 1
        out["seq_adapters"] = packed[o:o + ms + 1]
        o += ms
    if seq_rows:
        o += 1
        out["seq_state"] = packed[o:o + (ms + 1) * seq_rows].reshape(ms + 1, seq_rows)
    if sampled:
        out["sample_meta"] = packed[packed.shape[0] - 6 * ms:]
    return out
