"""The window pool's host side alone (no engine, no device program): the
allocator and the state manager's ring table (``ragged/kv_cache.WindowPool``,
``ragged_manager.DSStateManager``) under steps as the engine makes them -
reserve, gather, advance, release behind - and the admission gate on either
pool (``serving/admission.CapacityGate``)."""

import numpy as np
import pytest

from deepspeed_tpu.inference.v2.ragged.kv_cache import NULL_BLOCK, BlockedKVCache, WindowPool
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.serving.admission import CapacityGate, RequestTooLargeError

W, BS, BUDGET = 512, 64, 512


def manager(window_blocks=64, window=W, bs=BS, budget=BUDGET, tracked=4, blocks=2048):
    pool = WindowPool(window, bs, budget, window_blocks)
    cache = BlockedKVCache(1, blocks, bs, 1, 8)
    return DSStateManager(cache, tracked, max_blocks_per_seq=512, seq_rows=pool.ring,
                          window_pool=pool), pool


def step(sm, uid, rows):
    """One step of ``rows`` rows for ``uid`` as the engine packs it → the
    blocks the sequence held inside the step."""
    desc = sm.get_or_create_sequence(uid)
    sm.reserve([desc], [desc.blocks_needed(rows)])
    sm.reserve_window([desc], sm.window_need([desc], rows))
    held = len(desc.window_blocks)
    ring = sm.gather([desc])[1][0]
    first, last = max(0, desc.seen_tokens - sm.window_pool.window + 1), desc.seen_tokens + rows - 1
    for b in range(first // sm.window_pool.block_size, last // sm.window_pool.block_size + 1):
        # every block the step's rows read or write is in the ring, where the kernel looks
        assert ring[b % sm.window_pool.ring] == desc.window_blocks[b - desc.window_first] != NULL_BLOCK
    desc.advance(rows)
    sm.release_behind([desc])
    return held


def test_the_bounds_are_the_issues():
    pool = WindowPool(W, BS, BUDGET, 64)
    assert pool.bound(1) == -(-W // BS) + 1 == 9
    assert pool.bound(BUDGET) == 17 <= -(-(W + BUDGET) // BS) + 1 and pool.ring == 17
    assert WindowPool(8, 4, 16, 8).bound(1) == 3


@pytest.mark.parametrize("chunks", [(512, 512, 300), (64,), (1,), (511, 2), (200, 512)])
def test_a_sequence_never_holds_more_than_its_bound(chunks):
    """Prompt chunks, then a long decode: inside a step of ``k`` rows at most
    ``bound(k)`` blocks, between steps at most ``bound(1)``, at any length;
    the full pool's table grows as it always did."""
    sm, pool = manager()
    for rows in chunks:
        assert step(sm, 1, rows) <= pool.bound(rows)
        assert len(sm.query(1).window_blocks) <= pool.bound(1)
    for _ in range(3 * W):
        assert step(sm, 1, 1) <= pool.bound(1)
    desc = sm.query(1)
    assert desc.seen_tokens == sum(chunks) + 3 * W
    assert len(desc.blocks) == -(-desc.seen_tokens // BS)              # the full layers' table
    assert desc.window_first == (desc.seen_tokens - W + 1) // BS
    assert pool.in_use == len(desc.window_blocks) and pool.high_water <= pool.bound(max(chunks))
    assert pool.released == desc.window_first
    sm.flush_sequence(1)
    assert pool.in_use == 0 and sm.kv_cache.free_blocks == 2047
    assert not sm.state_table.any()


def test_released_blocks_are_taken_by_others_and_bursts_hold_their_rows():
    sm, pool = manager(window_blocks=1 + 2 * 10)          # two decoding sequences and no more
    step(sm, 1, 512), step(sm, 2, 100)
    taken = set()
    for _ in range(40):
        assert step(sm, 1, 32) <= pool.bound(32)          # a burst of 32: its rows reserved up front
        assert step(sm, 2, 32) <= pool.bound(32)
        taken |= set(sm.query(2).window_blocks)
    assert pool.released > pool.num_blocks                 # the pool went round more than once
    assert taken & set(range(1, 9))                       # blocks 1's prompt held first
    assert set(sm.query(1).window_blocks).isdisjoint(sm.query(2).window_blocks)
    with pytest.raises(ValueError, match="only .* free"):
        step(sm, 3, 512)


def test_rewind_trims_ahead_and_marks_what_it_cannot_give_back():
    sm, pool = manager()
    step(sm, 1, 600)
    desc = sm.query(1)
    sm.reserve_window([desc], sm.window_need([desc], 100))      # a burst's rows, then EOS at once
    desc.advance(100)
    ahead = len(desc.window_blocks)
    sm.rewind_sequence(desc, 100)
    assert desc.seen_tokens == 600 and len(desc.window_blocks) < ahead and not desc.window_stale
    assert desc.window_first + len(desc.window_blocks) == -(-600 // BS)
    step(sm, 1, 1)                                              # goes on
    sm.rewind_sequence(desc, 300)                               # into what the window released
    assert desc.window_stale
    with pytest.raises(ValueError, match="rewound past the blocks its window had released"):
        sm.window_need([desc], 1)
    sm.flush_sequence(1)
    assert pool.in_use == 0


def test_drop_keeps_the_blocks_with_the_descriptor_and_clears_the_row():
    sm, pool = manager()
    step(sm, 1, 700)
    row = sm.query(1).row
    desc = sm.drop_sequence(1)
    assert desc.window_blocks and pool.in_use == len(desc.window_blocks)
    assert not sm.state_table[row].any()
    pool.free(desc.window_blocks)
    assert pool.in_use == 0


def test_release_unused_blocks_keeps_both_tables():
    sm, pool = manager()
    desc = sm.get_or_create_sequence(1)
    sm.reserve([desc], [desc.blocks_needed(200)])
    sm.reserve_window([desc], sm.window_need([desc], 200))
    desc.advance(70)                                            # EOS after 70 of 200 rows
    sm.release_unused_blocks(desc)
    assert len(desc.blocks) == len(desc.window_blocks) == 2 and pool.in_use == 2


class _engine:
    """What the gate reads of an engine: the full pool's count as it stands,
    and what a sequence has laid."""
    block_size, max_ctx_tokens = BS, 17408

    def __init__(self, sm, pool):
        self.state_manager, self.window_pool = sm, pool

    @property
    def free_blocks(self):
        return self.state_manager.kv_cache.free_blocks

    def query(self, uid):
        desc = self.state_manager.query(uid)
        return desc and (desc.seen_tokens, len(desc.blocks) * BS - desc.seen_tokens)


def test_the_gate_admits_on_what_a_sequence_holds_in_either_pool():
    """The window pool's commitment is what a sequence holds there already
    (``bound(1) + 1`` at any length) and stays; the full pool's is the
    prompt's blocks, then what the engine says the sequence has laid."""
    sm, pool = manager(window_blocks=1 + 8 + 2 * 10, tracked=8)
    gate = CapacityGate(_engine(sm, pool), BUDGET)
    assert gate.usable_window_blocks == 20 and gate.window_footprint(6000, 400) == 10
    assert gate.window_footprint(100, 28) == 2                  # a short request holds what it is
    assert gate.try_commit(1, 6000, 400) and gate.try_commit(2, 600, 400)
    assert gate.committed_blocks == 94 + 10 and gate.committed_worst == 100 + 16
    assert not gate.try_commit(3, 900, 100)                     # the window pool refuses
    assert gate.refused_by == {"kv_blocks": 0, "window_blocks": 1, "sequences": 0}
    assert pool.gate_refused == 1
    gate.release(2)
    assert gate.try_commit(3, 900, 100)
    gate.release(3), gate.release(1)
    assert gate.committed_window_blocks == gate.committed_blocks == gate.active == 0
    # the full pool lets in two prompts whose worst cases (100 + 100 of 199) kept the
    # second out, and refuses what would leave less than the reserve
    sm, pool = manager(window_blocks=512, tracked=8, blocks=200)
    gate = CapacityGate(_engine(sm, pool), BUDGET)
    assert gate.usable_blocks == 199 and gate.reserve(3) == 8 + 1
    assert gate.try_commit(1, 6000, 400) and gate.try_commit(2, 6000, 400)         # 94 + 94
    assert not gate.try_commit(3, 600, 400)                                        # + 10 + 9
    assert gate.refused_by["kv_blocks"] == 1 and pool.gate_refused == 0
    step(sm, 1, 512)                            # what a sequence has laid the engine counts
    assert gate.committed_blocks == 86 + 94 and gate.headroom() == 199 - 8 - 86 - 94
    with pytest.raises(RequestTooLargeError, match="KV blocks"):
        gate.check_feasible(16000, 1000)
    small = CapacityGate(_engine(*manager(window_blocks=1 + 8 + 5)), BUDGET)
    with pytest.raises(RequestTooLargeError, match="window-pool blocks"):
        small.check_feasible(6000, 400)


def test_what_the_gate_admits_a_steps_rows_fit():
    """The gate's promise: whatever it admitted, a step of the budget's rows
    dealt over those sequences finds its blocks."""
    tracked = 6
    sm, pool = manager(window_blocks=1 + 8 + tracked * 10, tracked=tracked)
    gate = CapacityGate(_engine(sm, pool), BUDGET)
    assert all(gate.try_commit(uid, 3000, 1000) for uid in range(tracked))
    assert not gate.try_commit(tracked, 3000, 1000)
    rng = np.random.RandomState(0)
    for uid in range(tracked):
        step(sm, uid, 512)
    for _ in range(200):
        cuts = np.sort(rng.choice(np.arange(1, BUDGET), tracked - 1, replace=False))
        deal = np.diff(np.concatenate([[0], cuts, [BUDGET]]))
        descs = [sm.query(u) for u in range(tracked)]
        sm.reserve_window(descs, sm.window_need(descs, deal))          # one call, as a step does
        for desc, rows in zip(descs, deal):
            desc.advance(int(rows))
        sm.release_behind(descs)
    assert pool.high_water <= pool.num_blocks - 1


def test_a_manager_without_a_window_pool_is_the_one_it_was():
    cache = BlockedKVCache(1, 16, BS, 1, 8)
    sm = DSStateManager(cache, 2, max_blocks_per_seq=8)
    desc = sm.get_or_create_sequence(1)
    sm.allocate_for(desc, 100)
    assert sm.window_pool is None and sm.state_table is None and desc.window_blocks == []
    sm.rewind_sequence(desc, 0)
    sm.flush_sequence(1)
    with pytest.raises(ValueError, match="columns"):
        DSStateManager(cache, 2, seq_rows=3, window_pool=WindowPool(8, 4, 16, 8))
