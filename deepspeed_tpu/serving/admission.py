"""Admission control + backpressure for the serving gateway.

Two layers sit between ``submit()`` and the engine:

1. :class:`AdmissionQueue` — the bounded wait queue. When full, the
   configured policy decides: ``reject`` (typed error to the caller),
   ``shed`` (evict the lowest-priority *queued* request to make room for
   a strictly higher-priority one), or ``block`` (the submitting thread
   waits for room, bounded by a timeout).

2. :class:`CapacityGate` — KV-block accounting on what requests hold. A
   request is handed to the scheduler once its *prompt's* blocks fit the
   pool beside what every live request holds and a small reserve derived
   from the engine; its answer's blocks are claimed as it grows, and the
   scheduler fits every step to the blocks that are free, so the engine's
   "KV pool exhausted" runtime error cannot fire mid-flight and wedge the
   pump. Requests that could never fit — even on an idle engine — are
   rejected at ``submit()`` with an actionable
   :class:`RequestTooLargeError` instead of queueing forever.
"""

import threading
import time


# ---------------------------------------------------------------------- errors
class ServingError(RuntimeError):
    """Base for all gateway-surfaced request errors.

    Every serving error is machine-readable so routing layers (the fleet
    router) can act on it without string matching:

    - ``reason`` — a stable snake_case identifier for the failure class;
    - ``retry_elsewhere`` — whether a *different* replica could
      plausibly serve this request (a full queue here is not a full
      queue everywhere) or the condition is fleet-wide / terminal
      (too large for the model, cancelled, deadline blown);
    - ``details`` — numeric hints attached at the raise site (queue
      depth, evictable KV blocks, estimated wait) that let a router
      pick between "retry elsewhere", "back off and retry here", and
      "shed fleet-wide".
    """

    reason = "serving_error"
    retry_elsewhere = False

    def __init__(self, message, **details):
        super().__init__(message)
        self.details = details


class GatewayClosedError(ServingError):
    """submit() after drain()/shutdown() began."""
    reason = "gateway_closed"
    retry_elsewhere = True  # this replica is leaving; peers may accept


class QueueFullError(ServingError):
    """The admission queue is full and the policy could not make room.

    ``details`` carries ``queue_depth`` (entries waiting here) and — when
    raised through ``ServingGateway.submit`` — ``evictable_blocks`` and
    ``est_wait_s`` so a router can weigh waiting against rerouting."""
    reason = "queue_full"
    retry_elsewhere = True


class RequestTooLargeError(ServingError):
    """The request can never fit this engine's KV pool / context window.
    Fleet-wide shed for homogeneous replicas — retrying elsewhere cannot
    help."""
    reason = "too_large"
    retry_elsewhere = False


class RequestShedError(ServingError):
    """This queued request was evicted to admit a higher-priority one."""
    reason = "shed"
    retry_elsewhere = True


class RequestCancelledError(ServingError):
    """The client cancelled the request before completion."""
    reason = "cancelled"
    retry_elsewhere = False


class DeadlineExceededError(ServingError):
    """deadline_ms expired before the request completed."""
    reason = "deadline"
    retry_elsewhere = False


class GatewayFailedError(ServingError):
    """The pump thread died; the engine state is no longer trustworthy."""
    reason = "gateway_failed"
    retry_elsewhere = True


# ---------------------------------------------------------------- capacity
class CapacityGate:
    """Static feasibility + admission on the blocks requests **hold**.

    A request's commitment is what it holds in the pool, and it moves with
    it: on admission its **prompt's** blocks (``ceil((prompt + 1) /
    block)``: they are certain, so a prompt is never let into a pool that
    cannot hold it), nothing for the answer it has not made, and as it
    generates the blocks it has. The gate keeps no sum of its own for that:
    it reads the engine's count (``free_blocks`` + ``evictable_blocks``) and
    takes off the prompt blocks of requests it admitted that the engine has
    not laid yet (:meth:`headroom`). A request is admitted while the
    headroom, less its prompt's blocks, stays at or above a **reserve**
    (:meth:`reserve`): the blocks one engine call is expected to claim,
    from ``block_size``, ``token_budget``, ``max_burst`` and the live
    count. Nothing holds back a request when every admitted request's
    worst case (``prompt + max_new_tokens``) fits beside its own - such a
    pool cannot run dry - or when nothing is active: :meth:`check_feasible`
    has refused at ``submit()`` what cannot run alone.

    What makes that safe is the scheduler's half
    (``DynamicSplitFuseScheduler._plan``): a step is fitted to the blocks
    that are free - a decode row whose next token opens a block that is not
    there waits a step, a prompt chunk is cut to the blocks there are - and
    when no row can run the gateway preempts one request by recompute
    (``ServingGateway._preempt_for_room``), so ``engine.put`` is never asked
    for blocks the pool has not got.

    ``usable_blocks`` is snapshotted from an idle engine at gateway
    construction (evictable prefix-cache blocks are reclaimable capacity:
    a warm cache never shrinks what admission believes the pool can hold).

    An engine whose model kind has window layers has a second pool
    (``engine.window_pool``), whose commitment is already what a sequence
    holds: ``bound(1)`` blocks between steps whatever its length, one more
    inside a decode step or a short burst, and the rows of one step (the
    token budget, however the scheduler deals it) add ``ceil(token_budget /
    block_size)`` over all sequences - kept back from
    ``usable_window_blocks`` once, not committed a request. ``refused_by``
    counts what held a request back, by pool. An engine whose sequences own
    a slot of state each is also asked for the slot an arriving request will
    own: free, or one of the prefix cache's snapshots, which give way (LRU).
    """

    def __init__(self, engine, token_budget, pool="unified", max_burst=1):
        # which fleet pool this gate protects ("unified" | "prefill" |
        # "decode") — stamped into every rejection's details so the
        # router can steer (a saturated prefill pool means degrade or
        # re-pool, NOT retry the same gate)
        self.pool = str(pool)
        self.engine = engine
        self.block_size = int(engine.block_size)
        self.usable_blocks = self._engine_blocks()
        self.max_ctx_tokens = int(engine.max_ctx_tokens)
        self.max_tracked = int(engine.state_manager.max_tracked_sequences)
        self.token_budget = int(token_budget)
        self.max_burst = int(max_burst)
        # uid -> [prompt blocks the engine has yet to lay, worst-case blocks,
        # window-pool blocks]: every request holding a place
        self._admitted = {}
        self.committed_worst = 0
        self.window_pool = getattr(engine, "window_pool", None)
        self.committed_window_blocks = self.usable_window_blocks = 0
        if self.window_pool is not None:
            self.usable_window_blocks = int(self.window_pool.free_blocks) \
                - -(-self.token_budget // self.block_size)
        self.refused_by = {"kv_blocks": 0, "window_blocks": 0, "sequences": 0}
        # an engine whose sequences each own a slot of state (``ragged/slot_pool.py``): the
        # pool may hold the prefix cache's snapshots too, which a sequence can always take
        self.slot_pool = getattr(engine, "slot_pool", None)
        if self.slot_pool is not None:
            self.refused_by["slots"] = 0

    def _engine_blocks(self):
        """Blocks the pool can give right now: the free list and what the
        prefix cache gives back on demand (LRU)."""
        return int(self.engine.free_blocks) + int(getattr(self.engine, "evictable_blocks", 0))

    @property
    def active(self):
        """Requests holding a place."""
        return len(self._admitted)

    @property
    def committed_blocks(self):
        """Prompt blocks of admitted requests that the engine has not laid
        yet: all the gate holds back beyond the engine's own count."""
        awaited = 0
        for uid, held in self._admitted.items():
            if held[0]:
                state = self.engine.query(uid)
                laid = (state[0] + state[1]) // self.block_size if state is not None else 0
                if laid >= held[0]:
                    held[0] = 0     # prefilled: what it holds is in the engine's count
                else:
                    awaited += held[0] - laid
        return awaited

    def prompt_blocks(self, prompt_len):
        """Blocks a prompt and its first token hold."""
        return -(-(prompt_len + 1) // self.block_size)

    def footprint(self, prompt_len, max_new_tokens):
        """Worst-case KV blocks a request will ever hold."""
        return -(-(prompt_len + max_new_tokens) // self.block_size)

    def window_footprint(self, prompt_len, max_new_tokens):
        """Window-pool blocks a request holds outside a prompt chunk's own
        rows (0 without such a pool)."""
        if self.window_pool is None:
            return 0
        return min(self.footprint(prompt_len, max_new_tokens), self.window_pool.bound(1) + 1)

    def reserve(self, live):
        """Blocks kept free beside ``live`` requests: what one engine call
        is expected to claim - a prompt step's ``token_budget`` rows, and a
        burst of ``max_burst`` tokens over every live row."""
        return -(-self.token_budget // self.block_size) \
            + -(-live * self.max_burst // self.block_size)

    def headroom(self):
        """Blocks the pool can give, less the prompt blocks of admitted
        requests that the engine has not laid yet."""
        return self._engine_blocks() - self.committed_blocks

    def check_feasible(self, prompt_len, max_new_tokens):
        """Raise :class:`RequestTooLargeError` when the request could not
        run even on an idle engine (its worst case: nothing is preempted
        for a request that cannot end alone)."""
        if prompt_len < 1:
            raise RequestTooLargeError("empty prompt can never be scheduled")
        total = prompt_len + max_new_tokens
        if total > self.max_ctx_tokens:
            raise RequestTooLargeError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) = "
                f"{total} tokens exceeds the engine context window "
                f"({self.max_ctx_tokens}); shorten the prompt or lower "
                f"max_new_tokens",
                total_tokens=total, max_ctx_tokens=self.max_ctx_tokens,
                pool=self.pool)
        need = self.footprint(prompt_len, max_new_tokens)
        if need > self.usable_blocks:
            raise RequestTooLargeError(
                f"request needs {need} KV blocks ({total} tokens at block size "
                f"{self.block_size}) but the pool only has {self.usable_blocks} "
                f"— raise num_kv_blocks or shrink the request",
                needed_blocks=need, usable_blocks=self.usable_blocks,
                pool=self.pool)
        need = self.window_footprint(prompt_len, max_new_tokens)
        if need > self.usable_window_blocks:
            raise RequestTooLargeError(
                f"request needs {need} window-pool blocks but the pool only has "
                f"{self.usable_window_blocks} beside one step's rows — raise num_window_blocks",
                needed_blocks=need, usable_blocks=self.usable_window_blocks, pool=self.pool)

    def try_commit(self, uid, prompt_len, max_new_tokens, resumed_blocks=0):
        """Give ``uid`` a place; False when it does not fit right now
        (caller keeps it queued). ``resumed_blocks``: what a suspended
        request's resume lays at once (``engine.suspended_blocks``)."""
        need = max(self.prompt_blocks(prompt_len), int(resumed_blocks))
        worst = max(self.footprint(prompt_len, max_new_tokens), need)
        need_window = self.window_footprint(prompt_len, max_new_tokens)
        live = len(self._admitted)
        if live and self.committed_worst + worst > self.usable_blocks \
                and self.headroom() - need < self.reserve(live + 1):
            self.refused_by["kv_blocks"] += 1
            return False
        if self.committed_window_blocks + need_window > self.usable_window_blocks:
            self.refused_by["window_blocks"] += 1
            self.window_pool.gate_refused += 1
            return False
        if live + 1 > self.max_tracked:
            self.refused_by["sequences"] += 1
            return False
        if self.slot_pool is not None:
            # the slot this request will own, beside those of admitted requests that the
            # engine has not begun (a begun one holds its slot): free or the cache's to give
            awaited = sum(1 for held in self._admitted if self.engine.query(held) is None)
            if self.slot_pool.reclaimable_slots < awaited + 1:
                self.refused_by["slots"] += 1
                return False
        self._admitted[uid] = [need, worst, need_window]
        self.committed_worst += worst
        self.committed_window_blocks += need_window
        return True

    def release(self, uid):
        """``uid`` ended, was suspended or was preempted: its place is free
        (the blocks it held are the engine's to count)."""
        _, worst, need_window = self._admitted.pop(uid)
        self.committed_worst -= worst
        self.committed_window_blocks -= need_window


# ---------------------------------------------------------------- wait queue
class AdmissionQueue:
    """Bounded, priority-aware wait queue with a pluggable full-queue
    policy. Thread-safe; ``push`` runs on client threads, everything
    else on the pump thread."""

    def __init__(self, max_depth, policy, block_timeout_s=30.0):
        self.max_depth = int(max_depth)
        self.policy = policy
        self.block_timeout_s = float(block_timeout_s)
        self._entries = []  # arrival order; scheduling order is computed
        self._lock = threading.Lock()
        self._space = threading.Condition(self._lock)  # entry removed
        self._arrived = threading.Condition(self._lock)  # entry added
        self.closed = False

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def close(self):
        with self._lock:
            self.closed = True
            self._space.notify_all()
            self._arrived.notify_all()

    def push(self, entry):
        """Admit ``entry`` to the wait queue, applying the full-queue
        policy. Returns the entry that was shed to make room (caller
        must fail it), or None. Raises :class:`QueueFullError` /
        :class:`GatewayClosedError`."""
        with self._lock:
            if self.closed:
                raise GatewayClosedError("gateway is draining — not accepting requests")
            if len(self._entries) < self.max_depth:
                self._entries.append(entry)
                entry._depth_at_enqueue = len(self._entries)
                self._arrived.notify_all()
                return None
            if self.policy == "reject":
                raise QueueFullError(
                    f"admission queue full ({self.max_depth} waiting); retry "
                    f"later or raise serving.max_queue_depth",
                    queue_depth=len(self._entries), policy=self.policy)
            if self.policy == "shed":
                # evict the LOWEST-priority queued entry, youngest among
                # ties (older requests of equal priority keep their spot)
                victim = min(reversed(self._entries),
                             key=lambda e: e.priority)
                if victim.priority >= entry.priority:
                    raise QueueFullError(
                        f"admission queue full ({self.max_depth} waiting) and no "
                        f"queued request has priority < {entry.priority}",
                        queue_depth=len(self._entries), policy=self.policy)
                self._entries.remove(victim)
                self._entries.append(entry)
                entry._depth_at_enqueue = len(self._entries)
                self._arrived.notify_all()
                return victim
            # block: wait for room (deadline-bounded)
            deadline = time.monotonic() + self.block_timeout_s
            while len(self._entries) >= self.max_depth:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise QueueFullError(
                        f"admission queue stayed full for {self.block_timeout_s}s "
                        f"(policy=block)",
                        queue_depth=len(self._entries), policy=self.policy)
                self._space.wait(timeout=remaining)
                if self.closed:
                    raise GatewayClosedError(
                        "gateway is draining — not accepting requests")
            self._entries.append(entry)
            entry._depth_at_enqueue = len(self._entries)
            self._arrived.notify_all()
            return None

    def push_front(self, entry):
        """Put back, at the head of its priority level, a request the
        gateway had admitted and preempted for room: it was accepted long
        ago, so neither ``max_depth`` nor a closed queue refuses it."""
        with self._lock:
            self._entries.insert(0, entry)
            self._arrived.notify_all()

    def candidates(self):
        """Snapshot in scheduling order: highest priority first, FIFO
        within a priority level."""
        with self._lock:
            return sorted(self._entries, key=lambda e: -e.priority)

    def remove(self, entry):
        """Take ``entry`` out (admitted, cancelled, or expired). False if
        someone else already removed it."""
        with self._lock:
            try:
                self._entries.remove(entry)
            except ValueError:
                return False
            self._space.notify_all()
            return True

    def expired(self, now):
        """Entries whose deadline passed (still queued; caller removes)."""
        with self._lock:
            return [e for e in self._entries
                    if e.deadline is not None and now >= e.deadline]

    def wait_for_work(self, timeout):
        """Pump idle-wait: returns once an entry arrives / close / timeout."""
        with self._lock:
            if self._entries or self.closed:
                return
            self._arrived.wait(timeout=timeout)
