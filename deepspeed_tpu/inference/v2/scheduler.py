"""Continuous-batching scheduler (Dynamic SplitFuse).

Capability match for the scheduling policy the reference ships in
DeepSpeed-MII on top of ``InferenceEngineV2`` (and described in the
DeepSpeed-FastGen paper): every engine step carries a fixed token
budget; running (decode) sequences get one token each first, and the
remaining budget is filled with chunks of pending prompts — long
prompts are SPLIT across steps, decodes are FUSED into prefill steps,
so step latency stays flat and the MXU stays fed.

A step is also fitted to the **blocks that are free** (``_plan``,
``_plan_burst``): whoever admits requests may let in more than the pool
holds at their worst case (``serving/admission.CapacityGate`` commits what
a request holds, not what it may reach), so a decode row whose next token
opens a block that is not there waits a step - it keeps its token, the rows
beside it run, end and free blocks - a prompt chunk is cut to the blocks
there are, and a burst is as long as the pool can reserve. ``engine.put``
is never asked for blocks the pool has not got. When no row can run at all,
:meth:`DynamicSplitFuseScheduler.preempt_for_room` gives up one request,
for its owner to run again from its tokens (preemption by recompute)."""

from collections import OrderedDict, deque
import functools

import numpy as np

from deepspeed_tpu.utils import tracing


class Request:

    def __init__(self, uid, prompt_tokens, max_new_tokens, priority=0, spec=True,
                 adapter_id=None, sample=None, schema=None, breakpoints=()):
        self.uid = uid
        # token offsets of the prompt where a prefix shared with other requests ends (a
        # system prompt's length): the engine keeps a snapshot of the sequence's state
        # there, for a model kind that has such state and a prefix cache (engine.prefix_match)
        self.breakpoints = tuple(int(b) for b in breakpoints)
        self.prompt = list(np.atleast_1d(np.asarray(prompt_tokens)).tolist())
        self.max_new_tokens = max_new_tokens
        self.priority = int(priority)  # larger = scheduled first
        # multi-tenant LoRA: which adapter serves this request (None =
        # base model); bound to a hot slot at admission
        self.adapter_id = adapter_id
        # per-request sampling spec (None = the scheduler-wide default):
        # rides the packed batch as data, so mixed specs share programs
        self.sample = dict(sample) if sample else None
        # per-request decode constraint (a CompiledSchema bound to the
        # engine's StructuredStore at admission); None = unconstrained
        self.schema = schema
        # per-request speculative-decoding opt-out: False rides along in
        # verify bursts without drafts of its own (engine-level spec
        # support still decides whether drafting happens at all)
        self.spec = bool(spec)
        self.prefill_cursor = 0  # prompt tokens already scheduled
        # radix prefix cache: leading prompt tokens whose KV was reused
        # from the cache (prefill skips them — the cursor starts there)
        self.prefix_cached_tokens = 0
        self.prefix_checked = False
        # life-cycle stamps for the request record (utils/tracing.py):
        # when the request first got a place in a step, the seq of that
        # step's record and of the one that gave its first token, and
        # how many steps carried a chunk of its prompt
        self.first_scheduled_ns = None
        self.first_scheduled_seq = 0
        self.first_token_seq = 0
        self.prefill_steps = 0
        self.generated = []
        self.next_token = None  # decode token awaiting scheduling
        # tokens dispatched to the device but not yet fenced/accepted —
        # ``len(generated) + _inflight`` is the request's true generation
        # frontier; 0 whenever no burst of its is in flight
        self._inflight = 0
        self.done = False
        # paused requests hold scheduler state but take no step work —
        # their KV may be suspended to host (gateway preemption)
        self.paused = False
        # tokens it had in the cache when it was given up for room
        # (preempt_for_room): what running it again computes a second time
        self.recomputed = 0

    @property
    def prefilling(self):
        return self.prefill_cursor < len(self.prompt)


class DynamicSplitFuseScheduler:
    """Drives an :class:`InferenceEngineV2` to completion over a request
    stream. ``sample_fn(logits) -> token`` picks the next token
    (default greedy argmax); generation stops at ``eos_token_id`` or
    ``max_new_tokens``."""

    def __init__(self, engine, token_budget=None, sample_fn=None, eos_token_id=None,
                 max_burst=16, sampling=None, on_tokens=None):
        self.engine = engine
        self.budget = int(token_budget or engine.max_tokens)
        if self.budget > engine.max_tokens:
            raise ValueError(f"budget {self.budget} > engine max_tokens {engine.max_tokens}")
        # default greedy sampling runs ON DEVICE (engine.put sample="greedy"):
        # one int32 per sequence crosses to the host instead of a vocab-wide
        # logits row. A custom sample_fn needs the logits, so it opts out.
        if sampling is not None and sample_fn is not None:
            raise ValueError("pass either sampling (on-device) or sample_fn (host), not both")
        # sampling: {"temperature": t, "top_k": k, "top_p": p} → stochastic
        # sampling ON DEVICE (put(sample=dict) / sampling bursts); None with
        # no sample_fn → on-device greedy. Both keep vocab-wide logits off
        # the host; a custom sample_fn opts out of both.
        # normalize {} to None: an empty dict would mean greedy on one
        # path and unfiltered T=1.0 sampling on the other
        self._sampling = dict(sampling) if sampling else None
        if self._sampling is not None:
            from deepspeed_tpu.inference.sampling import validate_sample_spec
            validate_sample_spec(self._sampling)
        self._device_greedy = sample_fn is None
        # multi-step decode: when every live request is decoding, run up
        # to max_burst steps in one compiled program (on-device sampled
        # tokens feed the next step) — one host sync per burst instead of
        # per token. 1 disables bursting. Only for device-side sampling:
        # a custom sample_fn needs each step's logits on the host.
        self.max_burst = max(1, int(max_burst)) if self._device_greedy else 1
        self.sample_fn = sample_fn or (lambda logits: int(np.argmax(logits)))
        self.eos_token_id = eos_token_id
        # on_tokens(rows, in_flight): the serving gateway's streaming hook,
        # one call for an engine call's worth of accepted tokens. rows:
        # [(uid, token, done)] in the order accepted — a stream's tokens in
        # order, its done row last; in_flight: a program is on the device
        # meanwhile. None = no streaming.
        self.on_tokens = on_tokens
        # accepted and not handed over yet: the rows wait for the NEXT
        # program's dispatch, and the engine runs hand_over while that
        # program runs (engine.while_running) — nothing the next plan needs
        # is in the hook, so the device does not wait for it. An engine
        # that never runs it loses nothing: see _ran
        self._rows = []
        engine.while_running = None if on_tokens is None \
            else functools.partial(self.hand_over, in_flight=True)
        # uids whose last token was accepted (their rows may still be in
        # _rows): the owner takes the list and retires them, which frees
        # their room for the next admission at once
        self.ended = []
        self.requests = OrderedDict()  # uid -> Request
        # how many bursts stay in flight, unfetched (the engine config's
        # async_burst.depth): the pump dispatches burst k+1 while burst k
        # executes on device and fences one burst late. 0 fetches every
        # burst in the call that dispatched it and the pipeline stays empty
        self.async_depth = int(getattr(engine, "async_burst_depth", 0))
        self._pipeline = deque()  # (AsyncBurstHandle, [Request]) oldest first
        # seq of the step record the engine wrote last (0: it writes none)
        self.last_step_seq = 0
        # what the last _plan() knows and the engine cannot: prompt tokens
        # in the step, and the requests that got their first place in it
        self._planned_prompt_tokens = 0
        self._planned_first = []
        self.block_size = int(engine.block_size)
        # decode rows that waited a step for a block (a count that only grows)
        self.rows_held_back = 0

    def add_request(self, uid, prompt_tokens, max_new_tokens=16, priority=0,
                    spec=True, adapter_id=None, sample=None, schema=None, breakpoints=()):
        if uid in self.requests:
            raise ValueError(f"uid {uid} already queued")
        if sample is not None:
            from deepspeed_tpu.inference.sampling import validate_sample_spec
            validate_sample_spec(sample)  # typed, pre-admission
            sample = dict(sample)
            if "seed" not in sample:
                # resolve the seed AT ADMISSION from the engine's
                # deterministic stream: the emitted tokens then depend
                # only on (seed, position), never on how later
                # scheduling interleaves this request with others
                draw = getattr(self.engine, "draw_seed", None)
                if draw is not None:
                    sample["seed"] = draw()
        if schema is not None and sample is None and not self._device_greedy:
            raise ValueError(f"uid {uid}: schema-constrained requests sample "
                             f"on device; host sample_fn cannot enforce the "
                             f"constraint")
        req = Request(uid, prompt_tokens, max_new_tokens, priority=priority,
                      spec=spec, adapter_id=adapter_id, sample=sample,
                      schema=schema, breakpoints=breakpoints)
        if not req.prompt:
            raise ValueError(f"uid {uid}: empty prompt can never be scheduled")
        if schema is not None:
            # bind BEFORE queueing, same discipline as adapters: schema
            # compile/capacity errors surface typed at admission
            bind = getattr(self.engine, "bind_schema", None)
            if bind is None or getattr(self.engine, "structured", None) is None:
                raise ValueError(f"uid {uid}: schema given but constrained "
                                 f"decoding is disabled (config.structured / "
                                 f"DS_CONSTRAINED)")
            bind(uid, schema)
        if adapter_id:
            # bind BEFORE queueing: a cold adapter promotes (or raises
            # typed capacity/unknown errors) here, not mid-step — and the
            # lease guarantees the slot survives until the engine flush
            bind = getattr(self.engine, "bind_adapter", None)
            if bind is None:
                raise ValueError(f"uid {uid}: adapter_id={adapter_id} but the "
                                 f"engine has no adapter support")
            bind(uid, adapter_id)
        self.requests[uid] = req
        # KV-tier prefetch kick: stage any demoted prefix extension for
        # this prompt off-thread NOW, so the host→device copy overlaps
        # the wait until _plan first schedules the request
        prefetch = getattr(self.engine, "prefetch_prefix", None)
        if prefetch is not None:
            prefetch(req.prompt)
        return req

    @property
    def has_work(self):
        return any(not r.done for r in self.requests.values())

    def _live(self):
        """Schedulable requests, highest priority first (stable: equal
        priorities keep arrival order). Paused requests hold their state
        but take no step work."""
        live = [r for r in self.requests.values() if not r.done and not r.paused]
        return sorted(live, key=lambda r: -r.priority)

    def cancel(self, uid):
        """Stop a request now: mark done, release its engine state (live
        KV or suspended host copy). Returns the tokens generated so far."""
        r = self.requests.get(uid)
        if r is None:
            raise KeyError(f"unknown request {uid}")
        self._drain_if_inflight(r)
        self.hand_over()  # its stream holds every token this returns
        if not r.done:
            r.done = True
            r.next_token = None
            try:
                self.engine.flush(uid)
            except KeyError:
                pass  # nothing prefilled yet — no engine state to drop
        return list(r.generated)

    def retire(self, uid):
        """Remove a finished request from the table (long-running serving
        must not grow the request dict without bound)."""
        r = self.requests.get(uid)
        if r is None:
            raise KeyError(f"unknown request {uid}")
        if not r.done:
            raise ValueError(f"request {uid} is still live — cancel() first")
        del self.requests[uid]
        return r

    def pause(self, uid):
        """Preempt a live request: suspend its KV to host memory (freeing
        pool blocks for other sequences) and stop scheduling it until
        :meth:`unpause`. Returns True when KV was actually offloaded
        (False for a request that never reached the engine)."""
        r = self.requests.get(uid)
        if r is None:
            raise KeyError(f"unknown request {uid}")
        if r.done or r.paused:
            raise ValueError(f"request {uid} is not pausable (done={r.done})")
        self._drain_if_inflight(r)
        self.hand_over()  # no dispatch may follow: every request paused
        if r.done:
            raise ValueError(f"request {uid} finished while its pipelined "
                             f"bursts drained — not pausable")
        r.paused = True
        if self.engine.query(uid) is not None:
            self.engine.suspend(uid)
            return True
        return False

    def unpause(self, uid):
        """Resume a paused request; restores suspended KV (needs pool
        room — caller checks ``engine.suspended_blocks(uid)`` first)."""
        r = self.requests.get(uid)
        if r is None:
            raise KeyError(f"unknown request {uid}")
        if not r.paused:
            raise ValueError(f"request {uid} is not paused")
        if self.engine.is_suspended(uid):
            self.engine.resume(uid)
        r.paused = False

    def _free_blocks(self):
        """Blocks the pool can give a step: the free list and what the
        prefix cache gives back on demand."""
        return int(self.engine.free_blocks) + int(getattr(self.engine, "evictable_blocks", 0))

    def _fit(self, uid, want, free):
        """→ ``(tokens, blocks)``: how many of the ``want`` tokens ``uid``
        asks a place for fit the room in its last block and ``free`` more
        blocks, and the blocks they claim."""
        state = self.engine.query(uid)
        room = state[1] if state is not None else 0
        if want <= room:
            return want, 0
        need = -(-(want - room) // self.block_size)
        if need <= free:
            return want, need
        return room + free * self.block_size, free

    def _plan(self):
        """One step's (uids, token-chunks) within the budget and the free
        blocks: decodes first, then prompt chunks (splitting long prompts).
        A decode row that needs a block the pool has not got keeps its
        token and waits; a prompt chunk is cut to the blocks there are."""
        uids, chunks = [], []
        budget = self.budget
        max_seqs = self.engine.max_seqs
        live = self._live()
        self._planned_prompt_tokens = 0
        self._planned_first = []
        # no request claims more than a block a decode row, or its chunk's: where the
        # pool has that for every row the step fits as it is, and nobody is asked
        block = self.block_size
        avail, claimed = self._free_blocks(), 0
        tight = avail < len(live) + -(-budget // block)
        # 1) decodes: one token each
        for r in live:
            if r.next_token is not None and budget > 0 and len(uids) < max_seqs:
                if tight:
                    fits, need = self._fit(r.uid, 1, avail - claimed)
                    if not fits:
                        self.rows_held_back += 1
                        continue
                    claimed += need
                uids.append(r.uid)
                chunks.append([r.next_token])
                r.next_token = None
                budget -= 1
        # 2) prefills: fill the remaining budget with prompt chunks
        for r in live:
            if budget <= 0 or len(uids) >= max_seqs:
                break
            if r.prefilling:
                if not r.prefix_checked:
                    if tight and avail - claimed < 1:
                        continue    # a prompt that has not begun begins with a block
                    # first time this request is scheduled: ask the engine
                    # for its longest cached prompt prefix — prefill then
                    # starts at the first uncached token (batch positions
                    # follow the descriptor's seen_tokens automatically)
                    r.prefix_checked = True
                    r.first_scheduled_ns = tracing.now_ns()
                    self._planned_first.append(r)
                    match = getattr(self.engine, "prefix_match", None)
                    if match is not None and r.prefill_cursor == 0:
                        named = {"breakpoints": r.breakpoints} if r.breakpoints else {}
                        r.prefix_cached_tokens = int(match(r.uid, r.prompt, **named))
                        r.prefill_cursor = r.prefix_cached_tokens
                        if r.prefix_cached_tokens:
                            # the blocks it leased are no longer the cache's to give
                            if not tight:   # what the rows placed may claim, at the most
                                tight = True
                                claimed = len(uids) + -(-(self.budget - budget) // block)
                            avail = self._free_blocks()
                take = min(budget, len(r.prompt) - r.prefill_cursor)
                if r.breakpoints:   # a chunk ends where a snapshot is to be taken
                    take = self.engine.chunk_cut(r.uid, r.prefill_cursor, take)
                if tight:
                    take, need = self._fit(r.uid, take, avail - claimed)
                    if take < 1:
                        continue
                    claimed += need
                chunk = r.prompt[r.prefill_cursor:r.prefill_cursor + take]
                r.prefill_cursor += take
                r.prefill_steps += 1
                self._planned_prompt_tokens += take
                uids.append(r.uid)
                chunks.append(chunk)
                budget -= take
        return uids, chunks

    def preempt_for_room(self):
        """No live row could run: each needs a block, none is free and
        nothing is in flight that would free one. Give up the request that
        is cheapest to make again - fewest tokens in the cache, and never
        one of a higher priority than the rest - and → its ``Request``, off
        the table, with ``recomputed`` the tokens it had in the cache (its
        owner runs it again with ``prompt + generated`` as its prompt and
        what is left of ``max_new_tokens``); its stream holds every token it
        generated. None where there is nobody to give up: one live request
        alone always fits the pool it was admitted to."""
        live = self._live()
        held = {}
        for r in live:
            state = self.engine.query(r.uid)
            if state is not None and state[0] + state[1] > 0:
                held[r.uid] = state[0]
        if len(live) < 2 or not held:
            return None
        # _live() is stable: among equals the youngest goes
        victim = min((r for r in reversed(live) if r.uid in held),
                     key=lambda r: (r.priority, held[r.uid]))
        self.hand_over()    # nothing is in flight where no row could be planned
        victim.recomputed = held[victim.uid]
        self.engine.flush(victim.uid)
        del self.requests[victim.uid]
        return victim

    def _ran(self):
        """The engine call just returned: note the seq of the step record
        it wrote, so that what is accepted next can point at it. Rows still
        waiting were not handed over while its program ran (an engine
        without ``while_running``): they go now, before this call's."""
        self.hand_over()
        rec = getattr(self.engine, "last_step", None)
        self.last_step_seq = rec.seq if rec is not None else 0
        return rec

    def hand_over(self, in_flight=False):
        """Give the streaming hook the rows accepted since the last
        hand-over, in one call; → how many. Whoever stops the next dispatch
        from coming calls this first, so no row waits for it."""
        rows = self._rows
        if not rows:
            return 0
        self._rows = []
        with tracing.phase("sched.deliver"):
            self.on_tokens(rows, in_flight)
        return len(rows)

    def _plan_burst(self, rows):
        """Burst length for ``rows``, or None when the burst path does
        not apply this round. ``_inflight`` stands in for generated
        tokens not fenced yet (0 with nothing in flight; the engine's
        ``seen_tokens`` advanced at dispatch, so the context-room term
        needs no correction)."""
        if (self.max_burst < 2 or not rows or len(rows) > self.engine.max_seqs
                or len(rows) > self.budget):  # burst must respect the per-step
            # token budget too: one decode token per live request per
            # burst step, same bound _plan enforces
            return None
        states = [self.engine.query(r.uid) for r in rows]
        k = min(self.max_burst,
                min(r.max_new_tokens - len(r.generated) - r._inflight for r in rows),
                self.engine.max_ctx_tokens - max(seen for seen, _ in states))
        if k < 2:
            return None
        k = 1 << (k.bit_length() - 1)  # power-of-two bursts: each distinct
        # k compiles its own scan program, so an arbitrary tail (15, 14,
        # 13...) would compile once per value; rounding down bounds the
        # set to log2(max_burst) programs
        # ... and as long as the pool can reserve up front: k tokens a row less the
        # room in its last block, in whole blocks
        free = self._free_blocks()
        if free < len(rows) * -(-k // self.block_size):
            room = np.fromiter((room for _, room in states), np.int64, len(states))
            while k >= 2 and int((-(-np.maximum(k - room, 0) // self.block_size)).sum()) > free:
                k >>= 1
            if k < 2:
                return None
        if not self.engine.can_burst([r.uid for r in rows], k):
            # The engine's own check (its window pool's too): too tight to
            # reserve k tokens per sequence up front. The stepwise path needs
            # at most one block per sequence per step and EOS flushes free
            # blocks between steps, so fall back. (A pre-check, not try/except: a
            # failure inside the compiled burst would land after state
            # mutation + KV donation and is not recoverable.)
            return None
        return k

    def _try_burst(self):
        """All live requests decoding → run a k-step decode burst; None
        when the burst path doesn't apply this round. At depth 0 the
        burst is fetched in the call and accepted at once. Otherwise it
        joins the pipeline — entry tokens chained on the device from the
        burst before — and the oldest burst is fenced once more than
        ``async_depth`` are in flight; whatever breaks the chain (live
        set changed, tail too short, pool too tight, a fenced row
        finished) drains it."""
        with tracing.phase("sched.plan"):
            live = self._live()
            prev, prev_rows = self._pipeline[-1] if self._pipeline else (None, None)
            # a chained burst continues the rows of the burst before; a
            # cold start needs every row's last token on the host
            fits = live == prev_rows if prev is not None \
                else all(r.next_token is not None for r in live)
            k = self._plan_burst(live) if fits else None
            if k is not None:
                uids = [r.uid for r in live]
                entry = None if prev is not None else [r.next_token for r in live]
                sample = self._sample_arg(live)
        if k is None:
            return self._drain_pipeline() if prev is not None else None
        for r in live:
            r.next_token = None
            r._inflight += k
        if not self.async_depth:
            # looked up on the engine at call time: whoever rebinds
            # decode_burst on the instance sees every fetched burst
            self._accept_burst(self.engine.decode_burst(uids, entry, k, sample=sample), live)
            return uids
        self._pipeline.append((self.engine.decode_burst_async(
            uids, entry, k, sample=sample, prev=prev), live))
        if len(self._pipeline) > self.async_depth:
            self._fence_one()
            if any(r.done for r in live):
                self._drain_pipeline()  # EOS discovered one burst late
        return uids

    def _accept_burst(self, toks, rows, settle=True):
        """Accept a fetched burst's ``[k, len(rows)]`` tokens, oldest
        step first. A row that ends mid-burst leaves the rest of its
        ``_inflight`` as debt: KV positions the burst advanced past the
        end, which hold post-EOS garbage the rewind reclaims."""
        self._ran()
        with tracing.phase("sched.accept"):
            for step_toks in toks:
                for r, tok in zip(rows, step_toks):
                    if r.done:
                        continue  # ended in an earlier step; later rows are discarded
                    r._inflight -= 1
                    self._accept_token(r, int(tok), settle=settle)

    def _spec_of(self, r):
        """The sampling spec governing request ``r``: its own, else the
        scheduler-wide default; None = greedy."""
        return r.sample if r.sample is not None else self._sampling

    def _sample_arg(self, live):
        """The engine ``sample=`` argument for a batch over ``live``:
        per-row specs when any row samples (mixed greedy rows stay
        ``None`` — the packed program argmaxes them), else None for the
        plain greedy program."""
        specs = [self._spec_of(r) for r in live]
        return specs if any(s is not None for s in specs) else None

    def _try_spec_burst(self):
        """All live requests decoding on device on an engine with
        speculative decoding armed → draft with the n-gram drafter and
        score entry + drafts in ONE compiled verify forward — greedy
        acceptance under greedy decoding, rejection-sampled acceptance
        under per-sequence sampling (bit-identical to the spec-off
        stream either way); None when the speculative path doesn't
        apply this round (no drafts found, a schema-bound request in
        the batch, budget too tight…) — the plain k-step burst then
        gets its chance."""
        engine = self.engine
        spec = getattr(engine, "spec", None)
        if spec is None or not self._device_greedy:
            return None
        with tracing.phase("sched.plan"):
            live = self._live()
            if (not live or len(live) > engine.max_seqs
                    or any(r.next_token is None for r in live)
                    # constrained sequences never verify: their drafts were
                    # proposed without the DFA mask
                    or any(r.schema is not None for r in live)):
                return None
            n = len(live)
            # each sequence enters the verify batch as a (d+1)-token chunk,
            # so the shared d is bounded by the per-step token budget…
            d_cap = self.budget // n - 1
            # …and by context room for EVERY live sequence: all rows write
            # d+1 KV positions regardless of their own draft count
            for r in live:
                d_cap = min(d_cap, engine.max_ctx_tokens
                            - engine.query(r.uid)[0] - 1)
            if d_cap < 1:
                return None
            max_lens = [min(d_cap, r.max_new_tokens - len(r.generated) - 1)
                        if r.spec else 0 for r in live]
            uids = [r.uid for r in live]
            drafts = engine.propose_drafts(uids, [[r.next_token] for r in live],
                                           max_lens)
            d = max((len(dr) for dr in drafts), default=0)
            if d < 1:
                return None
            # pad the shared draft length up to a power of two (within the
            # caps): dlen masks the padding, so acceptance is unchanged, and
            # the verify-program set stays log2-bounded instead of compiling
            # once per distinct max-draft-length the drafter happens to find
            d = min(1 << (d - 1).bit_length(), d_cap)
            if not engine.can_burst(uids, d + 1):
                return None  # pool too tight: fall back (see _try_burst)
            entry = [[r.next_token] for r in live]
            sample = self._sample_arg(live)
        toks, acc = engine.verify_burst(uids, entry, drafts, sample=sample)
        self._ran()
        with tracing.phase("sched.accept"):
            for r in live:
                r.next_token = None
            for j, r in enumerate(live):
                a = int(acc[j])
                for e in range(a + 1):
                    if r.done:
                        break  # EOS among the accepted run; rest discarded
                    # the verify advanced KV by a+1; ending at emitted index
                    # e leaves a-e post-EOS tokens for the rewind to reclaim
                    self._accept_token(r, int(toks[j, e]), unused_tokens=a - e)
        return uids

    def _accept_token(self, r, tok, unused_tokens=0, settle=True):
        """Record a generated token; finish on EOS/max_new_tokens (single
        copy of the completion semantics for the stepwise, burst,
        pipelined and speculative paths). ``unused_tokens``: KV positions
        the engine advanced past this token (burst/verify reservations
        run to their planned end) on top of the request's ``_inflight``
        debt. ``settle=False`` leaves the engine side of an ending to
        :meth:`_drain_pipeline`: younger bursts are still running over
        the sequence's KV reservation. What the next plan needs is done
        here; what a client sees is a row left for :meth:`hand_over`."""
        r.generated.append(tok)
        if len(r.generated) == 1:
            r.first_token_seq = self.last_step_seq
        if r.schema is not None:
            # the authoritative host DFA advances ONLY for accepted
            # tokens — burst tails discarded after EOS/max_new never
            # touch it, so the state the next batch packs stays right
            self.engine.advance_schema(r.uid, tok)
        if (self.eos_token_id is not None and tok == self.eos_token_id) \
                or len(r.generated) >= r.max_new_tokens:
            r.done = True
            r.next_token = None
            r._inflight += unused_tokens
            if settle:
                self._settle(r)
        else:
            r.next_token = tok
        if self.on_tokens is not None:
            self._rows.append((r.uid, tok, r.done))
            if r.done:
                self.ended.append(r.uid)

    def _settle(self, r):
        """The engine side of an ending: rewind the KV positions
        dispatched past the request's last token (``_inflight``), so
        retire frees them and the prefix cache never content-addresses
        post-EOS garbage, then flush."""
        if r._inflight:
            self.engine.rewind(r.uid, r._inflight)
            r._inflight = 0
        self.engine.flush(r.uid)

    # ------------------------------------------------------ the pipeline
    def _drain_if_inflight(self, r):
        """Settle the whole pipeline when ``r`` has unfenced bursts in
        it (cancel/pause must observe the request's final state)."""
        if r._inflight:
            self._drain_pipeline()

    def _fence_one(self):
        """Fence the OLDEST in-flight burst (the one device→host copy it
        ever pays) and accept its tokens; rows that end skip the tail —
        their ``_inflight`` debt is settled at drain time."""
        handle, rows = self._pipeline.popleft()
        self._accept_burst(handle.fetch(), rows, settle=False)
        return [r.uid for r in rows]

    def _drain_pipeline(self):
        """Fence every in-flight burst in dispatch order, then settle
        the rows that ended, as the fetched form does at accept time."""
        uids = []
        rows = self._pipeline[-1][1] if self._pipeline else []
        while self._pipeline:
            uids = self._fence_one()
        for r in rows:
            if r.done:
                self._settle(r)
        return uids

    def step(self):
        """Schedule + run one engine step; returns the uids stepped. What
        it accepts is handed to the streaming hook while the NEXT step's
        program runs — or here, when this step dispatched nothing or left
        bursts in flight (a pipeline fences a burst late as it is)."""
        stepped = self._step()
        if not stepped or self.async_depth:
            self.hand_over()
        return stepped

    def _step(self):
        """The order of the decode paths is decided here and nowhere else.
        The drafter goes first, and a running pipeline yields to it: it
        proposes from tokens the host has fetched, which a pipeline
        learns one burst late, so with speculative decoding armed the
        burst in flight is drained before the next is planned and the
        drafter gets the turn it gets at depth 0, before every burst.
        Without a drafter the pipeline continues (or drains) before
        anything else — the stepwise path needs the fenced host state."""
        if self._pipeline and getattr(self.engine, "spec", None) is not None:
            return self._drain_pipeline()
        if not self._pipeline:
            stepped = self._try_spec_burst()
            if stepped is not None:
                return stepped
        stepped = self._try_burst()
        if stepped is not None:
            return stepped
        with tracing.phase("sched.plan"):
            uids, chunks = self._plan()
            if not uids:
                return []
            if self._device_greedy:
                sample = self._sample_arg([self.requests[u] for u in uids]) or "greedy"
        if self._device_greedy:
            out = self.engine.put(uids, chunks, sample=sample)
        else:
            out = self.engine.put(uids, chunks)
        rec = self._ran()
        if rec is not None:
            # _plan knows which chunks are prompt; the engine cannot tell a
            # one-token chunk from a decode token
            rec.n_prompt_tokens = self._planned_prompt_tokens
        for r in self._planned_first:
            r.first_scheduled_seq = self.last_step_seq
        with tracing.phase("sched.accept"):
            for uid, row in zip(uids, out):
                r = self.requests[uid]
                if r.prefilling:
                    continue  # mid-prompt chunk: its last-token logits are unused
                self._accept_token(r, int(row) if self._device_greedy else self.sample_fn(row))
        if rec is not None and rec.n_tokens > self.engine.max_seqs:
            # a prompt step ran, and built its own program if it was the first of its
            # size: have the engine build the other sizes a prompt step of this budget
            # can land on in the same mode, so that a compile stops traffic once, here,
            # and a warm-up need not know the sizes (nothing to do once they are built)
            self.engine.build_put_programs(self.budget)
        return uids

    def run_to_completion(self, max_steps=10000):
        """→ {uid: generated tokens} after all requests finish."""
        steps = 0
        while self.has_work:
            stepped = self.step()
            steps += 1
            if steps > max_steps or (not stepped and self.has_work):
                raise RuntimeError("scheduler stalled")
        return {uid: list(r.generated) for uid, r in self.requests.items()}
