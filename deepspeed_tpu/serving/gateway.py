"""Request-level serving front-end over the v2 ragged engine.

``ServingGateway`` owns an :class:`InferenceEngineV2` plus a
:class:`DynamicSplitFuseScheduler` and runs a **pump loop** in a
background thread: clients ``submit()`` at any time from any thread and
get back a :class:`RequestHandle` that streams tokens as the engine
produces them. The pump overlaps host-side work (admission, deadline
checks, queue management) with device decode bursts — the structural fix
for the host-sync cadence that dominates ragged-serving wall time.

Layering (everything engine-side stays single-threaded in the pump):

    client threads --submit()--> AdmissionQueue --pump--> scheduler --> engine
                   <--handle.tokens() stream-- on_tokens(rows) <----+

A step's tokens reach their streams while the NEXT step's program runs:
the scheduler keeps the rows it accepted and the engine runs its hand-over
between that program's dispatch and its fetch, so the device does not wait
for what only a client reads (``_on_tokens``). What frees room - retiring a
finished request, its gate commitment - is done in the pass that accepted
its last token (``_retire``).

Admission is KV-block aware (:class:`CapacityGate`): a request enters
the scheduler when its prompt's blocks fit the pool beside what every
live request holds and a small reserve; its answer's blocks are claimed as
it grows. The scheduler fits every step to the blocks that are free (a
decode row that needs a block the pool has not got waits a step), so the
engine's "KV pool exhausted" error cannot wedge the pump; and when no row
can run, the request that is cheapest to make again is **preempted by
recompute** (``_preempt_for_room``): flushed, back at the head of the
queue with ``prompt + generated`` as its prompt, its stream unbroken.
Higher-priority requests may also *preempt* running lower-priority ones
(``allow_preemption``: KV suspended to host via ``engine.suspend``,
resumed when the pool has room again).

Lifecycle: ``drain()`` stops admission, finishes everything in flight,
stops the pump, and destroys the engine. A pump crash fails every
outstanding handle with :class:`GatewayFailedError` instead of hanging
clients.
"""

import itertools
import queue as _queue
import threading
import time
from collections import OrderedDict
from statistics import median

import numpy as np

from deepspeed_tpu.inference.v2.scheduler import DynamicSplitFuseScheduler
from deepspeed_tpu.serving.admission import (AdmissionQueue, CapacityGate,
                                             DeadlineExceededError, GatewayClosedError,
                                             GatewayFailedError, RequestCancelledError,
                                             RequestShedError)
from deepspeed_tpu.serving.config import ServingConfig
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.utils import tracing
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.sanitize import tracked_lock

_DONE = object()  # stream sentinel
_HANDOFF_OUTBOX = 64  # exported records kept (LRU) awaiting router pickup

# ServingConfig fields a tuned-config JSON (offline serving tuner) may
# override through DS_AUTOTUNE_CONFIG — the cheap serving-scope knobs;
# engine-scope knobs in the file need a rebuild and are applied by the
# deploy tooling via their DS_* env vars instead
_TUNABLE_SERVING_FIELDS = ("token_budget", "max_burst", "max_queue_depth")


def _apply_tuned_config(cfg):
    """When ``DS_AUTOTUNE_CONFIG`` points at a tuned-config JSON, fold
    its serving-scope knobs over ``cfg`` (validated copy). Unset — the
    overwhelmingly common case — returns ``cfg`` untouched."""
    from deepspeed_tpu.utils.env_registry import env_raw
    path = env_raw("DS_AUTOTUNE_CONFIG")
    if path is None or not str(path).strip():
        return cfg
    from deepspeed_tpu.autotuning.serving_tuner import load_tuned_config
    doc = load_tuned_config(path)
    overrides = {}
    for name, value in (doc.get("knobs") or {}).items():
        if not name.startswith("serving."):
            continue
        field = name.split(".", 1)[1]
        if field not in _TUNABLE_SERVING_FIELDS:
            raise ValueError(
                f"tuned config {path}: {name} is not a gateway-applicable "
                f"serving knob (expected one of "
                f"{['serving.' + f for f in _TUNABLE_SERVING_FIELDS]})")
        overrides[field] = value
    if not overrides:
        return cfg
    logger.info(f"serving: applying tuned config {path}: {overrides}")
    return type(cfg)(**{**cfg.model_dump(), **overrides})


class RequestHandle:
    """Client-side view of one in-flight request.

    ``tokens()`` iterates generated token ids as they stream out of the
    engine; it raises the terminal :class:`ServingError` when the request
    ended abnormally (shed / cancelled / deadline / gateway failure).
    ``result()`` blocks to completion and returns the full token list.
    """

    def __init__(self, uid, prompt, max_new_tokens, priority, deadline_s,
                 spec=True, adapter_id=None, sample=None, schema=None):
        self.uid = uid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.priority = priority
        # per-request speculative-decoding opt-out (engine support and
        # the DS_SPEC_DECODE kill switch still gate it globally)
        self.spec = bool(spec)
        # multi-tenant LoRA: serve this request through adapter_id's
        # weights (None = base model)
        self.adapter_id = adapter_id
        # on-device sampling spec (seed already resolved — replays and
        # failovers are uid-stable) and compiled constrained-decoding
        # schema; None/None = greedy unconstrained
        self.sample = sample
        self.schema = schema
        # life-cycle stamps, all time.perf_counter_ns() (utils/tracing.py):
        # the request record is written from them when the request ends
        self.submitted_ns = tracing.now_ns()
        self.admitted_ns = None
        self.admitted_seq = 0       # seq of the pump pass that admitted it
        self.first_token_ns = None
        self.last_token_ns = None
        # the same clock in seconds (time.perf_counter()), for deadlines
        self.submitted_at = self.submitted_ns / 1e9
        self.deadline = (self.submitted_at + deadline_s
                         if deadline_s is not None else None)
        self.status = "queued"  # queued|running|completed|cancelled|shed|deadline|failed
        self.error = None
        self.ttft_s = None
        self.queue_wait_s = None
        self._stream = _queue.Queue()
        self._collected = []
        self._done = threading.Event()
        self._cancel_cb = None  # wired by the gateway

    # ------------------------------------------------------------- client API
    def tokens(self, timeout=None):
        """Yield token ids as they are generated. Raises the terminal
        error for abnormal endings after yielding what was produced."""
        while True:
            item = self._stream.get(timeout=timeout)
            if item is _DONE:
                if self.error is not None:
                    raise self.error
                return
            yield item

    def result(self, timeout=None):
        """Block until the request finishes; return all generated token
        ids (raises the terminal error for abnormal endings)."""
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(f"request {self.uid} still running after {timeout}s")
        if self.error is not None:
            raise self.error
        return list(self._collected)

    def cancel(self):
        """Ask the gateway to stop this request (no-op once finished)."""
        if not self._done.is_set() and self._cancel_cb is not None:
            self._cancel_cb(self)

    @property
    def done(self):
        return self._done.is_set()

    # ------------------------------------------------------- gateway internals
    def _emit(self, token):
        self._collected.append(token)
        self._stream.put(token)

    def _finish(self, status, error=None):
        if self._done.is_set():
            return False
        self.status = status
        self.error = error
        self._done.set()
        self._stream.put(_DONE)
        return True


def _cpu_ms_between(prev, rec):
    """CPU time of the thread that ran both engine records between their
    last CPU marks (each record's ``ds.engine.fetch`` exit: its result on
    the host), in ms; None where a record has no mark or another thread
    ran it."""
    if not prev.cpu_marks or not rec.cpu_marks or prev.thread != rec.thread:
        return None
    return (rec.cpu_marks[-1][2] - prev.cpu_marks[-1][2]) / 1e6


class ServingGateway:

    def __init__(self, engine, config=None, monitor=None, auto_start=True):
        """``engine``: an idle :class:`InferenceEngineV2` (the gateway
        takes ownership — ``drain()`` destroys it). ``monitor``: any
        object with the ``Monitor.write_events(event_list)`` interface;
        serving metrics are published through it every
        ``metrics_interval_steps`` engine steps."""
        self.engine = engine
        self.config = _apply_tuned_config(config or ServingConfig())
        self.monitor = monitor
        cfg = self.config
        self.scheduler = DynamicSplitFuseScheduler(
            engine,
            token_budget=cfg.token_budget or None,
            eos_token_id=cfg.eos_token_id,
            max_burst=cfg.max_burst,
            sampling=cfg.sampling,
            on_tokens=self._on_tokens)
        self.metrics = ServingMetrics(window=cfg.metrics_window)
        # gauges and subsystem stats are read when somebody asks
        # (snapshot() / events()), not pushed on every pump pass
        # step and request records (utils/tracing.py) carry the engine's number
        self._engine_id = getattr(engine, "trace_id", 0)
        self.metrics.attach_sources(self._gauges, self._external,
                                    engine_id=self._engine_id)
        self._pump_seq = 0   # seq of the pump pass in progress
        # stall watch (pump thread only): the engine record the last pass
        # that ran a step ended with, None once the pump had nothing to run;
        # and what the thread spent waiting and in passes that did nothing
        # since the last kept pass
        self._last_engine_rec = None
        self._waited_ns = self._idle_passes = 0
        self._rows_held_back = 0    # the scheduler's count, as last read
        # disaggregated serving: a "prefill" gateway exports a KV
        # handoff record into a bounded outbox when a request finishes;
        # the fleet router claims it via take_handoff() and delivers it
        # to a "decode" gateway's import_handoff()
        self.role = cfg.role
        self._handoffs = OrderedDict()   # uid -> exported handoff record
        self._handoff_lock = tracked_lock(threading.Lock(),
                                          "ServingGateway._handoff_lock")
        self.gate = CapacityGate(engine, self.scheduler.budget, pool=cfg.role,
                                 max_burst=self.scheduler.max_burst)
        self.queue = AdmissionQueue(cfg.max_queue_depth, cfg.admission_policy,
                                    cfg.block_timeout_s)
        self._uids = itertools.count()
        self._active = {}    # uid -> handle, admitted to the scheduler
        self._paused = []    # uids preempted (KV suspended), admission order
        # uid -> (handle, its scheduler.Request): retired, and its last
        # tokens are still on their way to the stream (_on_tokens ends it)
        self._ending = {}
        self._cancels = []   # handles with a pending cancel request
        self._cancel_lock = tracked_lock(threading.Lock(),
                                         "ServingGateway._cancel_lock")
        self._state = "running"  # running|draining|stopped|failed
        self._state_lock = tracked_lock(threading.Lock(),
                                        "ServingGateway._state_lock")
        # live weight refresh: a staged swap the pump applies once the
        # engine is quiet (admission held, in-flight streams finish)
        self._pending_refresh = None
        self._refresh_lock = tracked_lock(threading.Lock(),
                                          "ServingGateway._refresh_lock")
        self._wake = threading.Event()
        self._pump_stop = False
        self._pump_thread = None
        # serving autotuner hooks: an optional traffic recorder (attach
        # via attach_recorder()) and the online SLO controller. Both off
        # is the default and costs one attribute check per submit — the
        # DS_AUTOTUNE=0 pipeline is otherwise byte-identical
        self._recorder = None
        self.controller = None
        from deepspeed_tpu.autotuning.online import (OnlineSLOController,
                                                     autotune_enabled)
        if autotune_enabled(cfg):
            self.controller = OnlineSLOController(self, cfg.autotune)
        if auto_start:
            self.start()

    # ---------------------------------------------------------------- client
    def submit(self, prompt_tokens, max_new_tokens=None, priority=None,
               deadline_ms=None, spec=True, adapter_id=None, sample=None,
               schema=None, cache_breakpoints=()):
        """Accept a request from any thread → :class:`RequestHandle`.
        ``spec=False`` opts this request out of speculative decoding
        (it still rides in verify batches, just without drafts).
        ``adapter_id`` routes the request through that LoRA adapter's
        weights (None = base model). ``sample`` is a per-request
        on-device sampling spec (``{"temperature", "top_k", "top_p",
        "seed"}``, all optional); when it carries no ``seed`` one is
        derived deterministically from the request uid, so trace
        replays and fleet failovers draw the identical stream.
        ``schema`` constrains generation to a JSON schema (dict), a
        regex (str), or a precompiled
        :class:`~deepspeed_tpu.inference.structured.grammar.CompiledSchema`;
        raw schemas compile through the process-wide schema cache over
        ``config.token_strings``. ``cache_breakpoints``: token offsets of
        the prompt where a prefix shared with other requests ends (a system
        prompt's length): behind a model kind with recurrent state and a
        prefix cache, the state is snapshotted at the last block boundary at
        or before each, so the next request with that prefix starts there.

        Raises :class:`RequestTooLargeError` when the request can never
        fit this engine, :class:`QueueFullError` per the admission
        policy, :class:`GatewayClosedError` after ``drain()`` began,
        ``UnknownAdapterError`` when no tier of the engine's adapter
        store can serve ``adapter_id``, and ``ValueError`` /
        ``SchemaCompileError`` for malformed sampling specs or schemas
        — all typed, all BEFORE the request queues.
        """
        prompt = [int(t) for t in np.atleast_1d(np.asarray(prompt_tokens))]
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.config.default_max_new_tokens)
        prio = int(priority if priority is not None
                   else self.config.default_priority)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if self._state in ("draining", "stopped"):
            raise GatewayClosedError("gateway is draining — not accepting requests")
        if self._state == "failed":
            raise GatewayFailedError("gateway pump died; rebuild the gateway")
        if adapter_id:
            # typed unknown-adapter rejection at the door — NOT a
            # mid-pump failure after the request already queued
            knows = getattr(self.engine, "knows_adapter", None)
            if knows is None or not knows(adapter_id):
                from deepspeed_tpu.serving.lora.store import UnknownAdapterError
                self.metrics.count("rejected_unknown_adapter")
                raise UnknownAdapterError(
                    f"adapter {adapter_id} is not registered with this "
                    f"replica (hot, host, or published)",
                    adapter_id=int(adapter_id))
        raw_schema = None
        if sample is not None:
            # typed pre-admission validation: a malformed spec fails at
            # the door, never mid-pump after the request already queued
            from deepspeed_tpu.inference.sampling import validate_sample_spec
            try:
                validate_sample_spec(sample)
            except ValueError:
                self.metrics.count("rejected_bad_sample")
                raise
            sample = dict(sample)
        if schema is not None:
            from deepspeed_tpu.inference.structured.grammar import CompiledSchema
            if getattr(self.engine, "structured", None) is None:
                self.metrics.count("rejected_schema")
                raise ValueError(
                    "schema given but constrained decoding is disabled on "
                    "this replica (config.structured.enabled / DS_CONSTRAINED)")
            if isinstance(schema, CompiledSchema):
                raw_schema = schema.schema
            else:
                # compile at the door through the process-wide cache:
                # repeat schemas hit; malformed ones raise typed here
                raw_schema = schema
                toks = self.config.token_strings
                if not toks:
                    self.metrics.count("rejected_schema")
                    raise ValueError(
                        "raw schema given but config.token_strings is unset — "
                        "pass a precompiled CompiledSchema or configure the "
                        "tokenizer surface")
                from deepspeed_tpu.inference.structured.store import schema_cache
                try:
                    schema = schema_cache().get_or_compile(
                        schema, toks, self.config.eos_token_id)
                except Exception:
                    self.metrics.count("rejected_schema")
                    raise
        try:
            self.gate.check_feasible(len(prompt), max_new)
        except Exception:
            self.metrics.count("rejected_too_large")
            raise
        uid = next(self._uids)
        if sample is not None and "seed" not in sample:
            # resolve the seed AT THE GATEWAY, derived from the request
            # uid: the recorder below sees the RESOLVED spec, so a trace
            # replay (or a failover resubmit reusing the uid) draws the
            # bit-identical stream
            from deepspeed_tpu.inference.structured.prng import derive_seed
            from deepspeed_tpu.utils.env_registry import env_int
            sample["seed"] = derive_seed(env_int("DS_SEED"), uid)
        recorder = self._recorder
        if recorder is not None:
            # record OFFERED traffic (pre-admission): a replay must let
            # the candidate config make its own admission decisions
            recorder.record(prompt, max_new, prio, adapter_id=adapter_id,
                            sample=sample, schema=raw_schema)
        handle = RequestHandle(uid, prompt, max_new, prio,
                               deadline_ms / 1e3 if deadline_ms is not None else None,
                               spec=spec, adapter_id=adapter_id,
                               sample=sample, schema=schema)
        handle._cancel_cb = self._request_cancel
        # where a prefix shared with other requests ends (a system prompt's length), for the
        # prefix cache of a model kind with recurrent state (scheduler.Request.breakpoints)
        handle.cache_breakpoints = tuple(int(b) for b in cache_breakpoints)
        try:
            shed = self.queue.push(handle)
        except Exception as e:
            from deepspeed_tpu.serving.admission import QueueFullError
            if isinstance(e, QueueFullError):
                self.metrics.count("rejected_queue_full")
                # estimated-wait hints for routing layers: how deep the
                # line is, how much KV the prefix cache could give back,
                # and a rough wait guess from observed queue-wait times —
                # enough for a router to pick "retry elsewhere" over
                # "shed fleet-wide" without string-matching the message
                qw = self.metrics.queue_wait
                e.details.setdefault("queue_depth", len(self.queue))
                e.details.update(
                    pool=self.gate.pool,
                    evictable_blocks=int(getattr(self.engine,
                                                 "evictable_blocks", 0)),
                    active=self.gate.active,
                    est_wait_s=round(qw.total_ms / qw.count / 1e3, 4)
                    if qw.count else None)
                if adapter_id:
                    # adapter-miss hint: a router seeing hot=False should
                    # prefer a replica whose hot set already holds this
                    # adapter over re-queueing here behind a promotion
                    has = getattr(self.engine, "has_adapter", None)
                    e.details.update(
                        adapter_id=int(adapter_id),
                        adapter_hot=bool(has(adapter_id)) if has else False)
            raise
        self.metrics.count("submitted")
        self.metrics.gauge_peak("queue_depth_peak",
                                getattr(handle, "_depth_at_enqueue", 1))
        if shed is not None:
            self._end(shed, "shed", RequestShedError(
                f"request {shed.uid} (priority {shed.priority}) evicted from a "
                f"full queue by request {handle.uid} (priority {prio})"))
        # KV-tier prefetch kick at ADMISSION, not at scheduling: the
        # tier's worker stages host→device copies of this prompt's
        # demoted prefix while the request waits in the queue, so the
        # copy is already on device when the pump acquires the prefix
        prefetch = getattr(self.engine, "prefetch_prefix", None)
        if prefetch is not None:
            prefetch(prompt)
        if adapter_id:
            # same overlap trick for cold adapters: stage the padded
            # slabs on the store's worker while the request queues
            pf = getattr(self.engine, "prefetch_adapter", None)
            if pf is not None:
                pf(adapter_id)
        self._wake.set()
        return handle

    def _request_cancel(self, handle):
        with self._cancel_lock:
            self._cancels.append(handle)
        self._wake.set()

    # ------------------------------------------------------ trace recording
    def attach_recorder(self, recorder):
        """Record every feasible ``submit()`` into ``recorder`` (a
        :class:`deepspeed_tpu.autotuning.trace.TraceRecorder`) until
        :meth:`detach_recorder`. Returns the recorder for chaining."""
        self._recorder = recorder
        return recorder

    def detach_recorder(self):
        """Stop recording; returns the detached recorder (or None)."""
        recorder, self._recorder = self._recorder, None
        return recorder

    # ------------------------------------------------------------- lifecycle
    def start(self):
        if self._pump_thread is not None:
            return
        with self._state_lock:
            self._pump_stop = False
        self._pump_thread = threading.Thread(target=self._run, name="ds-serve-pump",
                                             daemon=True)
        self._pump_thread.start()
        if self.controller is not None:
            self.controller.start()

    def drain(self, timeout=None):
        """Stop admitting, finish everything in flight (queued requests
        included — they were accepted), then stop the pump and destroy
        the engine. Raises :class:`TimeoutError` if in-flight work does
        not finish in time (engine left alive for inspection)."""
        timeout = self.config.drain_timeout_s if timeout is None else timeout
        with self._state_lock:
            if self._state in ("stopped", "failed"):
                return
            self._state = "draining"
        if self.controller is not None:
            self.controller.stop()
        self.queue.close()
        self._wake.set()
        thread = self._pump_thread
        if thread is None:
            # manual-pump mode (auto_start=False): drive the pump inline
            deadline = time.monotonic() + timeout
            while self._active or self._ending or len(self.queue) > 0:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"drain: in-flight requests still running after "
                        f"{timeout}s ({len(self._active)} active, "
                        f"{len(self.queue)} queued)")
                self._pump_once()
        else:
            # the pump thread exits on its own once draining finds
            # nothing in flight (see _run)
            thread.join(timeout=timeout)
            if thread.is_alive():
                raise TimeoutError(
                    f"drain: in-flight requests still running after {timeout}s "
                    f"({len(self._active)} active, {len(self.queue)} queued)")
            self._pump_thread = None
        if self._state != "failed":
            with self._state_lock:
                self._state = "stopped"
            self.metrics.detach_sources()  # last observed values stay readable
            self.engine.destroy()

    def shutdown(self):
        """Hard stop: fail every outstanding request and destroy the
        engine. For aborts; prefer :meth:`drain` for clean exits."""
        with self._state_lock:
            if self._state == "stopped":
                return
            self._state = "draining"  # reject new submits while we tear down
        self.queue.close()
        self._stop_pump()
        self._fail_outstanding(GatewayClosedError("gateway shut down"))
        with self._state_lock:
            self._state = "stopped"
        self.metrics.detach_sources()
        self.engine.destroy()

    def kill(self, error=None):
        """Hard, ungraceful death — the fault-injection / fleet-crash
        primitive. Stops the pump, fails EVERY outstanding request with
        ``error`` (default :class:`GatewayFailedError`), marks the
        gateway ``failed`` (a killed replica is not a cleanly stopped
        one) and releases engine HBM. Unlike a real pump crash this is
        synchronous: when it returns, no handle is left hanging."""
        with self._state_lock:
            if self._state in ("stopped", "failed"):
                return
            self._state = "failed"
        self.queue.close()
        self._stop_pump()
        self._fail_outstanding(error or GatewayFailedError("gateway killed"))
        self.metrics.detach_sources()
        try:
            self.engine.destroy()
        except Exception:
            logger.exception("engine destroy failed during kill()")

    def shed_queued(self, error):
        """Fail every request still WAITING in the admission queue with
        the typed ``error``; active (streaming) requests are untouched.
        This is the queued-work half of a rolling-restart handoff: the
        fleet router sees a retry-elsewhere error and replays each shed
        request on a peer replica from its prompt (nothing was streamed
        yet, so nothing can double-emit). Returns the number shed."""
        n = 0
        for entry in self.queue.candidates():
            if entry._collected:
                continue    # preempted for room with part of its answer sent: it ends here
            if self.queue.remove(entry) and self._end(entry, "failed", error):
                n += 1
        return n

    # -------------------------------------------------------- weight refresh
    @property
    def weight_version(self):
        """The engine's adopted weight version (0 = as-built)."""
        engine = self.engine
        return int(getattr(engine, "weight_version", 0)) if engine is not None else 0

    def refresh_weights(self, params, version, timeout=None):
        """Live, no-drain weight refresh: stage ``params`` for the pump
        to swap in once the engine is quiet. Admission is HELD (queued
        requests wait, nothing is shed) while in-flight streams finish on
        the old weights; the pump then swaps the param tree in place —
        no engine rebuild, no recompilation — invalidates every trace of
        old-version KV (prefix trie, tier-2 store, handoff outbox), and
        re-opens admission on the new version. Blocks until applied.

        Raises the swap's error if it failed (the pump marks the gateway
        failed — a mid-swap crash must look like a crash, not a silently
        half-refreshed replica) and :class:`TimeoutError` when in-flight
        work does not quiesce in time (the staged swap is withdrawn and
        admission resumes on the old version — nothing was adopted)."""
        timeout = self.config.drain_timeout_s if timeout is None else timeout
        if self._state != "running":
            raise GatewayClosedError(
                f"weight refresh on a {self._state} gateway")
        pending = {"params": params, "version": int(version),
                   "done": threading.Event(), "error": None}
        with self._refresh_lock:
            if self._pending_refresh is not None:
                raise RuntimeError("a weight refresh is already in progress")
            self._pending_refresh = pending
        self._wake.set()
        if self._pump_thread is None:
            # manual-pump mode (auto_start=False): drive the pump inline
            deadline = time.monotonic() + timeout
            while not pending["done"].is_set() and time.monotonic() <= deadline:
                try:
                    self._pump_once()
                except BaseException as e:
                    with self._state_lock:
                        self._state = "failed"
                    self._fail_outstanding(GatewayFailedError(
                        f"pump died mid-refresh: {type(e).__name__}: {e}"))
                    break
        if not pending["done"].wait(timeout):
            with self._refresh_lock:
                if self._pending_refresh is pending:
                    self._pending_refresh = None  # withdraw; admission resumes
            raise TimeoutError(
                f"weight refresh to version {version}: in-flight requests "
                f"still running after {timeout}s — nothing adopted")
        if pending["error"] is not None:
            raise pending["error"]
        return int(version)

    def _maybe_refresh(self):
        """Pump-side half of :meth:`refresh_weights`: while a swap is
        staged, admission stays held; once the last in-flight request
        retires, swap in place and invalidate old-version KV."""
        with self._refresh_lock:
            pending = self._pending_refresh
        if pending is None:
            return False
        if self._active:
            return False  # in-flight streams finish on the old weights
        try:
            self.engine.swap_params(pending["params"], pending["version"])
        except BaseException as e:
            pending["error"] = e
            with self._refresh_lock:
                self._pending_refresh = None
            pending["done"].set()
            raise  # pump crash path: a mid-swap failure fails the replica
        with self._handoff_lock:
            self._handoffs.clear()  # exported records predate the new weights
        with self._refresh_lock:
            self._pending_refresh = None
        self.metrics.count("weight_refreshes")
        logger.info(f"serving: weights refreshed to version "
                    f"{pending['version']} in place")
        pending["done"].set()
        return True

    def prefix_match_len(self, prompt_tokens):
        """Read-only placement signal: leading tokens of
        ``prompt_tokens`` whose KV this gateway's engine already caches
        (0 when the prefix cache is off or the gateway is not running).
        Never creates a sequence, takes no leases, skews no hit-rate
        stats — safe for a router to call on every placement."""
        if self._state != "running":
            return 0
        engine = self.engine
        fn = getattr(engine, "prefix_match_len", None) if engine is not None \
            else None
        return int(fn(prompt_tokens)) if fn is not None else 0

    def inflight(self):
        """Request counts by stage — the router's least-loaded signal.
        Reads race the pump benignly (a load hint, not an invariant)."""
        return {"queued": len(self.queue),
                "active": len(self._active),
                "paused": len(self._paused)}

    def _stop_pump(self):
        if self.controller is not None:
            self.controller.stop()
        thread = self._pump_thread
        with self._state_lock:
            self._pump_stop = True
        self._wake.set()
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=30)
        self._pump_thread = None

    def _fail_outstanding(self, error):
        with self._refresh_lock:
            pending, self._pending_refresh = self._pending_refresh, None
        if pending is not None:
            # never strand a refresh caller on a dead pump
            if pending.get("error") is None:
                pending["error"] = error
            pending["done"].set()
        for entry in self.queue.candidates():
            self.queue.remove(entry)
            self._end(entry, "failed", error)
        try:
            # what was generated is streamed before a handle fails (and a
            # retired request whose tokens all arrive has completed)
            self.scheduler.hand_over()
        except Exception:
            pass  # the hook itself may be what killed the pump
        for uid, handle in list(self._active.items()):
            try:
                self.scheduler.cancel(uid)
            except Exception:
                pass
            self._end(handle, "failed", error,
                      request=self.scheduler.requests.get(uid))
        for handle, request in self._ending.values():
            self._end(handle, "failed", error, request=request)
        self._active.clear()
        self._ending.clear()
        self._paused = []

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.drain()
        else:
            self.shutdown()
        return False

    # ------------------------------------------------------------------ pump
    def _run(self):
        while not self._pump_stop:
            began = tracing.now_ns()
            try:
                did_work = self._pump_once()
            except Exception as e:  # crash-safe: never hang clients
                logger.exception("serving pump died")
                with self._state_lock:
                    self._state = "failed"
                self._fail_outstanding(GatewayFailedError(
                    f"serving pump died: {type(e).__name__}: {e}"))
                return
            in_flight = bool(self._active or self._ending) or len(self.queue) > 0
            if not in_flight and self._state == "draining":
                return
            if not did_work:
                self._wake.wait(timeout=self.config.idle_poll_s if in_flight
                                else 0.05)
                self._wake.clear()
                self._idle_passes += 1
                self._waited_ns += tracing.now_ns() - began

    def _pump_once(self):
        """One pump iteration; True when any request made progress."""
        # one step record a pass (kind "pump"); a pass that did nothing —
        # the pump polls every idle_poll_s — leaves none
        with tracing.step("pump", span="gateway.pump", engine=self._engine_id) as rec:
            self._pump_seq = rec.seq
            with tracing.phase("gateway.admit"):
                did = self._process_cancels()
                did |= self._process_deadlines()
                did |= self._maybe_refresh()
                refreshing = self._pending_refresh is not None
                if not refreshing:  # admission held while a weight swap is staged
                    did |= self._admit()
                did |= self._resume_paused()
            progressed, stepped = self._step()
            did |= progressed
            rec.keep = did
            if did:
                rec.waited_ns, rec.idle_passes = self._waited_ns, self._idle_passes
                self._waited_ns = self._idle_passes = 0
        if stepped:
            self._watch_stall()
        interval = self.config.metrics_interval_steps
        if self.monitor is not None and interval and did:
            steps = self.metrics.counter("engine_steps")
            if steps and steps % interval == 0:
                self.metrics.write_events(self.monitor, step=steps)
        return did

    def _watch_stall(self):
        """After a pass that ran a step: the time from the end of the
        engine's previous step record to the end of this pass's, while the
        gateway held a request it could run throughout. One subtraction;
        the rest only when that alone is ``tracing.STALL_NS`` or more."""
        prev, rec = self._last_engine_rec, getattr(self.engine, "last_step", None)
        if rec is None or rec.end_ns is None:
            return
        self._last_engine_rec = rec
        if prev is not None and prev is not rec \
                and rec.end_ns - prev.end_ns >= tracing.STALL_NS:
            self._check_stall(prev, rec)

    def _check_stall(self, prev, rec):
        """The interval ``prev.end_ns`` → ``rec.end_ns`` less what
        ``rec.program`` usually takes from dispatch to the end of its fetch,
        and less what was spent compiling (every compile event's own time, so
        a nest of traces counts once and cannot outgrow the interval), is the
        excess;
        ``tracing.STALL_NS`` of it or more, on a program seen
        ``tracing.STALL_MIN_RECORDS`` times before, is a stall: counted,
        written to the recorder's events and logged, with what the pump
        thread, the collector and the compiler did meanwhile."""
        ring = tuple(tracing.RECORDER.steps)
        earlier = [tracing.device_ns(r) for r in ring if r.engine == rec.engine and r is not rec
                   and r.kind != "pump" and r.program == rec.program]
        earlier = [ns for ns in earlier if ns is not None]
        if len(earlier) < tracing.STALL_MIN_RECORDS:
            return
        expected = median(earlier[-tracing.STALL_MEDIAN_OF:])
        start, end = prev.end_ns, rec.end_ns
        gc_ns, gc_passes, compile_ns, compiles = (
            now - then for now, then in zip(rec.counters_at, prev.counters_at))
        # a program compiled in the interval (a variant the warm-up did not
        # reach) is no stall: the events ring names the compile already
        excess = end - start - expected - compile_ns
        if excess < tracing.STALL_NS:
            return
        # where the interval went: every phase of the records that overlap it
        # (they do not overlap one another), the rest of a pump pass as the
        # pass itself, and what lies in no pass
        held, passes, waited = {}, 0, 0
        for r in ring:
            if r.engine != rec.engine or r.end_ns <= start or r.start_ns >= end:
                continue
            if r.kind == "pump":
                passes += min(r.end_ns, end) - max(r.start_ns, start)
                if r.start_ns > start:
                    waited += r.waited_ns
            for name, enter, exit_ in r.phases:
                inside = min(exit_, end) - max(enter, start)
                if inside > 0:
                    held[name] = held.get(name, 0) + inside
        in_phases = sum(held.values())
        held["ds.gateway.pump"] = max(0, passes - in_phases)
        held["(no pass)"] = max(0, end - start - max(passes, in_phases))
        between = max(0, rec.start_ns - start)
        found = {
            "excess_ms": excess / 1e6, "expected_ms": expected / 1e6,
            "where": "inside" if end - start - between - expected >= between else "between",
            "record_kind": rec.kind, "program": rec.program, "n_seqs": rec.n_seqs,
            "n_tokens": rec.n_tokens, "phase": max(held, key=held.get),
            "cpu_ms": _cpu_ms_between(prev, rec), "gc_ms": gc_ns / 1e6,
            "gc_passes": gc_passes, "compile_ms": compile_ns / 1e6, "compiles": compiles,
            "waited_ms": waited / 1e6}
        self.metrics.count("stalls")
        self.metrics.count("stalled_ms", int(round(found["excess_ms"])))
        tracing.event("stall", start, end, seq=rec.seq, **found)
        logger.warning(
            "serving: stall of {excess_ms:.1f} ms beyond the {expected_ms:.1f} ms a step of "
            "program {program} takes, {where} {record_kind} record {seq} ({n_seqs} rows, {n_tokens} "
            "tokens), most of it in {phase}; pump thread cpu {cpu_ms} ms, collector "
            "{gc_ms:.1f} ms in {gc_passes} passes, compile {compile_ms:.1f} ms in {compiles} "
            "compiles, waited {waited_ms:.1f} ms".format(
                seq=rec.seq, **{**found, "cpu_ms": "n/a" if found["cpu_ms"] is None
                                else f"{found['cpu_ms']:.1f}"}))

    def _gauges(self):
        """Gauge source of :class:`ServingMetrics` (read on demand; races
        the pump benignly, like :meth:`inflight`)."""
        free = int(self.engine.free_blocks)
        return {"queue_depth": len(self.queue),
                "running": len(self._active) - len(self._paused),
                "paused": len(self._paused),
                "kv_free_blocks": free,
                "kv_occupancy": round(1.0 - free / max(self.gate.usable_blocks, 1), 4)}

    def _external(self):
        """External-group source of :class:`ServingMetrics`: each engine
        subsystem's ``stats()`` under its tag prefix."""
        engine = self.engine
        groups = {}
        for prefix, attr in (("Serve/PrefixCache", "prefix_cache"),
                             ("Serve/KVTier", "kv_tier"), ("Serve/Spec", "spec"),
                             ("Serve/LoRA", "lora_store"), ("Serve/WindowPool", "window_pool")):
            subsystem = getattr(engine, attr, None)
            if subsystem is not None:
                groups[prefix] = subsystem.stats()
        syncs = getattr(engine, "host_syncs", None)
        if syncs is not None:
            groups["Serve/Engine"] = {
                "host_syncs": int(syncs),
                "tokens_emitted": int(engine.tokens_emitted),
                "syncs_per_token": engine.syncs_per_generated_token,
                "async_burst": int(getattr(engine, "async_burst_depth", 0)),
            }
        if "Serve/WindowPool" in groups:    # the gate's queue by which pool refused
            groups["Serve/WindowPool"].update(
                {f"gate_refused_by_{name}": n for name, n in self.gate.refused_by.items()})
        return groups

    def _end(self, handle, status, error=None, counter=None, request=None):
        """Every ending of a request the gateway accepted: finish the
        handle, count it, and write its request record (utils/tracing.py)
        from the stamps on the handle and on ``request``, its
        ``scheduler.Request`` if it was ever admitted. False when it had
        ended already."""
        if not handle._finish(status, error):
            return False
        self.metrics.count(counter or status)
        tracing.request(
            uid=handle.uid, engine=self._engine_id, status=status,
            prompt_len=len(handle.prompt), generated=len(handle._collected),
            submitted_ns=handle.submitted_ns,
            admitted_ns=handle.admitted_ns, admitted_seq=handle.admitted_seq,
            first_scheduled_ns=request.first_scheduled_ns if request else None,
            first_scheduled_seq=request.first_scheduled_seq if request else 0,
            first_token_ns=handle.first_token_ns,
            first_token_seq=request.first_token_seq if request else 0,
            prefill_steps=request.prefill_steps if request else 0,
            prefix_cached_tokens=request.prefix_cached_tokens if request else 0,
            ended_ns=tracing.now_ns())
        return True

    def _process_cancels(self):
        with self._cancel_lock:
            cancels, self._cancels = self._cancels, []
        did = False
        for handle in cancels:
            if handle.done:
                continue
            did |= self._terminate(handle, "cancelled", lambda: RequestCancelledError(
                f"request {handle.uid} cancelled after "
                f"{len(handle._collected)} tokens"), "cancelled")
        return did

    def _process_deadlines(self):
        now = time.perf_counter()  # the clock of submitted_at and deadline
        did = False
        for entry in self.queue.expired(now):
            did |= self._terminate(entry, "deadline", lambda: DeadlineExceededError(
                f"request {entry.uid} expired in queue after "
                f"{(now - entry.submitted_at) * 1e3:.0f}ms"), "deadline_expired")
        for uid, handle in list(self._active.items()):
            if handle.deadline is not None and now >= handle.deadline:
                did |= self._terminate(handle, "deadline", lambda: DeadlineExceededError(
                    f"request {uid} exceeded its deadline mid-generation "
                    f"({len(handle._collected)} tokens generated)"),
                    "deadline_expired")
        return did

    def _terminate(self, handle, status, error, counter):
        """Stop a queued or active request with the given terminal state.
        ``error()`` makes its error, here and at once, when the stream holds
        every token the request generated (``scheduler.cancel`` hands over
        what waited)."""
        uid = handle.uid
        request = None
        if uid in self._active:
            self.scheduler.cancel(uid)
            if uid not in self._active:
                # its last token was in a pipelined burst that cancel drained:
                # it has completed, retired and ended with its tokens (_on_tokens)
                return True
            request = self.scheduler.retire(uid)
            self._release(handle)
        elif not self.queue.remove(handle):
            return False  # already finished concurrently, or ending (_ending)
        return self._end(handle, status, error(), counter, request)

    def _release(self, handle):
        self.gate.release(handle.uid)
        self._active.pop(handle.uid, None)
        if handle.uid in self._paused:
            self._paused.remove(handle.uid)

    def _admit(self):
        """Move queued requests into the scheduler, highest priority
        first, while the gate has room for their prompts; optionally preempt
        lower-priority running requests for the head of the queue. A request
        that was preempted for room comes back with what it generated as
        the end of its prompt, and the rest of its answer to make."""
        did = False
        for entry in self.queue.candidates():
            uid, made = entry.uid, entry._collected
            prompt = entry.prompt + made if made else entry.prompt
            max_new = entry.max_new_tokens - len(made)
            while not self.gate.try_commit(uid, len(prompt), max_new):
                if not self.config.allow_preemption or not self._preempt_for(entry):
                    return did  # strict priority order: no skip-ahead
            if not self.queue.remove(entry):  # cancelled concurrently
                self.gate.release(uid)
                continue
            if entry.done:  # shed/failed between snapshot and now
                self.gate.release(uid)
                continue
            schema = getattr(entry, "schema", None)
            try:
                self.scheduler.add_request(uid, prompt,
                                           max_new_tokens=max_new,
                                           priority=entry.priority,
                                           spec=getattr(entry, "spec", True),
                                           adapter_id=getattr(entry, "adapter_id",
                                                              None),
                                           sample=getattr(entry, "sample", None),
                                           schema=schema,
                                           breakpoints=getattr(entry, "cache_breakpoints", ()))
                if schema is not None:
                    for token in made:  # the DFA stands where the stream does
                        self.engine.advance_schema(uid, token)
            except Exception as e:
                from deepspeed_tpu.serving.admission import ServingError
                # schema bind failures (every DFA slot leased by a live
                # sequence, state overflow) are per-request admission
                # failures just like typed adapter errors — fail THIS
                # request retryably, never the pump
                if not isinstance(e, ServingError) and schema is None:
                    raise
                # typed adapter failure at bind time (hot set saturated
                # with leased slots, publication vanished): fail THIS
                # request with the retryable error instead of killing
                # the pump — the fleet router fails it over
                self.gate.release(uid)
                self._end(entry, "failed", e, "rejected_schema" if schema is not None
                          else "rejected_adapter")
                did = True
                continue
            entry.status = "running"
            entry.admitted_ns, entry.admitted_seq = tracing.now_ns(), self._pump_seq
            entry.queue_wait_s = (entry.admitted_ns - entry.submitted_ns) / 1e9
            self.metrics.observe_queue_wait(entry.queue_wait_s)
            self.metrics.count("admitted")
            self._active[uid] = entry
            did = True
        return did

    def _preempt_for(self, entry):
        """Suspend the lowest-priority running request whose priority is
        strictly below ``entry``'s; False when no valid victim exists."""
        running = [(uid, h) for uid, h in self._active.items()
                   if uid not in self._paused]
        victims = [(uid, h) for uid, h in running if h.priority < entry.priority]
        if not victims:
            return False
        # lowest priority loses; youngest among ties (oldest keeps running)
        uid, handle = min(reversed(victims), key=lambda it: it[1].priority)
        try:
            self.scheduler.pause(uid)
        except ValueError:
            # the pipelined-burst drain inside pause() can discover the
            # victim already finished — nothing left to preempt; the
            # normal finish path releases its gate tokens
            return False
        self.gate.release(uid)
        self._paused.append(uid)
        self.metrics.count("preemptions")
        logger.info(f"serving: preempted request {uid} (priority "
                    f"{handle.priority}) for request {entry.uid} (priority "
                    f"{entry.priority})")
        return True

    def _resume_paused(self):
        """Bring preempted requests back once the pool has room again
        (highest priority first; admitted queue entries take precedence
        because _admit runs before this)."""
        did = False
        for uid in sorted(self._paused, key=lambda u: -self._active[u].priority):
            handle = self._active[uid]
            resumed = self.engine.suspended_blocks(uid) if self.engine.is_suspended(uid) else 0
            if not self.gate.try_commit(uid, len(handle.prompt), handle.max_new_tokens,
                                        resumed_blocks=resumed):
                break
            self.scheduler.unpause(uid)
            self._paused.remove(uid)
            self.metrics.count("resumes")
            did = True
        return did

    def _step(self):
        """→ (a request made progress, an engine step ran)."""
        if not any(uid not in self._paused for uid in self._active):
            self._last_engine_rec = None   # nothing to run: what follows is no stall
            # ... and no dispatch follows for the last step's tokens to ride
            return self.scheduler.hand_over() > 0, False
        before = getattr(self.engine, "last_step", None)
        stepped = self.scheduler.step()
        self.metrics.count("engine_steps")
        rec = getattr(self.engine, "last_step", None)
        if rec is not before and rec.kind == "put" and rec.n_tokens > self.engine.max_seqs:
            # a step that carried a prompt, and whether it ran a program smaller
            # than the budget's (a rung of engine.put_buckets)
            self.metrics.count("prompt_steps")
            if rec.n_rows < self.engine.max_tokens:
                self.metrics.count("prompt_steps_on_rung")
        held_back = self.scheduler.rows_held_back
        if held_back != self._rows_held_back:
            self.metrics.count("rows_held_back", held_back - self._rows_held_back)
            self._rows_held_back = held_back
        ended, self.scheduler.ended = self.scheduler.ended, []
        if not stepped and not ended and not self._preempt_for_room():
            # every live request is schedulable yet nothing ran — a real
            # stall would spin the pump forever; fail fast instead
            raise RuntimeError(
                f"scheduler stalled with {len(self._active)} active requests")
        with tracing.phase("gateway.deliver"):
            for uid in ended:
                self._retire(uid)
        return True, True

    def _preempt_for_room(self):
        """The last resort, for every state kind: no live row could run
        (each needs a block, none is free, nothing is ending). The scheduler
        gives up the request that is cheapest to make again
        (``preempt_for_room``: flushed, no ``engine.suspend``); its place at
        the gate is released and it goes back to the head of the queue, to
        be admitted again (``_admit``) with ``prompt + generated`` as its
        prompt and what is left of ``max_new_tokens``. Its stream keeps what
        it was sent and continues; a sampled request keeps its seed, so its
        tokens depend on (seed, position) as before. False: nobody to give up."""
        request = self.scheduler.preempt_for_room()
        if request is None:
            return False
        handle = self._active[request.uid]
        self._release(handle)
        handle.status = "queued"
        self.queue.push_front(handle)
        self.metrics.count("preempted_for_room")
        self.metrics.count("recomputed_tokens", request.recomputed)
        logger.info(f"serving: preempted request {request.uid} for room after "
                    f"{len(handle._collected)} tokens ({request.recomputed} to recompute)")
        return True

    def _retire(self, uid):
        """A request's last token was accepted: give its room back now, in
        the pass that accepted it, so that the next ``_admit`` sees it. What
        its client sees - the last tokens, the end of the stream, the
        request record - follows with the tokens (``_on_tokens``)."""
        handle = self._active.get(uid)
        if handle is None:
            return
        request = self.scheduler.retire(uid)
        self._release(handle)
        if self.role == "prefill":
            # retire first: the release path folds the request's full
            # blocks into the trie, which is what export walks. Here, not
            # with the tokens: the export gathers from the pool, which a
            # dispatched program holds until it ends
            self._export_handoff(handle)
        self._ending[uid] = (handle, request)

    def _export_handoff(self, handle):
        """Prefill-role finish hook (pump thread only — the export
        gathers from the donated pool): serialize the request's cached
        prompt KV into the outbox for the router to claim via
        :meth:`take_handoff` and deliver to a decode replica. An export
        failure is contained — the router re-plans the request; it must
        never take down the pump."""
        exporter = getattr(self.engine, "export_prefix", None)
        if exporter is None:
            return
        try:
            record = exporter(handle.prompt)
        except Exception:
            logger.exception(
                f"handoff export failed for request {handle.uid}")
            return
        if record is None:
            return
        with self._handoff_lock:
            self._handoffs[handle.uid] = record
            while len(self._handoffs) > _HANDOFF_OUTBOX:
                self._handoffs.popitem(last=False)
        self.metrics.count("handoffs_exported")

    def take_handoff(self, uid):
        """Claim (pop) the exported handoff record for ``uid``; None
        when no export landed (tierless engine, export failure, or the
        outbox rotated it out). Safe from any thread."""
        with self._handoff_lock:
            return self._handoffs.pop(uid, None)

    def import_handoff(self, record):
        """Adopt a peer prefill replica's KV handoff record into this
        engine's spill tier (decode role). Validation errors propagate
        to the caller — a forged/torn record must fail the handoff, not
        be half-adopted. → blocks adopted. Safe from any thread."""
        importer = getattr(self.engine, "import_prefix", None)
        if importer is None or record is None:
            return 0
        n = int(importer(record))
        self.metrics.count("handoffs_imported")
        return n

    def _on_tokens(self, rows, in_flight):
        """Streaming hook (pump thread): the scheduler's rows ``(uid, token,
        done)`` of one engine call, handed over while the next program runs
        (``in_flight``) or with none dispatched. This is when a client can
        see them, so the first-token stamps and ``ttft_s`` are taken here:
        one clock reading a batch, and the metrics' lock once a batch for
        each kind of observation."""
        now = tracing.now_ns()
        active, ending = self._active, self._ending
        firsts, latencies = [], []
        for uid, token, done in rows:
            handle = active.get(uid)
            if handle is None:
                retired = ending.get(uid)
                if retired is None:
                    continue    # cancelled meanwhile: its stream has ended
                handle = retired[0]
            if handle.first_token_ns is None:
                firsts.append(self._first_token(handle, now))
            else:   # 0 for a burst's later tokens: they arrive with its first
                latencies.append((now - handle.last_token_ns) / 1e9)
            handle.last_token_ns = now
            handle._emit(int(token))
            if done:
                self._retire(uid)   # where its pass has not yet (a pipeline's drain)
                handle, request = ending.pop(uid)
                self._end(handle, "completed", request=request)
        n = len(firsts) + len(latencies)
        if n:
            self.metrics.count("tokens_generated", n)
            self.metrics.count("tokens_delivered_in_flight" if in_flight
                               else "tokens_delivered_idle", n)
        if firsts:
            self.metrics.observe_first_tokens(firsts)
        if latencies:
            self.metrics.observe_token_latencies(latencies)

    def _first_token(self, handle, now):
        """Stamp a request's first token → what ``observe_first_tokens``
        takes of it: ttft = queue_wait (submitted → admitted) + sched_wait
        + prefill_span, in seconds."""
        handle.first_token_ns = now
        handle.ttft_s = (now - handle.submitted_ns) / 1e9
        ended = self._ending.get(handle.uid)
        request = ended[1] if ended else self.scheduler.requests.get(handle.uid)
        scheduled = request.first_scheduled_ns if request is not None else None
        if scheduled is None:
            return handle.ttft_s, None, None
        return (handle.ttft_s, (scheduled - handle.admitted_ns) / 1e9,
                (now - scheduled) / 1e9)

    # ------------------------------------------------------------------ misc
    @property
    def state(self):
        return self._state

    def snapshot(self):
        """Metrics snapshot plus gateway state (tests / CLI)."""
        snap = self.metrics.snapshot()
        snap["state"] = self._state
        return snap
