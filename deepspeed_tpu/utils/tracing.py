"""Step records and request life-cycle stamps, kept by the program itself.

One process-wide :class:`Recorder` holds three bounded rings. It is always
on — there is no switch — because a record costs a few
``time.perf_counter_ns()`` / ``time.thread_time_ns()`` calls and one
append per *model step* (about ten a second) or per *request*:

* a **step record** for every model program run (``put``, a decode
  burst, an async burst and its fetch, a verify burst, ``train_batch``)
  and for every serving pump pass that did work (kind ``pump``). The
  code that runs the program opens the record and knows what the device
  trace cannot: which program, how many steps ``k``, how many rows and
  tokens. ``caused_by`` is the ``seq`` of the record that was open on the
  thread when this one was opened (the pump pass; 0 outside a gateway);
* a **request record** for every request a gateway saw end, with the
  stamps of its life (submitted, admitted, first scheduled, first token,
  ended) and the ``seq`` of the pump pass and step records they fell in;
* an **event** for what stops a thread from outside its own code: every
  collector pass and every compile (trace, lowering, backend compile) of
  ``EVENT_MIN_NS`` or more, whichever thread it ran on, and every stall a
  serving gateway found (``serving/gateway.py``), each with its start and
  end on the records' clock and the ``seq`` of the record that was open
  on the thread.

A step record says what the thread that opened it did with its time.
``cpu_marks`` holds that thread's CPU clock (``time.thread_time_ns()``)
read at the exit of the few phases named in ``CPU_MARKED`` — three a
served step: the program is packed (the launch follows), its result is
fetched, its tokens are accepted — as ``[phase, wall ns, cpu ns]``. Two
marks of one ``thread`` bound an interval: its CPU time over its wall time
says whether the thread ran or waited — for the device, the interpreter
lock or the operating system. Only those phases, because the clock is a
system call: 6 us a reading on the hosts the chips hang on (0.3 us on a
developer's machine), where it also ticks in steps of about 10 ms, so only
sums over a second or more of marks mean anything (``PERF.md`` section 6).
``gc_ns`` / ``gc_passes`` / ``compile_ns`` / ``compiles`` are what four
process-wide counters (:func:`process_counters`: one ``gc.callbacks``
hook, one ``jax.monitoring`` listener, installed when this module is
imported) moved by between the record's begin and its end.

``with tracing.phase("engine.pack"):`` stamps enter and exit into the
record that is open on this thread (none open: the stamp is dropped) and
enters a ``jax.profiler.TraceAnnotation("ds.engine.pack")``. Without a
profiler session the annotation is inert; with one — anybody's
``jax.profiler.start_trace`` — the program's phases are on the profiler's
own timeline beside the device ops.

Every wall stamp is ``time.perf_counter_ns()``. Writers take no lock: a
``deque.append`` and ``next()`` of an ``itertools.count`` are atomic
under the interpreter lock, and a record is mutated only by the thread
that opened it until it is appended. ``snapshot()`` and ``dump(path)``
copy the rings when asked; nothing is written on the hot path.
"""

import collections
import gc
import itertools
import json
import threading
import time
import weakref

import jax.monitoring
from jax.profiler import TraceAnnotation

STEP_RING = 8192       # ~10 minutes of serving at ten steps a second
REQUEST_RING = 4096
EVENT_RING = 1024
EVENT_MIN_NS = 1_000_000    # a collector pass, a trace or a lowering shorter than this leaves no event
# a serving gateway calls the time from one engine record's end to the next
# one's a stall when it exceeds what the program usually takes by STALL_NS,
# once it has seen the program STALL_MIN_RECORDS times (warm-up compiles are
# not stalls); what it usually takes is the median of its last
# STALL_MEDIAN_OF records (serving/gateway.py)
STALL_NS = 250_000_000
STALL_MIN_RECORDS = 8
STALL_MEDIAN_OF = 32
PREFIX = "ds."

now_ns = time.perf_counter_ns
cpu_ns = time.thread_time_ns
# the phases at whose exit the thread's CPU clock is read (a record's
# cpu_marks): between two marks lie, in a serving step, the host's work after
# a result (accept), its work before the next launch (deliver, admit, plan,
# pack) and the program itself (dispatch, fetch); a training step's phases
# are few and long, so all of its own are marked
CPU_MARKED = frozenset(PREFIX + name for name in (
    "engine.pack", "engine.fetch", "sched.accept",
    "train.prepare", "train.dispatch", "train.sync", "train.post"))

STEP_FIELDS = ("seq", "engine", "kind", "program", "k", "n_seqs", "n_tokens", "n_rows",
               "n_prompt_tokens", "n_ctx_tokens", "n_chunk_rows", "n_chunk_tiles",
               "n_table_rows_written",
               "n_layers_prefetched", "counts",
               "state_step", "caused_by", "uids",
               "start_ns", "end_ns", "thread", "gc_ns", "gc_passes", "compile_ns", "compiles",
               "waited_ns", "idle_passes")

# JAX's own duration events (jax.monitoring) that count as compiling: the
# ones benchmark/harness/device.CompileMeter sums from outside the program
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (COMPILE_EVENT, "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")


class _Process:
    """What the collector and the compiler took of this process so far,
    whichever thread they ran on. The collector runs one pass at a time
    under the interpreter lock, so its hook takes no lock; compiles end on
    several threads at once, so theirs does."""
    gc_ns = gc_passes = compile_ns = compiles = 0
    gc_started = None
    compile_lock = threading.Lock()
    recorders = weakref.WeakSet()    # every Recorder's events ring is told


def process_counters():
    """→ ``(gc_ns, gc_passes, compile_ns, compiles)`` since this module was
    imported: nanoseconds in collector passes and their number, nanoseconds
    tracing, lowering and compiling (or reading a compiled program back from
    the persistent cache) and the number of backend compiles."""
    return _Process.gc_ns, _Process.gc_passes, _Process.compile_ns, _Process.compiles


def _tell(kind, start_ns, end_ns, **fields):
    for recorder in tuple(_Process.recorders):
        recorder.event(kind, start_ns, end_ns, **fields)


def _on_gc(phase, info):
    if phase == "start":
        _Process.gc_started = now_ns()
    elif _Process.gc_started is not None:
        start, end, _Process.gc_started = _Process.gc_started, now_ns(), None
        _Process.gc_ns += end - start
        _Process.gc_passes += 1
        if end - start >= EVENT_MIN_NS:
            _tell("gc", start, end, generation=info["generation"], collected=info["collected"])


def _on_duration(event, seconds, fun_name=None, **_):
    if event not in COMPILE_EVENTS:
        return
    end = now_ns()
    ns = int(seconds * 1e9)
    with _Process.compile_lock:
        _Process.compile_ns += ns
        _Process.compiles += event == COMPILE_EVENT
    if event == COMPILE_EVENT or ns >= EVENT_MIN_NS:
        _tell("compile", end - ns, end, name=event.rsplit("/", 1)[-1], program=fun_name,
              seconds=seconds)


gc.callbacks.append(_on_gc)
jax.monitoring.register_event_duration_secs_listener(_on_duration)


class StepRecord:
    # counters_at: the process counters at the record's begin, then at its
    # end - what the next record's interval is measured from
    __slots__ = STEP_FIELDS + ("phases", "cpu_marks", "keep", "counters_at", "_parent")

    def as_dict(self):
        out = {name: getattr(self, name) for name in STEP_FIELDS}
        out["uids"] = list(self.uids)
        out["phases"] = [list(p) for p in self.phases]
        out["cpu_marks"] = [list(m) for m in self.cpu_marks]
        return out


def device_ns(record):
    """``ds.engine.dispatch`` enter → ``ds.engine.fetch`` exit of an engine
    record: from the launch of its program to its result on the host. None
    without both phases."""
    enter = [t for name, t, _ in record.phases if name == "ds.engine.dispatch"]
    exit_ = [t for name, _, t in record.phases if name == "ds.engine.fetch"]
    return exit_[-1] - enter[0] if enter and exit_ else None


class _Step:
    """``with recorder.step(kind, ...) as record:`` — the record is kept
    when the block ends without raising (and ``record.keep`` was not
    cleared), so a ring holds only programs that ran."""
    __slots__ = ("_recorder", "_kind", "_fields", "_span", "record")

    def __init__(self, recorder, kind, span, fields):
        self._recorder, self._kind, self._fields = recorder, kind, fields
        self._span = TraceAnnotation(PREFIX + span) if span else None

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        self.record = self._recorder.begin(self._kind, **self._fields)
        return self.record

    def __exit__(self, exc_type, exc, tb):
        self._recorder.end(self.record, keep=exc_type is None and self.record.keep)
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        return False


class _Phase:
    __slots__ = ("_name", "_record", "_enter", "_span")

    def __init__(self, recorder, name):
        self._name = PREFIX + name
        self._record = recorder.current()
        self._span = TraceAnnotation(self._name)

    def __enter__(self):
        self._span.__enter__()
        self._enter = now_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        exit_ = now_ns()
        if self._record is not None:
            self._record.phases.append((self._name, self._enter, exit_))
            if self._name in CPU_MARKED:
                self._record.cpu_marks.append((self._name, exit_, cpu_ns()))
        self._span.__exit__(exc_type, exc, tb)
        return False


class Recorder:

    def __init__(self, step_ring=STEP_RING, request_ring=REQUEST_RING, event_ring=EVENT_RING):
        self.steps = collections.deque(maxlen=step_ring)
        self.requests = collections.deque(maxlen=request_ring)
        self.events = collections.deque(maxlen=event_ring)
        _Process.recorders.add(self)
        self._seq = itertools.count(1)
        self._engines = itertools.count(1)
        self._open = threading.local()

    def engine_id(self):
        """A number for one engine object (a process may hold several)."""
        return next(self._engines)

    def current(self):
        """The record open on this thread, or None."""
        return getattr(self._open, "record", None)

    # ------------------------------------------------------------- step records
    def begin(self, kind, engine=0, program="", k=1, n_seqs=0, n_tokens=0,
              n_prompt_tokens=0, uids=(), n_rows=0):
        rec = StepRecord()
        rec.seq = next(self._seq)
        rec.engine, rec.kind, rec.program, rec.k = engine, kind, program, k
        rec.n_seqs, rec.n_tokens, rec.n_prompt_tokens = n_seqs, n_tokens, n_prompt_tokens
        # the rows the program ran, padding included, over its k steps: of them n_tokens
        # held a token (the engine sets it: a put's bucket, a burst's k x max_seqs)
        rec.n_rows = n_rows
        rec.n_ctx_tokens = 0    # the engine adds each row's attended context as it packs
        # of n_rows, the rows the paged kernel attended through a query tile, and the tiles
        # (ops/pallas/paged_attention.chunk_counts of the batch the engine packed)
        rec.n_chunk_rows = rec.n_chunk_tiles = 0
        # a serving step: the rows of the state manager's block table written for it - new
        # sequences and those whose blocks changed (ragged_manager); the rest were gathered
        rec.n_table_rows_written = 0
        # a train step only: the layer gathers its program issues whole, ahead of the
        # arithmetic that reads them (runtime/zero/overlap.py), known when it is built
        rec.n_layers_prefetched = 0
        # what the program itself counted on the device, by name, fetched with its result
        # (model_runner: kind.step_counts); None where the model kind counts nothing
        rec.counts = None
        rec.state_step = None   # what serves the program's state step, if its kind has one
        rec.uids = uids
        rec.phases, rec.cpu_marks, rec.keep, rec.end_ns = [], [], True, None
        rec.thread = threading.get_ident()
        # a pump pass that is kept says what its thread did since the last kept pass
        rec.waited_ns = rec.idle_passes = 0
        parent = rec._parent = self.current()
        rec.caused_by = parent.seq if parent is not None else 0
        self._open.record = rec
        rec.counters_at = process_counters()
        rec.start_ns = now_ns()
        return rec

    def end(self, rec, keep=True):
        rec.end_ns = now_ns()
        self.suspend(rec)
        before, now = rec.counters_at, process_counters()
        rec.counters_at = now
        rec.gc_ns, rec.gc_passes = now[0] - before[0], now[1] - before[1]
        rec.compile_ns, rec.compiles = now[2] - before[2], now[3] - before[3]
        if keep:
            self.steps.append(rec)

    def suspend(self, rec):
        """Close the thread's view of ``rec`` without ending it: an async
        burst's record stays open between its dispatch and its fetch."""
        self._open.record, rec._parent = rec._parent, None

    def resume(self, rec):
        rec._parent = self.current()
        self._open.record = rec

    def step(self, kind, span=None, **fields):
        """``span`` names a ``ds.<span>`` annotation around the whole record."""
        return _Step(self, kind, span, fields)

    def phase(self, name):
        return _Phase(self, name)

    # ---------------------------------------------------------- request records
    def request(self, **stamps):
        self.requests.append(stamps)

    # ------------------------------------------------------------------- events
    def event(self, kind, start_ns, end_ns, seq=None, **fields):
        """One entry of the events ring; ``seq`` defaults to the record
        open on the calling thread (0: none)."""
        if seq is None:
            rec = self.current()
            seq = rec.seq if rec is not None else 0
        self.events.append({"kind": kind, "start_ns": start_ns, "end_ns": end_ns, "seq": seq,
                            **fields})

    # ------------------------------------------------------------------ reading
    def snapshot(self):
        """→ ``{"steps": [dict, ...], "requests": [dict, ...], "events":
        [dict, ...]}``, oldest first."""
        return {"steps": [r.as_dict() for r in tuple(self.steps)],
                "requests": [dict(r) for r in tuple(self.requests)],
                "events": [dict(e) for e in tuple(self.events)]}

    def dump(self, path):
        """The rings as JSON lines: ``{"record": "step" | "request" | "event", ...}``."""
        snap = self.snapshot()
        with open(path, "w") as f:
            for key, label in (("steps", "step"), ("requests", "request"), ("events", "event")):
                for rec in snap[key]:
                    # a caller may name its sequences with numpy integers
                    f.write(json.dumps({"record": label, **rec}, default=lambda o: o.item()) + "\n")
        return sum(len(ring) for ring in snap.values())


RECORDER = Recorder()

engine_id = RECORDER.engine_id
current = RECORDER.current
begin = RECORDER.begin
end = RECORDER.end
suspend = RECORDER.suspend
resume = RECORDER.resume
step = RECORDER.step
phase = RECORDER.phase
request = RECORDER.request
event = RECORDER.event
snapshot = RECORDER.snapshot
dump = RECORDER.dump
