"""Step records and request life-cycle stamps, kept by the program itself.

One process-wide :class:`Recorder` holds three bounded rings. It is always
on — there is no switch — because a record costs a few
``time.perf_counter_ns()`` / ``time.thread_time_ns()`` calls and one
append per *model step* (about ten a second) or per *request*:

* a **step record** for every model program run (``put``, a decode
  burst, an async burst and its fetch, a verify burst, ``train_batch``)
  and for every serving pump pass that did work (kind ``pump``); a ``put``
  program an engine runs on the null sequence alone to have it built before traffic
  is a record of kind ``build``. The
  code that runs the program opens the record and knows what the device
  trace cannot: which program, how many steps ``k``, how many rows and
  tokens. ``caused_by`` is the ``seq`` of the record that was open on the
  thread when this one was opened (the pump pass; 0 outside a gateway);
* a **request record** for every request a gateway saw end, with the
  stamps of its life (submitted, admitted, first scheduled, first token,
  ended) and the ``seq`` of the pump pass and step records they fell in;
* an **event** for what stops a thread from outside its own code: every
  collector pass of ``EVENT_MIN_NS`` or more, every backend compile, every
  lowering and every outermost trace of ``EVENT_MIN_NS`` or more, whichever
  thread it ran on, and every stall a serving gateway found
  (``serving/gateway.py``), each with its start and end on the records'
  clock and the ``seq`` of the record that was open on the thread.

Beside the rings it keeps what set-up cost, which is asked for long after
the rings have turned over: the **``setup`` records** of the engines'
constructors (:meth:`Recorder.setup`: contiguous ``ds.setup.*`` phases and
the process's age at entry) and a **build table** with one row a program
(``engine``, ``kind``, ``program``): what building it cost in tracing,
lowering and the backend, whether the persistent cache answered, and the
functions whose tracing took longest. Compile time is counted **once**:
JAX reports a traced function's time with the nested ``jit`` functions'
inside it and reports those too, so every compile event counts its own
time only (:func:`_on_duration`). Compile time with no record open on its
thread goes to the row ``outside`` (``docs/OBSERVABILITY.md``, "Set-up").

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
hook and ``jax.monitoring``'s two listeners, installed when this module is
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
import os
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
# a result (accept), its work before the next launch (retire, admit, plan,
# pack) and the program itself (dispatch, the hand-over of the step before's
# tokens, fetch); a training step's phases are few and long, so all of its
# own are marked
CPU_MARKED = frozenset(PREFIX + name for name in (
    "engine.pack", "engine.fetch", "sched.accept",
    "train.prepare", "train.dispatch", "train.sync", "train.post"))

STEP_FIELDS = ("seq", "engine", "kind", "program", "k", "n_seqs", "n_tokens", "n_rows",
               "n_prompt_tokens", "n_ctx_tokens", "n_chunk_rows", "n_chunk_tiles",
               "n_table_rows_written",
               "n_layers_prefetched", "counts",
               "state_step", "caused_by", "uids",
               "start_ns", "end_ns", "thread", "gc_ns", "gc_passes", "compile_ns", "compiles",
               "waited_ns", "idle_passes", "process_age_ns")

# JAX's own duration events (jax.monitoring) that count as compiling, and the
# part of a build each is: the backend's is compiling or, where the persistent
# cache answers, reading the executable back
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BUILD_PARTS = {TRACE_EVENT: "trace_ns", LOWER_EVENT: "lower_ns", COMPILE_EVENT: "backend_ns"}
# the persistent cache says which it was before the backend's event ends
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
BUILD_ROWS = 64         # an engine has 6-11 programs; past this the oldest rows fold into "other"
BUILD_FUNCTIONS = 8     # of a row: the functions with most own trace time
NEST_MAX = 4096         # a thread's open nest of compile events (_on_duration)


class _Process:
    """What the collector and the compiler took of this process so far,
    whichever thread they ran on. The collector runs one pass at a time
    under the interpreter lock, so its hook takes no lock; compiles end on
    several threads at once, so theirs does (``compile_lock`` also guards
    every recorder's build table)."""
    gc_ns = gc_passes = trace_ns = lower_ns = backend_ns = compiles = 0
    gc_started = None
    compile_lock = threading.Lock()
    recorders = weakref.WeakSet()    # every Recorder's events ring and build table is told
    # a thread's compile events: .nest, the open nest's (middle, ns, the trace event still to
    # tell or None) oldest first; .cache, what the persistent cache said of the backend event
    # to come
    thread = threading.local()


def process_counters():
    """→ ``(gc_ns, gc_passes, compile_ns, compiles)`` since this module was
    imported: nanoseconds in collector passes and their number, nanoseconds
    tracing, lowering and compiling (or reading a compiled program back from
    the persistent cache), each event's own time, and the number of backend
    compiles."""
    return (_Process.gc_ns, _Process.gc_passes,
            _Process.trace_ns + _Process.lower_ns + _Process.backend_ns, _Process.compiles)


def compile_counters():
    """→ ``(trace_ns, lower_ns, backend_ns)``: ``process_counters()[2]`` by part."""
    return _Process.trace_ns, _Process.lower_ns, _Process.backend_ns


def process_age_ns():
    """Nanoseconds since this process started (Linux: ``/proc``; None where
    that is unknown): the interpreter's start, the imports and the backend's
    start-up lie before any record."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0, int((uptime - start_ticks / os.sysconf("SC_CLK_TCK")) * 1e9))
    except (OSError, ValueError, IndexError):
        return None


class Build:
    """What building cost - a record's programs, or a row of the build table:
    own nanoseconds tracing, lowering and in the backend, backend compiles,
    those the persistent cache answered (``hits``) and those it was written
    (``misses``; neither: no cache, or a program too small for it to keep),
    and by traced function ``[times traced, own ns, whole ns]`` - whole: with
    the nested functions' time, as JAX reports it, so a function's and its
    callers' wholes overlap."""
    SUMS = ("trace_ns", "lower_ns", "backend_ns", "compiles", "hits", "misses")
    __slots__ = ("seq", "builds") + SUMS + ("functions",)

    def __init__(self, seq=0):
        self.seq = seq          # a row: the first record that built; builds: how many did
        self.builds = self.trace_ns = self.lower_ns = self.backend_ns = 0
        self.compiles = self.hits = self.misses = 0
        self.functions = {}

    @property
    def ns(self):
        return self.trace_ns + self.lower_ns + self.backend_ns

    def add(self, part, own, whole, fun_name, cache):
        setattr(self, part, getattr(self, part) + own)
        if part == "trace_ns":
            self._traced(fun_name, 1, own, whole)
        elif part == "backend_ns":
            self.compiles += 1
            self.hits += cache == "hit"
            self.misses += cache == "miss"

    def _traced(self, fun_name, times, own, whole):
        traced = self.functions.setdefault(fun_name, [0, 0, 0])
        traced[0] += times
        traced[1] += own
        traced[2] += whole

    def merge(self, other):
        self.builds += 1
        for name in self.SUMS:
            setattr(self, name, getattr(self, name) + getattr(other, name))
        for fun_name, traced in other.functions.items():
            self._traced(fun_name, *traced)
        if len(self.functions) > BUILD_FUNCTIONS:
            self.functions = dict(self.most_traced())

    def most_traced(self, n=BUILD_FUNCTIONS):
        return sorted(self.functions.items(), key=lambda item: -item[1][1])[:n]

    def as_dict(self):
        out = {name: getattr(self, name) for name in self.__slots__[:-1]}
        out["functions"] = [[name, *traced] for name, traced in self.most_traced()]
        return out

    def describe(self):
        """One clause for a log line: what it cost, and what was traced most."""
        cache = "hit" if self.hits else "miss" if self.misses else "none"
        said = (f"{self.ns / 1e9:.2f} s (trace {self.trace_ns / 1e9:.2f} own, lower "
                f"{self.lower_ns / 1e9:.2f}, backend {self.backend_ns / 1e9:.2f}, cache {cache})")
        most = self.most_traced(1)
        if most:
            name, (times, own, _) = most[0]
            said += f"; most traced: {name} x{times} {own / 1e9:.2f} s"
        return said


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
    """A compile event of JAX's, told as it ends, counted by its **own**
    time. JAX times a traced function with everything it calls, and a nested
    ``jit`` function reports itself as well (``inner`` 97 ms, then ``outer``
    100 ms, for 100 ms of tracing), so what the earlier events of this thread
    that lie inside this one's interval took is taken off it: ``inner`` 97,
    ``outer`` 3. Such an event is one whose middle lies after this one's start
    (the ends are read here, a few microseconds after JAX read them, so the
    middle decides, not an edge); they are the tail of the thread's list and
    leave it, so it holds the open nest alone, and a backend event, after
    which nothing of its program is open, empties it.

    The events ring is told every backend compile, and a lowering or a trace
    of ``EVENT_MIN_NS`` or more - of a nest of traces the outermost alone,
    known as that, and told, when the thread's next lowering or backend event
    arrives (a program's own, as a rule) and finds it still listed."""
    part = BUILD_PARTS.get(event)
    if part is None:
        return
    end = now_ns()
    ns = int(seconds * 1e9)
    start = end - ns
    thread = _Process.thread.__dict__
    nest = thread.get("nest")
    if nest is None:
        nest = thread["nest"] = []
    inside = 0
    while nest and nest[-1][0] >= start:
        inside += nest.pop()[1]
    own = ns - inside if ns > inside else 0
    cache, told = None, ()
    if part != "trace_ns":  # a program is lowered: the traces still listed are outermost
        told = [entry[2] for entry in nest if entry[2] is not None]
    if part == "backend_ns":
        cache = thread.pop("cache", None)
        del nest[:]
    else:
        if told:
            nest[:] = [(middle, took, None) for middle, took, _ in nest]
        untold = part == "trace_ns" and ns >= EVENT_MIN_NS
        nest.append((start + ns // 2, ns, (start, end, fun_name, seconds, own) if untold else None))
        if len(nest) > NEST_MAX:    # a nest this wide: its oldest half as one
            half = NEST_MAX // 2
            nest[:half] = [(nest[0][0], sum(entry[1] for entry in nest[:half]), None)]
    with _Process.compile_lock:
        setattr(_Process, part, getattr(_Process, part) + own)
        _Process.compiles += part == "backend_ns"
        for recorder in tuple(_Process.recorders):
            recorder.built(part, own, ns, fun_name, cache)
    for t_start, t_end, t_name, t_seconds, t_own in told:
        _tell("compile", t_start, t_end, name=TRACE_EVENT.rsplit("/", 1)[-1], program=t_name,
              seconds=t_seconds, own_ns=t_own, cache=None)
    if part == "backend_ns" or (part == "lower_ns" and ns >= EVENT_MIN_NS):
        _tell("compile", start, end, name=event.rsplit("/", 1)[-1], program=fun_name,
              seconds=seconds, own_ns=own, cache=cache)


def _on_event(event, **_):
    said = CACHE_EVENTS.get(event)
    if said is not None:
        _Process.thread.cache = said


gc.callbacks.append(_on_gc)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


class StepRecord:
    # counters_at: the process counters at the record's begin, then at its
    # end - what the next record's interval is measured from; build: what the
    # thread built while the record was the one open on it (a Build), or None
    __slots__ = STEP_FIELDS + ("phases", "cpu_marks", "keep", "counters_at", "build", "_parent")

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


class _Setup:
    """``with recorder.setup(engine) as setup:`` - a step record of kind
    ``setup`` around a constructor, with the process's age at entry.
    ``setup.phase("setup.params")`` ends the phase before it and begins
    ``ds.setup.params`` on the same stamp, so the phases are contiguous and a
    constructor is not indented under them; the last ends with the record. A
    phase's end waits for nothing: device work a phase started and did not
    wait for ends in a later phase or in the first program's fetch. The
    recorder keeps the record beside the ring (``Recorder.setups``)."""
    __slots__ = ("_recorder", "_fields", "_name", "_enter", "_span", "record")

    def __init__(self, recorder, fields):
        self._recorder, self._fields, self._name = recorder, fields, None

    def __enter__(self):
        age = process_age_ns()
        self.record = self._recorder.begin("setup", **self._fields)
        self.record.process_age_ns = age
        return self

    def phase(self, name):
        now = now_ns()
        self._close(now)
        self._name, self._enter = PREFIX + name, now
        self._span = TraceAnnotation(self._name)
        self._span.__enter__()

    def _close(self, now):
        if self._name is not None:
            self.record.phases.append((self._name, self._enter, now))
            self._span.__exit__(None, None, None)
            self._name = None

    def __exit__(self, exc_type, exc, tb):
        self._close(now_ns())
        self._recorder.end(self.record, keep=exc_type is None)
        if exc_type is None:
            self._recorder.setups.append(self.record)
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
        self.setups = collections.deque(maxlen=BUILD_ROWS)  # the kept records of kind setup
        # the build table: (engine, kind, program) -> Build; what no record was open for
        # (other: the rows that made room for newer ones, as one)
        self.builds, self.other, self.outside = {}, Build(), Build()
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
        rec.build = rec.process_age_ns = None
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
        if rec.build is not None:
            self._note_build(rec)
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

    def setup(self, engine, program="engine"):
        """A constructor's record: see :class:`_Setup`."""
        return _Setup(self, {"engine": engine, "program": program})

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

    # -------------------------------------------------------------- build table
    def built(self, part, own_ns, whole_ns, fun_name, cache):
        """A compile event's own time (``_on_duration``, under the compile
        lock), laid at the record open on the calling thread, or ``outside``."""
        rec = self.current()
        if rec is None:
            build = self.outside
        else:
            build = rec.build
            if build is None:
                build = rec.build = Build()
        build.add(part, own_ns, whole_ns, fun_name, cache)

    def _note_build(self, rec):
        """A record that built something ends: its row of the table (a
        ``put`` program built before traffic, kind ``build``, is the row of
        the ``put`` of its size: a row a program, whoever ran it first)."""
        key = (rec.engine, "put" if rec.kind == "build" else rec.kind, rec.program)
        with _Process.compile_lock:
            row = self.builds.get(key)
            if row is None:
                if len(self.builds) >= BUILD_ROWS:  # the oldest row makes room: a process
                    self.other.merge(self.builds.pop(next(iter(self.builds))))  # of many engines
                row = self.builds[key] = Build(rec.seq)
            row.merge(rec.build)

    def build_rows(self):
        """→ the table as dicts, in the order the rows were first built, then
        ``other`` (the oldest rows of a process that built more than
        ``BUILD_ROWS``, as one; ``builds`` counts them) and ``outside``: ``engine``, ``kind``, ``program``, ``seq`` of the first
        record that built, ``builds``, own ``trace_ns`` / ``lower_ns`` /
        ``backend_ns``, ``compiles``, ``hits``, ``misses``, ``functions``
        (``[name, times traced, own ns, whole ns]``, most own time first)."""
        with _Process.compile_lock:
            rows = [{"engine": engine, "kind": kind, "program": program, **row.as_dict()}
                    for (engine, kind, program), row in self.builds.items()]
            if self.other.builds:
                rows.append({"engine": 0, "kind": "other", "program": "", **self.other.as_dict()})
            rows.append({"engine": 0, "kind": "outside", "program": "", **self.outside.as_dict()})
        return rows

    def setup_summary(self, engine):
        """What setting engine number ``engine`` up cost so far →
        ``process_age_ns`` at its first constructor's entry, ``init_ns`` (its
        ``setup`` records' wall time) and ``phases_ns`` by name, ``init_build``
        (what those records built: inside ``init_ns``), ``build`` (the
        engine's other rows summed - its programs, ``programs`` their number,
        and what its gateway's pump passes built outside them) and ``outside``
        (the process's compile time under no record at all)."""
        setups = [rec for rec in tuple(self.setups) if rec.engine == engine]
        phases = {}
        for rec in setups:
            for name, enter, exit_ in rec.phases:
                phases[name] = phases.get(name, 0) + exit_ - enter
        init, build, programs = Build(), Build(), 0
        with _Process.compile_lock:
            for (row_engine, kind, _), row in self.builds.items():
                if row_engine == engine:
                    (init if kind == "setup" else build).merge(row)
                    programs += kind not in ("setup", "pump")
            outside = self.outside.as_dict()
        sums = Build.SUMS
        return {"engine": engine,
                "process_age_ns": setups[0].process_age_ns if setups else None,
                "init_ns": sum(rec.end_ns - rec.start_ns for rec in setups),
                "phases_ns": phases,
                "init_build": {name: getattr(init, name) for name in sums},
                "build": {"programs": programs, **{name: getattr(build, name) for name in sums}},
                "outside": {name: outside[name] for name in sums}}

    # ------------------------------------------------------------------ reading
    def snapshot(self):
        """→ ``{"steps": [dict, ...], "requests": [dict, ...], "events":
        [dict, ...], "builds": [dict, ...]}``, oldest first."""
        return {"steps": [r.as_dict() for r in tuple(self.steps)],
                "requests": [dict(r) for r in tuple(self.requests)],
                "events": [dict(e) for e in tuple(self.events)],
                "builds": self.build_rows()}

    def dump(self, path):
        """The rings and the build table as JSON lines: ``{"record": "step" |
        "request" | "event" | "build", ...}``. → the number of lines."""
        snap = self.snapshot()
        with open(path, "w") as f:
            for key, label in (("steps", "step"), ("requests", "request"), ("events", "event"),
                               ("builds", "build")):
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
setup = RECORDER.setup
setup_summary = RECORDER.setup_summary
phase = RECORDER.phase
request = RECORDER.request
event = RECORDER.event
snapshot = RECORDER.snapshot
dump = RECORDER.dump
