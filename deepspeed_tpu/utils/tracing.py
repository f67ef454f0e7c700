"""Step records and request life-cycle stamps, kept by the program itself.

One process-wide :class:`Recorder` holds two bounded rings. It is always
on — there is no switch — because a record costs a few
``time.perf_counter_ns()`` calls and one append per *model step* (about
ten a second) or per *request*:

* a **step record** for every model program run (``put``, a decode
  burst, an async burst and its fetch, a verify burst, ``train_batch``)
  and for every serving pump pass that did work (kind ``pump``). The
  code that runs the program opens the record and knows what the device
  trace cannot: which program, how many steps ``k``, how many rows and
  tokens. ``caused_by`` is the ``seq`` of the record that was open on the
  thread when this one was opened (the pump pass; 0 outside a gateway);
* a **request record** for every request a gateway saw end, with the
  stamps of its life (submitted, admitted, first scheduled, first token,
  ended) and the ``seq`` of the pump pass and step records they fell in.

``with tracing.phase("engine.pack"):`` stamps enter and exit into the
record that is open on this thread (none open: the stamp is dropped) and
enters a ``jax.profiler.TraceAnnotation("ds.engine.pack")``. Without a
profiler session the annotation is inert; with one — anybody's
``jax.profiler.start_trace`` — the program's phases are on the profiler's
own timeline beside the device ops.

Every stamp is ``time.perf_counter_ns()``. Writers take no lock: a
``deque.append`` and ``next()`` of an ``itertools.count`` are atomic
under the interpreter lock, and a record is mutated only by the thread
that opened it until it is appended. ``snapshot()`` and ``dump(path)``
copy the rings when asked; nothing is written on the hot path.
"""

import collections
import itertools
import json
import threading
import time

from jax.profiler import TraceAnnotation

STEP_RING = 8192       # ~10 minutes of serving at ten steps a second
REQUEST_RING = 4096
PREFIX = "ds."

now_ns = time.perf_counter_ns

STEP_FIELDS = ("seq", "engine", "kind", "program", "k", "n_seqs", "n_tokens",
               "n_prompt_tokens", "n_ctx_tokens", "counts", "caused_by", "uids", "start_ns",
               "end_ns")


class StepRecord:
    __slots__ = STEP_FIELDS + ("phases", "keep", "_parent")

    def as_dict(self):
        out = {name: getattr(self, name) for name in STEP_FIELDS}
        out["uids"] = list(self.uids)
        out["phases"] = [list(p) for p in self.phases]
        return out


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
        self._span.__exit__(exc_type, exc, tb)
        return False


class Recorder:

    def __init__(self, step_ring=STEP_RING, request_ring=REQUEST_RING):
        self.steps = collections.deque(maxlen=step_ring)
        self.requests = collections.deque(maxlen=request_ring)
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
              n_prompt_tokens=0, uids=()):
        rec = StepRecord()
        rec.seq = next(self._seq)
        rec.engine, rec.kind, rec.program, rec.k = engine, kind, program, k
        rec.n_seqs, rec.n_tokens, rec.n_prompt_tokens = n_seqs, n_tokens, n_prompt_tokens
        rec.n_ctx_tokens = 0    # the engine adds each row's attended context as it packs
        # what the program itself counted on the device, by name, fetched with its result
        # (model_runner: kind.step_counts); None where the model kind counts nothing
        rec.counts = None
        rec.uids = uids
        rec.phases, rec.keep, rec.end_ns = [], True, None
        parent = rec._parent = self.current()
        rec.caused_by = parent.seq if parent is not None else 0
        self._open.record = rec
        rec.start_ns = now_ns()
        return rec

    def end(self, rec, keep=True):
        rec.end_ns = now_ns()
        self.suspend(rec)
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

    # ------------------------------------------------------------------ reading
    def snapshot(self):
        """→ ``{"steps": [dict, ...], "requests": [dict, ...]}``, oldest first."""
        return {"steps": [r.as_dict() for r in tuple(self.steps)],
                "requests": [dict(r) for r in tuple(self.requests)]}

    def dump(self, path):
        """Both rings as JSON lines: ``{"record": "step" | "request", ...}``."""
        snap = self.snapshot()
        with open(path, "w") as f:
            for key, label in (("steps", "step"), ("requests", "request")):
                for rec in snap[key]:
                    # a caller may name its sequences with numpy integers
                    f.write(json.dumps({"record": label, **rec}, default=lambda o: o.item()) + "\n")
        return len(snap["steps"]) + len(snap["requests"])


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
snapshot = RECORDER.snapshot
dump = RECORDER.dump
