"""The program's own step records and request stamps, laid on the device
trace's clock.

Since PR 24 the program keeps, in ``deepspeed_tpu/utils/tracing.py``, a
record of every model program it runs (kind, ``k``, rows, tokens, and
the phases ``ds.sched.plan`` … ``ds.engine.fetch`` with enter and exit)
and of every request's life (submitted, admitted, first scheduled, first
token, ended). They are stamped with ``time.perf_counter_ns()``; the
profiler's ``start_ns`` count from the start of its session. This file
joins the two.

**Two span sources exist for now, and which to read.** ``spans.py`` wraps
``engine.put`` / ``engine.decode_burst`` from outside in ``bench.*``
annotations: they are on the profiler's clock but know nothing of what
happens inside or above the call, and they are what the older metrics
(``queue_wait_p90_ms``, ``tokens_per_step``, the ``breakdown``) read. The
program's records know the program, ``k``, the prompt tokens, the pump
pass and the request — a reader of anything *inside* the program reads
them, through this file. The ``bench.*`` spans stay because
``trace.load`` keeps only host events named ``bench.*``: each of them
wraps exactly one step record of kind ``put`` / ``burst``, and that pair
is the only thing both clocks see. :func:`align` finds the offset from
it. A later ``benchmark`` PR that lets ``trace.load`` keep the ``ds.*``
events (they are in the same ``.xplane.pb``) can retire the wrappers and
the alignment.

A checkout whose program has no recorder (the parent of PR 24) gives
``records() is None``; every reader built on this file then returns
``None`` and the metric is left out of the line.
"""

import bisect
from statistics import median

from benchmark.harness import trace as tr

SPAN_KIND = {"bench.engine.put": "put", "bench.engine.decode_burst": "burst"}
ENGINE_KINDS = ("put", "burst", "burst_async", "verify")
DURATION_TOLERANCE_NS = 500_000     # a span and the record inside it
MAX_RESIDUAL_NS = 1_000_000
MIN_MATCHED = 0.95


def records():
    """→ ``{"steps": [...], "requests": [...]}`` from the program's
    recorder, or None where the program has none."""
    try:
        from deepspeed_tpu.utils import tracing
    except ImportError:
        return None
    return tracing.snapshot()


def phase_intervals(record, prefix):
    return [(enter, exit_) for name, enter, exit_ in record["phases"] if name.startswith(prefix)]


def device_interval_ns(record):
    """``ds.engine.dispatch`` enter → ``ds.engine.fetch`` exit: from the
    launch of the program to its result on the host. None without both."""
    enter = [t for t, _ in phase_intervals(record, "ds.engine.dispatch")]
    exit_ = [t for _, t in phase_intervals(record, "ds.engine.fetch")]
    return exit_[-1] - enter[0] if enter and exit_ else None


def align(trace, steps):
    """The offset between the two clocks, from the ``bench.*`` spans of
    the trace and the step records each of them wraps.

    The spans, in order, must be a run of the ``put`` / ``burst`` records
    with the same sequence of kinds; a span *matches* its record when
    their durations agree to ``DURATION_TOLERANCE_NS`` (they differ from
    call to call by milliseconds, so a wrong run does not match). →
    ``{"offset_ns", "residual_ns", "worst_ns", "matched", "spans"}`` with
    ``offset_ns`` = median(span start − record start) and ``residual_ns``
    the median distance from it; None when no run or more than one run
    fits, fewer than ``MIN_MATCHED`` of the spans match, or the residual
    is over ``MAX_RESIDUAL_NS`` — a reader then leaves its metric out."""
    spans = sorted((start, dur, SPAN_KIND[name]) for name, start, dur in trace["host"]
                   if name in SPAN_KIND)
    calls = [r for r in steps if r["kind"] in ("put", "burst")]
    if not spans or len(calls) < len(spans):
        return None
    kinds = [s[2] for s in spans]
    need = MIN_MATCHED * len(spans)
    fits = []
    for j in range(len(calls) - len(spans) + 1):
        run = calls[j:j + len(spans)]
        if [c["kind"] for c in run] != kinds:
            continue
        pairs = [(s, c) for s, c in zip(spans, run)
                 if abs(s[1] - (c["end_ns"] - c["start_ns"])) <= DURATION_TOLERANCE_NS]
        if len(pairs) >= need:
            fits.append(pairs)
    if len(fits) != 1:
        return None
    deltas = [s[0] - c["start_ns"] for s, c in fits[0]]
    offset = int(median(deltas))
    off_by = sorted(abs(d - offset) for d in deltas)
    out = {"offset_ns": offset, "residual_ns": int(median(off_by)), "worst_ns": off_by[-1],
           "matched": len(fits[0]), "spans": len(spans)}
    return out if out["residual_ns"] <= MAX_RESIDUAL_NS else None


def extent_ns(trace):
    """First start and last end of anything in the trace (profiler clock)."""
    events = [ev for lines in trace["devices"].values() for evs in lines.values() for ev in evs]
    events += trace["host"]
    if not events:
        return None
    return min(ev[1] for ev in events), max(ev[1] + ev[2] for ev in events)


def in_window(trace, steps, offset_ns):
    """The step records that started inside the traced window."""
    extent = extent_ns(trace)
    if extent is None:
        return []
    lo, hi = extent[0] - offset_ns, extent[1] - offset_ns
    return [r for r in steps if lo <= r["start_ns"] <= hi]


class _Cover:
    """Intervals of one thread (sorted, not overlapping): does one hold t?"""

    def __init__(self, intervals):
        self.intervals = sorted(intervals)
        self.starts = [s for s, _ in self.intervals]

    def holds(self, t):
        i = bisect.bisect_right(self.starts, t) - 1
        return i >= 0 and self.intervals[i][1] >= t


def _gaps(trace):
    """The idle gaps of the first device: ``[(start, end), ...]`` between
    two of its ops, profiler clock. None without device ops."""
    ops = tr.ops_of(trace)
    if not ops:
        return None
    busy = tr.union([ev[1], ev[1] + ev[2]] for ev in ops[sorted(ops)[0]])
    return [(end, start) for (_, end), (start, _) in zip(busy, busy[1:])]


def gap_owners(trace, steps, offset_ns):
    """The idle gaps of the first device, each laid at the layer whose
    phase covers its middle: ``engine`` (a ``ds.engine.*`` phase, or
    anywhere inside a step record), then ``scheduler`` (``ds.sched.*``),
    then ``gateway`` (elsewhere inside a pump pass), else ``outside`` (no
    pump pass: the pump was waiting for work). → seconds by layer; they
    add up to the idle time between the first and the last op. None
    without device ops."""
    gaps = _gaps(trace)
    if gaps is None:
        return None
    engine, scheduler, gateway = [], [], []
    for r in steps:
        if r["kind"] == "pump":
            gateway.append((r["start_ns"], r["end_ns"]))
        elif r["kind"] in ENGINE_KINDS:
            engine += phase_intervals(r, "ds.engine.")
            if r["kind"] != "burst_async":  # its record stays open while others run
                engine.append((r["start_ns"], r["end_ns"]))
        scheduler += phase_intervals(r, "ds.sched.")
    layers = (("engine", _Cover(tr.union(engine))), ("scheduler", _Cover(tr.union(scheduler))),
              ("gateway", _Cover(tr.union(gateway))))
    seconds = {"gateway": 0.0, "scheduler": 0.0, "engine": 0.0, "outside": 0.0}
    for start, end in gaps:
        mid = (start + end) // 2 - offset_ns
        owner = next((name for name, cover in layers if cover.holds(mid)), "outside")
        seconds[owner] += (end - start) / 1e9
    return seconds


def gap_phases(trace, steps, offset_ns):
    """The same gaps by the innermost ``ds.*`` phase that covers their
    middle (``(none)``: no phase does), for the breakdown below the
    layers. → ``{phase name: seconds}``, None without device ops."""
    gaps = _gaps(trace)
    if gaps is None:
        return None
    covers = {}
    for r in steps:
        for name, enter, exit_ in r["phases"]:
            covers.setdefault(name, []).append((enter, exit_))
    covers = {name: _Cover(tr.union(spans)) for name, spans in covers.items()}
    # a phase inside another is the shorter of the two: ask the short ones first
    order = sorted(covers, key=lambda n: median(e - s for s, e in covers[n].intervals))
    seconds = {}
    for start, end in gaps:
        mid = (start + end) // 2 - offset_ns
        name = next((n for n in order if covers[n].holds(mid)), "(none)")
        seconds[name] = seconds.get(name, 0.0) + (end - start) / 1e9
    return seconds
