"""What the host did between two programs, read from the program's own
step records over the whole window (``deepspeed_tpu/utils/tracing.py``).

Since PR 36 a step record carries, beside its phases' enter and exit, the
CPU clock of the thread that opened it read at three points a step
(``cpu_marks``: the exits of ``ds.engine.pack``, ``ds.engine.fetch`` and
``ds.sched.accept``), what the collector and the compiler took meanwhile,
and - a pump pass - what the pump thread spent waiting for work
(``waited_ns``); the recorder's third ring holds an event for every
collector pass, compile and stall. The readers here take the engine and pump records that started
in the ``lookback_s`` before the traced run's window closed (the metric's
own file says how far back: the whole window, not the traced seconds).

**The gap** of an engine step is the interval from the previous engine
record's ``ds.engine.fetch`` exit (its result is on the host) to this
one's ``ds.engine.dispatch`` enter (the next program is launched); 0 where
the next was dispatched first (a pipelined step). It is split **by
overlap**, where ``harness/program_spans.gap_owners`` lays a whole device
gap at the one phase over its middle:

* ``scheduler``: inside a ``ds.sched.*`` phase (``plan``, ``accept``);
* ``engine``: inside an engine record (``pack`` before the dispatch,
  ``log`` after the fetch) or one of its ``ds.engine.*`` phases;
* ``gateway``: the rest - the pump pass outside those (``admit``,
  ``deliver``, its own code) and the pump's loop between two passes.

The three add up to the gap. An interval in which the pump waited for
work (a pump pass that started in it holds ``waited_ns``) is no gap
between two steps and is left out. What the device waits beyond this gap
- the launch after ``dispatch`` enters and the copy out before ``fetch``
exits - is inside dispatch -> fetch and is not counted here:
``facts.host_time.device_gap_ms`` sets the two side by side over the
traced seconds.

**The CPU share** of that time comes from the marks: two consecutive marks
of the pump thread bound a *segment*, named by the mark that ends it -
``accept`` (``ds.engine.fetch`` exit -> ``ds.sched.accept`` exit),
``prepare`` (-> the next ``ds.engine.pack`` exit: deliver, the pass's end,
admit, plan, pack) and ``device`` (-> ``ds.engine.fetch`` exit: the launch
and the wait). ``accept`` + ``prepare`` is the gap (to the few microseconds
between ``pack``'s exit and ``dispatch``'s enter).

Every reader returns ``None`` (the metric is left out of the line)
without a traced run, where the clocks could not be joined, and where a
record lacks the new fields or the snapshot the events: the parent of
PR 36.
"""

import bisect

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.stats import percentile
from benchmark.readers.program_spans import _serving

DEVICE = ("ds.engine.dispatch", "ds.engine.fetch")     # dispatch -> fetch is the device's
FIELDS = ("cpu_marks", "waited_ns", "thread")
SEGMENT = {"ds.sched.accept": "accept", "ds.engine.pack": "prepare", "ds.engine.fetch": "device"}
LAYERS = ("gateway", "scheduler", "engine")
NO_PHASE = "(pass, no phase)"
NO_PASS = "(between passes)"
GC_KEPT = 8     # collector passes of 1 ms or more are many; the line keeps the longest


def _ms(ns):
    return ns / 1e6


class _Spans:
    """Intervals that do not overlap one another, each with a payload:
    which of them overlap ``[a, b]``, and by how much."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: s[0])
        self.starts = [s[0] for s in self.spans]

    def over(self, a, b):
        """→ ``[(overlap, span), ...]`` of the spans that overlap ``[a, b]``."""
        out = []
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        while i < len(self.spans) and self.spans[i][0] < b:
            inside = min(self.spans[i][1], b) - max(self.spans[i][0], a)
            if inside > 0:
                out.append((inside, self.spans[i]))
            i += 1
        return out

    def overlap(self, a, b):
        return sum(inside for inside, _ in self.over(a, b))


def _device_phases(record):
    """→ (``ds.engine.dispatch`` enter, ``ds.engine.fetch`` exit) or None."""
    enter, exit_ = (ps.phase_intervals(record, name) for name in DEVICE)
    return (enter[0][0], exit_[-1][1]) if enter and exit_ else None


def split(steps):
    """The gaps of the engine records in ``steps`` (dicts as
    ``tracing.snapshot()`` gives them, pump passes among them), split by
    overlap. → ``{"gaps": [...], "left_out": n, "segments": {name: (cpu_ns,
    wall_ns)}}`` with one entry a counted engine step: ``{"seq", "start_ns",
    "ns", "gateway", "scheduler", "engine", "phases": {name: ns}}``, and the
    pump thread's CPU and wall time by segment between two marks; or None
    where a record lacks the fields this reads."""
    records = [r for r in steps if r["kind"] == "pump" or r["kind"] in ps.ENGINE_KINDS]
    if not records or any(f not in r for r in records for f in FIELDS):
        return None
    # (dispatch enter, fetch exit, record) of every engine record that has both
    launched = sorted(((*_device_phases(r), r) for r in records
                       if r["kind"] != "pump" and _device_phases(r)), key=lambda t: t[0])
    engine = [r for _, _, r in launched]
    pumps = sorted((r for r in records if r["kind"] == "pump"), key=lambda r: r["start_ns"])
    pump_starts = [r["start_ns"] for r in pumps]
    # every phase but the device's: they do not overlap one another
    phases = _Spans((enter, exit_, name) for r in records
                    for name, enter, exit_ in r["phases"] if name not in DEVICE)
    in_engine = _Spans(tr.union(
        [[r["start_ns"], r["end_ns"]] for r in engine if r["kind"] != "burst_async"]
        + [[enter, exit_] for r in engine for name, enter, exit_ in r["phases"]
           if name.startswith("ds.engine.")]))
    in_pass = _Spans(tr.union([r["start_ns"], r["end_ns"]] for r in pumps))

    def waited(a, b):
        """Did a pump pass that started in ``(a, b]`` wait for work first?"""
        return any(p["waited_ns"] for p in pumps[bisect.bisect_right(pump_starts, a):
                                                 bisect.bisect_right(pump_starts, b)])

    gaps, left_out = [], 0
    for (_, a, _), (b, _, cur) in zip(launched, launched[1:]):
        gap = {"seq": cur["seq"], "start_ns": a, "ns": max(0, b - a), "phases": {},
               **dict.fromkeys(LAYERS, 0)}
        if b > a:
            if waited(a, b):
                left_out += 1          # the pump had no work: not a gap between two steps
                continue
            for inside, (_, _, name) in phases.over(a, b):
                gap["phases"][name] = gap["phases"].get(name, 0) + inside
                if name.startswith("ds.sched."):
                    gap["scheduler"] += inside
            gap["engine"] = in_engine.overlap(a, b)
            gap["gateway"] = gap["ns"] - gap["scheduler"] - gap["engine"]
            passes = in_pass.overlap(a, b)
            gap["phases"][NO_PHASE] = max(0, passes - sum(gap["phases"].values()))
            gap["phases"][NO_PASS] = gap["ns"] - passes
        gaps.append(gap)
    # the pump thread's CPU clock between two of its marks, by the mark that ends the segment
    thread = pumps[-1]["thread"] if pumps else engine[-1]["thread"]
    marks = sorted((wall, cpu, name) for r in records if r["thread"] == thread
                   for name, wall, cpu in r["cpu_marks"])
    segments = {name: [0, 0] for name in SEGMENT.values()}
    for (a, cpu_a, _), (b, cpu_b, name) in zip(marks, marks[1:]):
        if name in SEGMENT and not waited(a, b):
            segments[SEGMENT[name]][0] += cpu_b - cpu_a
            segments[SEGMENT[name]][1] += b - a
    return {"gaps": gaps, "left_out": left_out, "segments": segments}


def _window(run, spec):
    """→ (the serving analysis, the recorder's snapshot, the records' clock
    ``lookback_s`` before the trace ended and when it ended) or None."""
    found = _serving(run)
    recorded = ps.records()
    if found is None or recorded is None:
        return None
    return found, recorded, found["end_ns"] - int(spec["lookback_s"] * 1e9), found["end_ns"]


def _analysis(run, spec):
    """Done once a run; the summary goes under ``facts.host_time``."""
    if "_host_time" in run:
        return run["_host_time"]
    out = run["_host_time"] = None
    window = _window(run, spec)
    if window is None:
        return out
    found, recorded, lo, hi = window
    steps = [r for r in recorded["steps"] if lo <= r["start_ns"] <= hi]
    out = split(steps)
    if out is None or not out["gaps"]:
        return None
    gaps = out["gaps"]
    n = len(gaps)
    out["mean_ms"] = {layer: _ms(sum(g[layer] for g in gaps)) / n for layer in LAYERS}
    lengths = [_ms(g["ns"]) for g in gaps]
    by_phase = {}
    for g in gaps:
        for name, ns in g["phases"].items():
            by_phase[name] = by_phase.get(name, 0) + ns
    engine = [r for r in steps if r["kind"] in ps.ENGINE_KINDS]
    longest = max(engine, key=lambda r: r["end_ns"] - r["start_ns"])
    widest = max(gaps, key=lambda g: g["ns"])
    facts = {
        "steps": n, "left_out_waiting": out["left_out"],
        "pipelined": sum(1 for g in gaps if not g["ns"]),
        "gap_ms": {"p50": percentile(lengths, 50), "p90": percentile(lengths, 90),
                   "max": max(lengths), "mean": sum(lengths) / n},
        "layers_ms": {**out["mean_ms"], "sum": sum(out["mean_ms"].values())},
        "by_phase_ms": {name: _ms(ns) / n
                        for name, ns in sorted(by_phase.items(), key=lambda kv: -kv[1])},
        # wall ms a step and CPU share of each segment between two CPU marks
        "cpu_by_segment": {name: {"ms": _ms(wall) / n, "cpu_share": 100.0 * cpu / wall}
                           for name, (cpu, wall) in out["segments"].items() if wall},
        "longest_record": _describe(longest),
        "longest_gap": {"seq": widest["seq"], "ms": _ms(widest["ns"]),
                        "phase": max(widest["phases"], key=widest["phases"].get)
                        if widest["phases"] else None},
        "events": _events(run, found, recorded, lo, hi),
        "device_gap_ms": _beside_the_device(run, found, gaps),
    }
    run.setdefault("facts", {})["host_time"] = facts
    run["_host_time"] = out
    return out


def _describe(record):
    phases = {name: exit_ - enter for name, enter, exit_ in record["phases"]}
    top = max(phases, key=phases.get) if phases else None
    return {"seq": record["seq"], "kind": record["kind"], "program": record["program"],
            "ms": _ms(record["end_ns"] - record["start_ns"]), "gc_ms": _ms(record["gc_ns"]),
            "compiles": record["compiles"], "phase": top,
            "phase_ms": _ms(phases[top]) if top else None}


def _events(run, found, recorded, lo, hi):
    """The recorder's events that ended in the window: every stall and
    compile, and of the collector's passes their number, their sum and the
    ``GC_KEPT`` longest; each with the seconds the first device idled under
    it where it falls in the traced part (the clocks are joined by
    ``facts.program_spans.offset_ns``)."""
    idle = ps._gaps(run["trace"]) if run.get("trace") else None
    extent = ps.extent_ns(run["trace"]) if run.get("trace") else None
    by_kind = {"stall": [], "compile": [], "gc": []}
    for event in recorded.get("events", ()):
        if not lo <= event["end_ns"] <= hi or event["kind"] not in by_kind:
            continue
        event = dict(event)
        a, b = event["start_ns"] + found["offset_ns"], event["end_ns"] + found["offset_ns"]
        if idle is not None and extent[0] <= b and a <= extent[1]:
            event["device_idle_s"] = sum(max(0, min(e, b) - max(s, a)) for s, e in idle) / 1e9
        by_kind[event["kind"]].append(event)
    passes = by_kind["gc"]
    by_kind["gc"] = {"passes": len(passes), "ms": _ms(sum(e["end_ns"] - e["start_ns"] for e in passes)),
                     "longest": sorted(passes, key=lambda e: e["start_ns"] - e["end_ns"])[:GC_KEPT]}
    return by_kind


def _beside_the_device(run, found, gaps):
    """Over the traced seconds: the host's gap a step (records) beside the
    device's idle time a step (trace). The device's is longer by the
    fetch's tail and the launch, which lie inside dispatch -> fetch."""
    trace = run.get("trace")
    extent = ps.extent_ns(trace) if trace else None
    if extent is None or not tr.ops_of(trace):
        return None
    lo, hi = extent[0] - found["offset_ns"], extent[1] - found["offset_ns"]
    traced = [g for g in gaps if lo <= g["start_ns"] <= hi]
    if not traced:
        return None
    host = _ms(sum(g["ns"] for g in traced)) / len(traced)
    device = (run["trace_window_s"] - tr.busy_seconds(trace)) * 1e3 / len(traced)
    return {"steps": len(traced), "host": host, "device": device, "device_minus_host": device - host}


# ------------------------------------------------------------------ the metrics
def gap_ms(run, spec):
    """Mean milliseconds an engine step of the gap that lies in the layer
    the metric's own file names under ``part``."""
    found = _analysis(run, spec)
    return None if found is None else found["mean_ms"][spec["part"]]


def host_cpu_share(run, spec):
    """The pump thread's CPU time over wall time, in %, over the segments
    that make up the gap (``accept`` and ``prepare``)."""
    found = _analysis(run, spec)
    if found is None:
        return None
    cpu, wall = (sum(found["segments"][name][i] for name in ("accept", "prepare")) for i in (0, 1))
    return 100.0 * cpu / wall if wall else None


def stalled_share(run, spec):
    """The ``stall`` events' ``excess_ms`` over the window, in %; 0 in a
    run that held none."""
    window = _window(run, spec)
    if window is None or "events" not in window[1]:
        return None
    _, recorded, lo, hi = window
    excess = sum(e["excess_ms"] for e in recorded["events"]
                 if e["kind"] == "stall" and lo <= e["end_ns"] <= hi)
    return 100.0 * excess / (spec["lookback_s"] * 1e3)
