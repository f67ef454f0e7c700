"""The profiler's trace, reduced to what the per-layer metrics read.

``capture`` wraps ``jax.profiler`` around a window; ``load`` turns the
``.xplane.pb`` it leaves into a plain dict (:func:`load` keeps only the
device planes' lines and the benchmark's own host spans, so a recorded
trace can be kept as JSON and reduced again without a chip)::

    {"devices": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...],
                                   "XLA Modules": [...]}},
     "host": [[name, start_ns, dur_ns], ...]}     # spans named bench.*

Everything below ``load`` is arithmetic on that dict: busy time is the
union of the op intervals of a device, an idle gap is the space between
two of them, and a gap is laid at the door of the ``bench.*`` host span
that covers its middle (the benchmark wraps each call into the engine in
such a span, from its own files).
"""

import glob
import gzip
import json
import os
import re
import shutil
import sys
import tempfile
import threading

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute",
                        re.I)
# the device lines name an op by its whole HLO text:
#   %copy.36 = bf16[128,32,128]{2,1,0:T(8,128)(2,1)S(1)} copy(bf16[...] %fusion.131)
_HLO = re.compile(r"^%(?P<name>[^\s=]+) = (?P<shape>\(.*?\)|\S+) (?P<op>[\w-]+)\(")


def short_name(text):
    """``copy.36 copy bf16[128,32,128]``: the op's name, its opcode and
    the shape of its result without the layout (a tuple is ``(tuple)``)."""
    m = _HLO.match(text)
    if not m:
        return text[:96]
    shape = "(tuple)" if m["shape"].startswith("(") else re.sub(r"\{.*", "", m["shape"])
    return f"{m['name']} {m['op']} {shape}"


class Capture:
    """``start()`` … ``stop()`` around part of a window → ``.trace`` (the
    dict above) and ``.window_s``. The files go to a directory of their
    own under the run's ``TMPDIR`` and are removed after reading, unless
    ``keep`` names a directory to copy the ``.xplane.pb`` into."""

    def __init__(self, keep=None):
        self.keep = keep
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.trace = None
        self.window_s = None
        self._thread = None
        self._t0 = None

    @property
    def started(self):
        return self._t0 is not None or self._thread is not None

    def start(self, clock, background=False):
        import jax

        def go():
            # without the Python tracer: the client's polling loop alone would
            # fill the host buffer with function calls and crowd the spans out
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self._t0 = clock()

        if background:  # a serving loop must not stall while the profiler starts
            self._thread = threading.Thread(target=go, name="bench-trace-start")
            self._thread.start()
        else:
            go()

    def stop(self, clock):
        import jax
        if self._thread is not None:
            self._thread.join()
        self.window_s = clock() - self._t0
        jax.profiler.stop_trace()
        try:
            found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError(f"the profiler left no .xplane.pb under {self.dir}")
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(found[0], os.path.join(self.keep, "trace.xplane.pb"))
            self.trace = load(found[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.trace


def load(path):
    """``.xplane.pb`` | ``.json`` | ``.json.gz`` → the trace dict."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper():
            lines = {}
            for line in plane.lines:
                events = [[short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
                if events:
                    lines[line.name] = events
            if lines:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


# ------------------------------------------------------------------ reduction
def ops_of(trace):
    """→ {device: op events}, only devices on which an op ran."""
    return {d: lines[OPS_LINE] for d, lines in trace["devices"].items() if lines.get(OPS_LINE)}


def modules_of(trace):
    return {d: lines[MODULES_LINE] for d, lines in trace["devices"].items()
            if lines.get(MODULES_LINE)}


def union(intervals):
    """Sorted, merged ``[start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def subtract(intervals, cover):
    """Total length of ``intervals`` (merged) outside ``cover`` (merged)."""
    total, j = 0, 0
    for start, end in intervals:
        at = start
        while j < len(cover) and cover[j][1] <= at:
            j += 1
        i = j
        while i < len(cover) and cover[i][0] < end:
            if cover[i][0] > at:
                total += cover[i][0] - at
            at = max(at, cover[i][1])
            i += 1
        if at < end:
            total += end - at
    return total


def busy_seconds(trace):
    """Seconds in which an operation ran on the device, averaged over the
    devices that ran any (0.0 when none did)."""
    per_device = [sum(e - s for s, e in union([ev[1], ev[1] + ev[2]] for ev in events)) / 1e9
                  for events in ops_of(trace).values()]
    return sum(per_device) / len(per_device) if per_device else 0.0


def self_times(events):
    """An op that holds others (a ``while`` holds its body's ops, on the
    same line) is charged only the time none of them covers.
    → ``[(name, self_ns, is_leaf)]``."""
    out, stack = [], []          # stack of [name, end, self_ns, has_child]

    def close():
        name, _, self_ns, has_child = stack.pop()
        out.append((name, max(self_ns, 0), not has_child))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close()
        if stack:
            stack[-1][2] -= dur
            stack[-1][3] = True
        stack.append([name, start + dur, dur, False])
    while stack:
        close()
    return out


def op_seconds(trace):
    """→ {op name: seconds of its own}, summed over calls, averaged over
    devices."""
    ops = ops_of(trace)
    totals = {}
    for events in ops.values():
        for name, self_ns, _ in self_times(events):
            totals[name] = totals.get(name, 0) + self_ns
    return {name: ns / 1e9 / len(ops) for name, ns in totals.items()}


def matching_seconds(trace, pattern):
    rx = re.compile(pattern)
    return sum(s for name, s in op_seconds(trace).items() if rx.search(name))


def module_durations_ms(trace, min_ms=1.0):
    """Durations of the programs run on the first device, in ms; programs
    under ``min_ms`` (transfers, scalar bumps) are left out."""
    modules = modules_of(trace)
    if not modules:
        return []
    events = modules[sorted(modules)[0]]
    return [dur / 1e6 for _, _, dur in events if dur / 1e6 >= min_ms]


def exposed_collective_seconds(trace):
    """Seconds in collective ops with no other op running on the same
    device, averaged over devices."""
    per_device = []
    for events in ops_of(trace).values():
        # leaves only: a while op that holds the whole step is not "another op running"
        inner = sorted(events, key=lambda e: (e[1], -e[2]))
        leaves = [ev for ev, nxt in zip(inner, inner[1:] + [None])
                  if nxt is None or nxt[1] >= ev[1] + ev[2]]
        coll = union([ev[1], ev[1] + ev[2]] for ev in leaves if COLLECTIVE.search(ev[0]))
        rest = union([ev[1], ev[1] + ev[2]] for ev in leaves if not COLLECTIVE.search(ev[0]))
        per_device.append(subtract(coll, rest) / 1e9)
    return sum(per_device) / len(per_device) if per_device else 0.0


def idle_gaps(trace, top=10):
    """The idle time of the first device by what the host was doing:
    → ``[[label, seconds], ...]``, the totals per label first (longest
    first), then the longest single gaps, at most ``top`` entries."""
    ops = ops_of(trace)
    if not ops:
        return []
    busy = union([ev[1], ev[1] + ev[2]] for ev in ops[sorted(ops)[0]])
    spans = sorted((s, s + d, name) for name, s, d in trace["host"])
    totals, singles = {}, []
    for (_, end), (start, _) in zip(busy, busy[1:]):
        gap, mid = start - end, (start + end) // 2
        label = "between engine calls (host code of the layers above)"
        for s, e, name in spans:
            if s > mid:
                break
            if e >= mid:
                label = "inside " + name[len(SPAN_PREFIX):]
        totals[label] = totals.get(label, 0) + gap
        singles.append((gap, label))
    out = [[f"all gaps: {label}", ns / 1e9]
           for label, ns in sorted(totals.items(), key=lambda kv: -kv[1])]
    singles.sort(reverse=True)
    out += [[f"gap {i + 1}: {label}", gap / 1e9] for i, (gap, label) in enumerate(singles)]
    return out[:top]


def breakdown(trace, top=10):
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[name, s] for name, s in ops], "idle_gaps": idle_gaps(trace, top)}


def summarize(path, out=sys.stdout):
    """What a trace holds, for a reader who has not seen one: planes,
    lines, the first events of each with their statistics."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name} ({len(lines)} lines)", file=out)
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name}: {len(events)} events", file=out)
            for e in events[:4]:
                print(f"    {e.name} start={e.start_ns} dur={e.duration_ns} "
                      f"{dict(list(e.stats)[:12])}", file=out)


def record(path, out_path, seconds=0.15):
    """Keep the first ``seconds`` of device activity of a trace as JSON:
    how ``tests/data/trace_small.json`` was made."""
    full = load(path)
    t0 = min(ev[1] for lines in full["devices"].values() for ev in lines.get(OPS_LINE, [[0, 0]]))
    t1 = t0 + int(seconds * 1e9)

    def cut(events):  # what starts inside is kept, and ends with the piece at the latest
        return [[ev[0], ev[1], min(ev[2], t1 - ev[1])] for ev in events if t0 <= ev[1] < t1]

    small = {"devices": {d: {n: cut(evs) for n, evs in lines.items()
                             if n in (OPS_LINE, MODULES_LINE)}
                         for d, lines in full["devices"].items()},
             "host": cut(full["host"])}
    with open(out_path, "w") as f:
        json.dump(small, f, separators=(",", ":"))
    return small


if __name__ == "__main__":
    if len(sys.argv) > 2:
        record(sys.argv[1], sys.argv[2], *(float(a) for a in sys.argv[3:]))
    else:
        summarize(sys.argv[1])
