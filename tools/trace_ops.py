#!/usr/bin/env python3
"""Every device op of a kept trace by own time (no chip needed).

    python3 tools/trace_ops.py DIR [N]

``DIR``: what ``benchmark/run.py --keep-trace DIR`` left (the newest
``.xplane.pb`` under it); prints the ``N`` (60) ops with most own time as a
share of the device's busy time, then the same grouped by kind of op (the
name without its number): where a traced line's ``breakdown`` stops at ten."""
import collections, glob, json, os, re, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.harness import trace as tr
path = sorted(glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"), recursive=True))[-1]
t = tr.load(path)
ops = tr.op_seconds(t)
busy = tr.busy_seconds(t)
rows = sorted(ops.items(), key=lambda kv: -kv[1])
print("busy_s", busy, "ops", len(rows))
for name, s in rows[:int(sys.argv[2]) if len(sys.argv) > 2 else 60]:
    print(f"{100 * s / busy:6.2f}%  {s:8.4f}s  {name[:150]}")
groups = collections.Counter()
for name, s in rows:
    groups[re.sub(r"\.\d+", "", name.split(" ")[0]) + " " + " ".join(name.split(" ")[1:2])] += s
print("== by kind")
for name, s in groups.most_common(25):
    print(f"{100 * s / busy:6.2f}%  {s:8.4f}s  {name}")
