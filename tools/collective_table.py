#!/usr/bin/env python3
"""The collectives of one training step, by where they sit:

    python3 tools/collective_table.py TRACE [MIN_STEP_MS]

``TRACE`` is what ``benchmark/run.py --trace 1 --keep-trace DIR`` leaves
(``.xplane.pb``) or a trace kept as ``.json`` / ``.json.gz``
(``benchmark/harness/trace.load``). Of the first device it takes the
middle program of at least ``MIN_STEP_MS`` (100), and prints every
collective op (``harness/trace.COLLECTIVE``: the reader of
``collective_exposed.train`` counts the same) by name and shape with its
count, own time and the part of it in which no other op ran, under the
``while`` loop that holds it (``while0`` the forward layer loop of a
training step, ``while1`` the backward), or ``before`` / ``between`` /
``after`` the loops; then each loop's largest ops. No chip: arithmetic on
a trace. ``PERF.md`` section 5's table of ``mistral7b-zero3-x4`` is its
output (PR 43).
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import trace  # noqa: E402


def _family(name):
    """``fusion.123 fusion bf16[..]`` -> ``fusion fusion bf16[..]``."""
    head, _, rest = name.partition(" ")
    return re.sub(r"\.\d+", "", head) + " " + rest


def table(loaded, min_step_ms=100.0, top=14, out=sys.stdout):
    device = sorted(trace.ops_of(loaded))[0]
    steps = [m for m in trace.modules_of(loaded)[device] if m[2] >= min_step_ms * 1e6]
    _, start, length = steps[len(steps) // 2]
    inside = sorted((e for e in trace.ops_of(loaded)[device] if start <= e[1] < start + length),
                    key=lambda e: (e[1], -e[2]))
    # an op that holds others (a while holds its body's ops) is no leaf
    leaves = [e for e, nxt in zip(inside, inside[1:] + [None]) if nxt is None or nxt[1] >= e[1] + e[2]]
    loops = [e for e in inside if " while " in e[0]]

    def place(event):
        for i, (_, at, dur) in enumerate(loops):
            if at <= event[1] < at + dur:
                return f"while{i}"
        if loops and event[1] < loops[0][1]:
            return "before"
        if loops and event[1] >= loops[-1][1] + loops[-1][2]:
            return "after"
        return "between"

    others = trace.union([e[1], e[1] + e[2]] for e in leaves if not trace.COLLECTIVE.search(e[0]))
    rows, totals = {}, {}
    for e in leaves:
        if trace.COLLECTIVE.search(e[0]):
            alone = trace.subtract([[e[1], e[1] + e[2]]], others)
            for key, into in (((place(e), _family(e[0])), rows), (place(e), totals)):
                entry = into.setdefault(key, [0, 0, 0])
                entry[0] += 1
                entry[1] += e[2]
                entry[2] += alone
    print(f"step {length / 1e6:.2f} ms on {device}; loops "
          f"{[round(w[2] / 1e6, 2) for w in loops]} ms; collectives by place:", file=out)
    for (where, name), (n, own, alone) in sorted(rows.items(), key=lambda kv: (kv[0][0], -kv[1][2])):
        print(f"  {where:8s} {n:4d} x {name[:90]:90s} own {own / 1e6:8.3f} ms exposed {alone / 1e6:8.3f} ms",
              file=out)
    for where, (n, own, alone) in sorted(totals.items()):
        print(f"TOTAL {where:8s} n {n:5d} own {own / 1e6:8.3f} ms exposed {alone / 1e6:8.3f} ms", file=out)
    print(f"exposed, all devices, the whole trace: {trace.exposed_collective_seconds(loaded):.4f} s", file=out)
    for i, (_, at, dur) in enumerate(loops):
        sums = {}
        for e in leaves:
            if at <= e[1] < at + dur:
                entry = sums.setdefault(_family(e[0]), [0, 0])
                entry[0] += 1
                entry[1] += e[2]
        print(f"-- while{i} {dur / 1e6:.2f} ms: largest ops", file=out)
        for name, (n, total) in sorted(sums.items(), key=lambda kv: -kv[1][1])[:top]:
            print(f"     {n:5d} x {name[:100]:100s} {total / 1e6:8.3f} ms", file=out)


if __name__ == "__main__":
    table(trace.load(sys.argv[1]), *(float(a) for a in sys.argv[2:3]))
