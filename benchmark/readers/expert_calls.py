"""The grouped expert matmuls, a call: the mean time of one.

A call is one device event whose name matches the metric file's
``calls`` pattern (a custom call with an array for a result: XLA's
``ragged-dot`` also issues one of ~2 us with a tuple's, a quarter of the
events by number, which is not a matmul): one grouped matmul of a
layer's routed experts in either direction of the FFN, whichever
program issued it (the Pallas
``gmm_ragged_dot`` or, where that kernel cannot run and before PR 31,
XLA's ``ragged-dot`` custom call). ``ms_a_call`` = the calls' own time
in the device trace over their number; ``facts.expert_matmul`` keeps the
same by result shape (the padded rows say which program), and the GB/s
the mean would be **if every call read one matrix of every expert of the
layer** (``experts x hidden x width x itemsize`` from the cell's
configuration file, under the keys the metric file names).

That is not a roofline share and is not reported as one: a call reads
only the experts that have a row, and at the whole stack's bytes the
kernel of PR 31 reads 103 % of the chip's 819 GB/s in
``moonlight16b-longgen`` (0.44 ms a 768-row call in the cell, 0.55 ms in
``tools/kernel_census.py`` where routing is uniform and all 64 experts
have rows: with random weights the router is skewed, and by the kernel's
own time about a fifth of the experts have no row in a step). A share
needs the number of groups with rows a call, which only the device
knows (PERF.md section 7).

→ ``None`` (the metric is left out) without a traced run or where no
event matches.
"""

import json
import os
import re

from benchmark.harness import trace as tr
from benchmark.harness.spec import BENCH_DIR


def ms_a_call(run, spec):
    if run.get("trace") is None:
        return None
    rx = re.compile(spec["calls"])
    by_shape = {}
    devices = tr.ops_of(run["trace"])
    for events in devices.values():
        for name, _, dur in events:
            if rx.search(name):
                seen = by_shape.setdefault(name.rsplit(" ", 1)[-1], [0, 0])
                seen[0] += 1
                seen[1] += dur
    calls = sum(n for n, _ in by_shape.values())
    ns = sum(t for _, t in by_shape.values())
    if not calls or ns <= 0:
        return None
    with open(os.path.join(BENCH_DIR, "configs", f"{spec['config']}.json")) as f:
        model = json.load(f)
    whole = model[spec["experts"]] * model["hidden_size"] * model[spec["width"]] * spec["itemsize"]
    run.setdefault("facts", {})["expert_matmul"] = {
        "calls": calls // len(devices), "ms_a_call": ns / calls / 1e6,
        "by_result_shape": {shape: {"calls": n // len(devices), "ms_a_call": t / n / 1e6}
                            for shape, (n, t) in sorted(by_shape.items())},
        "whole_stack_bytes": whole, "gb_s_if_whole_stack": whole * calls / ns}
    return ns / calls / 1e6
