"""The Solar Open 2 cell's own counts, and the delta rule against the chip's
memory bandwidth (``solar-open2-ep8-4l``).

The program counts on the device, in every step of the model kind whose
Kimi-delta-attention layers keep a state a sequence in a slot
(``model_runner.SolarOpen2Kind.step_counts``): ``n_state_slots``, the
sequences with a row in the step times the KDA layers - each the read and
the write of one slot of one layer, 64 x 128 x 128 float32 = 4.19 MB,
whatever the step's rows -, ``n_kda_rows``, the token-layers through the
delta rule, and ``n_scan_runs``, the (sequence, layer)s with more than one
row in the step: the runs a prompt chunk is cut into. They ride out with
the step's result into its step record (``counts``); the runner states the
layers and the state's shape under ``facts.solar_shapes``.

``kda_state_roofline`` = (the least bytes the delta rule has to move) / (the
own time of the device ops named ``kda_delta_rule`` in the trace) / (peak HBM
bytes/s), in %. The least bytes are :func:`kda_bytes`: every live slot of a
layer **once in and once out** - ``n_state_slots x 2 x H x d x d x 4`` - and
every row's operands in and result out - ``n_kda_rows x (q, k, v, the decays
and o of H x d float32, beta of H)`` - whatever implements the rule, so the
share cannot pass 100: no implementation moves less. The bound is **HBM**,
which is the truth for decode rows (a row brings 8.4 MB of state for
:func:`kda_flops`' 8.4 M operations: 1 operation a byte) and under-reads on
prompt rows: a run's later rows move 164 KB each and cost the same 8.4 M
multiply-adds on the vector unit, for which ``peaks.json`` states no peak -
so ``facts.kda.by_kind`` splits the records that hold prompt rows from the
decode-only ones, and the reading of a cell whose steps are mixed lies
between the two. Bytes and time are taken over the same programs: the step
records that lie whole inside the traced window, and the kernel's events
inside their device intervals.

Every reader returns ``None`` (the metric is left out) without a traced
run, with a program whose records carry no such counts (the parent's, or
another model kind's), or with a runner that states no ``solar_shapes``.
"""

import bisect
import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.jamba import _counted
from benchmark.readers.program_spans import BURSTS, _serving

KERNEL = re.compile(r"^kda_delta_rule")
COUNT, ROWS, RUNS = "n_state_slots", "n_kda_rows", "n_scan_runs"


def kda_bytes(state_slots, kda_rows, heads, head_dim, itemsize=4):
    """Least bytes through HBM for the KDA layers of steps that read and
    wrote ``state_slots`` (sequence, layer) states and ran ``kda_rows``
    (token, layer) rows: a state ``[heads, head_dim, head_dim]`` once in and
    once out; a row's ``q``, ``k``, ``v`` and decays in and ``o`` out,
    ``heads x head_dim`` wide, and its ``beta``, ``heads`` wide, float32 as
    the rule takes them."""
    return (state_slots * 2 * heads * head_dim * head_dim * itemsize
            + kda_rows * (5 * heads * head_dim + heads) * 4)


def kda_flops(kda_rows, heads, head_dim):
    """A row's arithmetic a layer, an operation an element of the state: the
    decay's product, ``S'^T k`` (two), the update's outer product (two) and
    ``S^T q`` (two): 7 x H x d x d, on the vector unit."""
    return kda_rows * 7 * heads * head_dim * head_dim


def _steps(run):
    """→ (the sums over the counted records that started in the traced
    window, their model steps, the shapes), or None."""
    found = _serving(run)
    shapes = run.get("facts", {}).get("solar_shapes")
    if found is None or not shapes:
        return None
    records = _counted(found["bursts"] + found["mixed"], ROWS)
    steps = sum(r["k"] for r in records)
    if not steps:
        return None
    facts = run["facts"].setdefault("kda_steps", {
        "records": len(records), "model_steps": steps,
        **{name: sum(r["counts"].get(name, 0) for r in records)
           for name in (COUNT, ROWS, RUNS)}})
    return facts, steps, shapes


def state_slots_per_step(run, spec):
    """Sequences whose state a model step reads and writes, in the mean
    over the window's steps."""
    found = _steps(run)
    if found is None:
        return None
    facts, steps, shapes = found
    return facts[COUNT] / (shapes["kda_layers"] * steps)


def scan_runs_per_step(run, spec):
    """Sequences with more than one row in a model step - the runs its
    prompt rows are cut into - in the mean over the window's steps."""
    found = _steps(run)
    if found is None:
        return None
    facts, steps, shapes = found
    return facts[RUNS] / (shapes["kda_layers"] * steps)


def _whole_records(run):
    """→ the counted records whole inside the trace, with their device
    intervals on the trace's clock, or None."""
    found = _serving(run)
    if found is None or not run.get("facts", {}).get("solar_shapes"):
        return None
    extent = ps.extent_ns(run["trace"])
    chosen = []
    for r in _counted(ps.in_window(run["trace"], ps.records()["steps"], found["offset_ns"]),
                      ROWS):
        if r["kind"] not in ps.ENGINE_KINDS:
            continue
        enter = [t for t, _ in ps.phase_intervals(r, "ds.engine.dispatch")]
        exit_ = [t for _, t in ps.phase_intervals(r, "ds.engine.fetch")]
        if not enter or not exit_:
            continue
        lo, hi = enter[0] + found["offset_ns"], exit_[-1] + found["offset_ns"]
        if lo >= extent[0] and hi <= extent[1]:
            chosen.append((lo, hi, r))
    return sorted(chosen, key=lambda c: c[0])


def kda_state_roofline(run, spec):
    chosen = _whole_records(run)
    if not chosen:
        return None
    s = run["facts"]["solar_shapes"]
    starts = [lo for lo, _, _ in chosen]
    by_device = []
    for events in tr.ops_of(run["trace"]).values():
        ns = [0] * len(chosen)
        for name, start, dur in events:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < chosen[i][1] and KERNEL.match(name):
                ns[i] += dur
        by_device.append(ns)
    if not by_device:
        return None
    kernel_ns = [sum(col) / len(by_device) for col in zip(*by_device)]    # a record, over devices
    peak = peaks_of(run["device"]["kind"])["hbm_gbytes_per_s"] * 1e9

    def share(pick):
        picked = [(t, r) for t, (_, _, r) in zip(kernel_ns, chosen) if pick(r)]
        ns = sum(t for t, _ in picked)
        if ns <= 0:
            return None
        slots = sum(r["counts"][COUNT] for _, r in picked)
        rows = sum(r["counts"].get(ROWS, 0) for _, r in picked)
        moved = kda_bytes(slots, rows, s["heads"], s["head_dim"], s["state_itemsize"])
        return {"programs": len(picked), COUNT: slots, ROWS: rows,
                "bytes": moved, "flops": kda_flops(rows, s["heads"], s["head_dim"]),
                "kernel_s": ns / 1e9, "achieved_gb_s": moved / ns,
                "roofline_pct": 100.0 * moved / (ns / 1e9) / peak}

    def decode_only(r):
        return r["kind"] in BURSTS or not r.get("n_prompt_tokens")

    whole = share(lambda r: True)
    if whole is None:
        return None
    run["facts"]["kda_roofline"] = {
        **whole, "by_kind": {"decode_only": share(decode_only),
                             "with_prompt_rows": share(lambda r: not decode_only(r))}}
    return whole["roofline_pct"]


def trace_facts(run):
    """What the traced run says of the constraints the kernel was written to
    (``facts.kda``): ``by_kind``, :func:`kda_state_roofline`'s split of the
    decode-only records from those with prompt rows; and, each a % of device
    busy time in ops whose result has a given shape:
    ``state_pool_copy_share``, the state pool's or one layer of it (copies,
    slices, scatters: nothing should produce one - the rule's own custom call
    is named for the kernel and is not among them); ``state_tensor_share``, a
    ``[T, H, d, d]`` result, which nothing should produce either;
    ``tail_pool_share``, the convolution tails' pool or a layer of it
    (``_conv_with_tail``'s gather of the step's tails and the in-place
    scatter that writes them back, by XLA). None without a trace or shapes."""
    s = run.get("facts", {}).get("solar_shapes")
    if run.get("trace") is None or not s:
        return None
    busy = tr.busy_seconds(run["trace"])
    if busy <= 0:
        return None
    L, NS, H, d = s["kda_layers"], s["slots"] + 1, s["heads"], s["head_dim"]
    patterns = {"state_pool_copy_share": rf"\[({L},)?{NS},{H},{d},{d}\]",
                "state_tensor_share": rf"\[\d+,{H},{d},{d}\]",
                "tail_pool_share": rf"\[({L},)?{NS},\d,{3 * H * d}\]"}
    roofline = run["facts"].get("kda_roofline") or {}
    return {**{name: 100.0 * tr.matching_seconds(run["trace"], pattern) / busy
               for name, pattern in patterns.items()}, "patterns": patterns,
            "by_kind": roofline.get("by_kind")}
