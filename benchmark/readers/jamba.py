"""The Jamba cell's own counts, and the selective scan against the chip's
memory bandwidth (``jamba2-3b``).

The program counts on the device, in every step of the model kind whose
Mamba-1 layers keep a state a sequence in a slot
(``model_runner.JambaKind.step_counts``): ``n_state_slots``, the sequences
with a row in the step times the Mamba layers - each the read and the
write of one slot of one layer, 16 x 5120 float32 = 320 KB, whatever the
step's rows -, ``n_ssm_rows``, the token-layers through the scan, and
``n_scan_runs``, the (sequence, layer)s with more than one row in the step:
the runs a prompt chunk is cut into. They ride out with the step's result
into its step record (``counts``); the runner states the layers and the
state's shape under ``facts.jamba_shapes``.

``selective_scan_roofline`` = (the least bytes the scan has to move) / (the
own time of the device ops named ``selective_scan`` in the trace) / (peak
HBM bytes/s), in %. The least bytes are :func:`scan_bytes`: every live slot
of a layer **once in and once out** - ``n_state_slots x 2 x N x C x 4`` -
and every row's operands in and result out - ``n_ssm_rows x (x, Delta and y
of C float32, B and C of N)`` - whatever implements the scan, so the share
cannot pass 100: no implementation moves less. The bound is **HBM**, which
is the truth for decode rows (a row brings 640 KB of state for
:func:`scan_flops`' 0.6 M operations: 1 operation a byte) and under-reads
on prompt rows: a run's later rows move 60 KB each and cost the same 82 k
``exp`` and 0.6 M multiply-adds on the vector and transcendental units, for
which ``peaks.json`` states no peak - so ``facts.selective_scan.by_kind``
splits the records that hold prompt rows from the decode-only ones, and the
reading of a cell whose every step is mixed lies between the two. Bytes and
time are taken over the same programs: the step records that lie whole
inside the traced window, and the kernel's events inside their device
intervals.

Every reader returns ``None`` (the metric is left out) without a traced
run, with a program whose records carry no such counts (the parent's, or
another model kind's), or with a runner that states no ``jamba_shapes``.
"""

import bisect
import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.program_spans import BURSTS, _serving

KERNEL = re.compile(r"^selective_scan")
COUNT = "n_state_slots"


def scan_bytes(state_slots, ssm_rows, channels, state_columns, itemsize=4):
    """Least bytes through HBM for the Mamba layers of steps that read and
    wrote ``state_slots`` (sequence, layer) states and ran ``ssm_rows``
    (token, layer) rows: a state once in and once out; a row's ``x`` and
    ``Delta`` in and ``y`` out, ``channels`` wide, and its ``B`` and ``C``,
    ``state_columns`` wide, float32 as the scan takes them."""
    return (state_slots * 2 * state_columns * channels * itemsize
            + ssm_rows * (3 * channels + 2 * state_columns) * 4)


def scan_flops(ssm_rows, channels, state_columns):
    """A row's arithmetic a layer, an operation an element of the state:
    ``Delta A``, ``exp`` (counted once), the decay's product, ``Delta x B``
    (two), the sum, and ``S C`` (two): 7 x N x C."""
    return ssm_rows * 7 * channels * state_columns


def _counted(records, name):
    return [r for r in records if r.get("counts") and name in r["counts"]]


def _steps(run):
    """→ (the counted records that started in the traced window, their model
    steps, the shapes), or None."""
    found = _serving(run)
    shapes = run.get("facts", {}).get("jamba_shapes")
    if found is None or not shapes:
        return None
    records = _counted(found["bursts"] + found["mixed"], COUNT)
    steps = sum(r["k"] for r in records)
    if not steps:
        return None
    facts = run["facts"].setdefault("scan_steps", {
        "records": len(records), "model_steps": steps,
        **{name: sum(r["counts"].get(name, 0) for r in records)
           for name in ("n_state_slots", "n_ssm_rows", "n_scan_runs")}})
    return facts, steps, shapes


def state_slots_per_step(run, spec):
    """Sequences whose state a model step reads and writes, in the mean
    over the window's steps."""
    found = _steps(run)
    if found is None:
        return None
    facts, steps, shapes = found
    return facts["n_state_slots"] / (shapes["mamba_layers"] * steps)


def scan_runs_per_step(run, spec):
    """Sequences with more than one row in a model step - the runs its
    prompt rows are cut into - in the mean over the window's steps."""
    found = _steps(run)
    if found is None:
        return None
    facts, steps, shapes = found
    return facts["n_scan_runs"] / (shapes["mamba_layers"] * steps)


def _whole_records(run):
    """→ the counted records whole inside the trace, with their device
    intervals on the trace's clock, or None."""
    found = _serving(run)
    if found is None or not run.get("facts", {}).get("jamba_shapes"):
        return None
    extent = ps.extent_ns(run["trace"])
    chosen = []
    for r in _counted(ps.in_window(run["trace"], ps.records()["steps"], found["offset_ns"]),
                      COUNT):
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


def selective_scan_roofline(run, spec):
    chosen = _whole_records(run)
    if not chosen:
        return None
    s = run["facts"]["jamba_shapes"]
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
        slots = sum(r["counts"]["n_state_slots"] for _, r in picked)
        rows = sum(r["counts"].get("n_ssm_rows", 0) for _, r in picked)
        moved = scan_bytes(slots, rows, s["channels"], s["state_columns"], s["state_itemsize"])
        return {"programs": len(picked), "n_state_slots": slots, "n_ssm_rows": rows,
                "bytes": moved, "flops": scan_flops(rows, s["channels"], s["state_columns"]),
                "kernel_s": ns / 1e9, "achieved_gb_s": moved / ns,
                "roofline_pct": 100.0 * moved / (ns / 1e9) / peak}

    def decode_only(r):
        return r["kind"] in BURSTS or not r.get("n_prompt_tokens")

    whole = share(lambda r: True)
    if whole is None:
        return None
    run["facts"]["selective_scan"] = {
        **whole, "by_kind": {"decode_only": share(decode_only),
                             "with_prompt_rows": share(lambda r: not decode_only(r))}}
    return whole["roofline_pct"]


def trace_facts(run):
    """What the traced run says of the constraints the kernel was written to,
    each a % of device busy time in ops whose result has a given shape:
    ``state_pool_copy_share``, the state pool's or one layer of it (copies,
    slices, scatters: nothing should produce one - the scan's own custom
    call is named for the kernel and is not among them);
    ``scan_tensor_share``, a ``[T, C, N]`` / ``[T, N, C]`` result, which
    nothing should produce either; ``tail_pool_share``, the convolution
    tails' pool or a layer of it (``_conv_with_tail``'s gather of the step's
    tails and the in-place scatter that writes them back, by XLA). None
    without a trace or shapes."""
    s = run.get("facts", {}).get("jamba_shapes")
    if run.get("trace") is None or not s:
        return None
    busy = tr.busy_seconds(run["trace"])
    if busy <= 0:
        return None
    L, NS, N, C = s["mamba_layers"], s["slots"] + 1, s["state_columns"], s["channels"]
    patterns = {"state_pool_copy_share": rf"\[({L},)?{NS},{N},{C}\]",
                "scan_tensor_share": rf"\[\d+,({C},{N}|{N},{C})\]",
                "tail_pool_share": rf"\[({L},)?{NS},\d,{C}\]"}
    return {**{name: 100.0 * tr.matching_seconds(run["trace"], pattern) / busy
               for name, pattern in patterns.items()}, "patterns": patterns}
