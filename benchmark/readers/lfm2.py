"""The LFM2 cell's own counts, and the paged attention kernel against the
chip's memory bandwidth at a head of 64 (``lfm2-24b-a2b-10l``).

The program counts on the device, in every step of the model kind whose
``conv`` operators keep their tail in a slot
(``model_runner.Lfm2Kind.step_counts``): ``n_tail_slots``, the sequences
with a row in the step times the ``conv`` layers - each the read and the
write of one slot of one layer, two rows of the hidden width -,
``n_conv_rows``, the token-layers through the convolution, and
``n_ctx_seq_tokens``: over the step's sequences, the context positions
each attends to, counted **once a sequence** however many rows it has in
the step. They ride out with the step's result into its step record
(``counts``); the runner states the layers and the heads under
``facts.lfm2_shapes``.

``paged_attn_roofline`` = (the least bytes an attention layer has to move)
/ (``paged_decode_attention``'s own time in the device trace) / (peak HBM
bytes/s), in %. The least bytes are :func:`kernel_bytes`: every key and
value row of every attended context **once a sequence a layer a step** -
``n_ctx_seq_tokens x attention layers x kv_heads x head_dim x 2 x
itemsize`` (2048 B a token a layer here) - whatever implements the
attention. Today's kernel walks a step's rows one by one and fetches a
chunk's context again for each of its rows, so in a 512-row prompt step it
moves many times that (~9 x in the cell: PERF.md, PR 41) and the share reads low; a kernel that shares
a chunk's fetches among its rows raises it and cannot push it past 100,
because no implementation moves less. In decode bursts (one row a
sequence) the least bytes are what the kernel moves, and the share is its
own roofline share: ``facts.paged_attn.bursts`` has it alone. The bound is
**HBM**: a key-value head's group of 4 query rows does ``2 x 4 x 2 x 64``
operations on a row's 256 bytes of keys and values - 4 operations a byte,
under the chip's ~240 a byte at the bf16 peak; :func:`kernel_flops` is
kept beside the bytes so that a reader of a trace can check the bound.
Bytes and time are taken over the same programs: the step records that lie
whole inside the traced window, and the kernel's events inside their
device intervals.

Every reader returns ``None`` (the metric is left out) without a traced
run, with a program whose records carry no such counts (the parent's, or
another model kind's), or with a runner that states no ``lfm2_shapes``.
"""

import bisect
import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.program_spans import BURSTS, _serving

KERNEL = re.compile(r"^paged_decode_attention")
COUNT = "n_ctx_seq_tokens"


def kernel_bytes(ctx_seq_tokens, attn_layers, kv_heads, head_dim, itemsize):
    """Least bytes through HBM for the attention layers of steps whose
    sequences attend to ``ctx_seq_tokens`` positions in all (once a
    sequence a step): a position is ``kv_heads x head_dim`` keys and as
    many values, a layer."""
    return ctx_seq_tokens * attn_layers * kv_heads * head_dim * 2 * itemsize


def kernel_flops(ctx_row_tokens, attn_layers, heads, head_dim):
    """Multiply-adds counted as two: scores and values, every query head,
    over ``ctx_row_tokens`` (row, attended position) pairs a layer."""
    return ctx_row_tokens * attn_layers * heads * 2 * 2 * head_dim


def _counted(records, name):
    return [r for r in records if r.get("counts") and name in r["counts"]]


def tail_slots_per_step(run, spec):
    """Sequences whose convolution tails a model step reads and writes, in
    the mean over the window's steps."""
    found = _serving(run)
    shapes = run.get("facts", {}).get("lfm2_shapes")
    if found is None or not shapes:
        return None
    records = _counted(found["bursts"] + found["mixed"], "n_tail_slots")
    steps = sum(r["k"] for r in records)
    if not steps:
        return None
    slots = sum(r["counts"]["n_tail_slots"] for r in records)
    run["facts"]["tail_slots"] = {
        "records": len(records), "model_steps": steps, "n_tail_slots": slots,
        "n_conv_rows": sum(r["counts"].get("n_conv_rows", 0) for r in records)}
    return slots / (shapes["conv_layers"] * steps)


def _whole_records(run):
    """→ the counted records whole inside the trace, with their device
    intervals on the trace's clock, or None."""
    found = _serving(run)
    if found is None or not run.get("facts", {}).get("lfm2_shapes"):
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


def paged_attn_roofline(run, spec):
    chosen = _whole_records(run)
    if not chosen:
        return None
    s = run["facts"]["lfm2_shapes"]
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
        ns = sum(t for t, (_, _, r) in zip(kernel_ns, chosen) if pick(r))
        ctx = sum(r["counts"][COUNT] for _, _, r in chosen if pick(r))
        moved = kernel_bytes(ctx, s["attn_layers"], s["kv_heads"], s["head_dim"], s["kv_itemsize"])
        if ns <= 0:
            return None
        return {"programs": sum(1 for _, _, r in chosen if pick(r)), "ctx_seq_tokens": ctx,
                "bytes": moved, "kernel_s": ns / 1e9, "achieved_gb_s": moved / ns,
                "roofline_pct": 100.0 * moved / (ns / 1e9) / peak}

    whole = share(lambda r: True)
    if whole is None:
        return None
    run["facts"]["paged_attn"] = {**whole, "bursts": share(lambda r: r["kind"] in BURSTS),
                                  "mixed": share(lambda r: r["kind"] not in BURSTS)}
    return whole["roofline_pct"]
