"""The paged latent (MLA) decode kernel against the chip's memory
bandwidth, and the context it attends.

``mla_decode_roofline`` = (the bytes the kernel has to move at the
least) / (its own time in the device trace) / (peak HBM bytes/s), in %.
The bound is **HBM**: per token of context the kernel reads one row of
``rank + lanes`` values and does ``2 x heads x (rank + lanes) + 2 x heads
x rank`` operations on it — 29 operations a byte at Moonlight's shapes,
under the chip's ~240 operations a byte at the bf16 peak, and that only
if the MXU ran sixteen rows a pass at full rate. :func:`kernel_flops` is
kept beside :func:`kernel_bytes` so that a reader of a trace can check
which bound a shape is under.

The bytes are the algorithmic minimum, so that no reading can pass 100 %:
each latent row of each sequence's context **once** per layer per model
step (``n_ctx_tokens`` of the program's step records: the context lengths
of the rows a program ran, summed over its ``k`` steps), plus each query
row in and each output row out. What the kernel reads again — a prompt
chunk's context once per token of the chunk, the unused rows of a
context's last block, rows of padding tokens — is time and not bytes.
Bytes and time are taken over the same programs: the step records that
lie whole inside the traced window, and the kernel's events inside their
device intervals.

Both readers return ``None`` (the metric is left out) without a traced
run, with a program whose records have no ``n_ctx_tokens``, or with a
runner that states no latent shapes.
"""

import bisect
import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.program_spans import _serving

KERNEL = re.compile(r"^paged_mla_decode_attention")


def kernel_bytes(ctx_tokens, query_rows, layers, heads, rank, lanes, itemsize):
    """Least bytes through HBM for ``ctx_tokens`` attended context
    positions and ``query_rows`` query tokens, over ``layers`` layers: a
    pooled row is ``rank + lanes`` values, a query ``heads`` such rows, an
    output ``heads x rank`` values."""
    rows = ctx_tokens * (rank + lanes)
    queries = query_rows * heads * (rank + lanes)
    outputs = query_rows * heads * rank
    return layers * itemsize * (rows + queries + outputs)


def kernel_flops(ctx_tokens, layers, heads, rank, lanes):
    """Multiply-adds counted as two: scores over the whole row, values
    over its first ``rank``, for every head."""
    return layers * ctx_tokens * heads * 2 * ((rank + lanes) + rank)


def _whole_records(run):
    """→ (records whole inside the trace with their device intervals on
    the trace's clock, shapes), or None."""
    found = _serving(run)
    shapes = run.get("facts", {}).get("latent_shapes")
    if found is None or not shapes:
        return None
    extent = ps.extent_ns(run["trace"])
    chosen = []
    for r in ps.in_window(run["trace"], ps.records()["steps"], found["offset_ns"]):
        if r["kind"] not in ps.ENGINE_KINDS:
            continue
        if r.get("n_ctx_tokens") is None:
            return None
        enter = [t for t, _ in ps.phase_intervals(r, "ds.engine.dispatch")]
        exit_ = [t for _, t in ps.phase_intervals(r, "ds.engine.fetch")]
        if not enter or not exit_:
            continue
        lo, hi = enter[0] + found["offset_ns"], exit_[-1] + found["offset_ns"]
        if lo >= extent[0] and hi <= extent[1]:
            chosen.append((lo, hi, r))
    return sorted(chosen, key=lambda c: c[0]), shapes


def decode_roofline(run, spec):
    got = _whole_records(run)
    if not got or not got[0]:
        return None
    chosen, s = got
    starts = [lo for lo, _, _ in chosen]
    kernel_ns = []
    for events in tr.ops_of(run["trace"]).values():
        total = 0
        for name, start, dur in events:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < chosen[i][1] and KERNEL.match(name):
                total += dur
        kernel_ns.append(total)
    seconds = sum(kernel_ns) / len(kernel_ns) / 1e9 if kernel_ns else 0.0
    if seconds <= 0:
        return None
    ctx = sum(r["n_ctx_tokens"] for _, _, r in chosen)
    rows = sum(r["n_tokens"] for _, _, r in chosen)
    moved = kernel_bytes(ctx, rows, s["layers"], s["heads"], s["rank"], s["lanes"],
                         s["itemsize"])
    peak = peaks_of(run["device"]["kind"])["hbm_gbytes_per_s"] * 1e9
    run["facts"]["mla_decode"] = {
        "programs": len(chosen), "ctx_tokens": ctx, "query_rows": rows, "bytes": moved,
        "kernel_s": seconds, "achieved_gb_s": moved / seconds / 1e9,
        "flops": kernel_flops(ctx, s["layers"], s["heads"], s["rank"], s["lanes"])}
    return 100.0 * moved / seconds / peak


def ctx_tokens_per_step(run, spec):
    """Mean over the burst records of the traced window of
    ``n_ctx_tokens / k``: the context positions one decode step attends,
    summed over its rows."""
    found = _serving(run)
    if found is None or not found["bursts"]:
        return None
    if any(r.get("n_ctx_tokens") is None for r in found["bursts"]):
        return None
    return sum(r["n_ctx_tokens"] / r["k"] for r in found["bursts"]) / len(found["bursts"])
