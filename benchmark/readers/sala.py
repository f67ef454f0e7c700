"""The sparse layers' own counts and their kernel against the chip's
memory bandwidth (``minicpm-sala-16l``).

The program counts on the device, in every step of the model kind whose
sparse layers select what they read (``model_runner.SalaKind.step_counts``),
over the step's tokens that are not padding, its sparse layers and their
key-value heads: the blocks a (token, key-value head) reads
(``n_blocks_selected``) and the blocks its context holds
(``n_blocks_context``); and ``n_linear_rows``, the token-layers through
the packed linear step. They ride out with the step's result into its
step record (``counts``).

``sparse_read_share`` = selected / context, in %: what the sparse layers
read of what dense attention over the same contexts would.

``sparse_attn_roofline`` = (the bytes ``paged_decode_attention`` has to
move under the selection) / (its own time in the device trace) / (peak HBM
bytes/s), in %. The bound is **HBM**: a selected block of one key-value
head is ``block x head_dim`` keys and as many values, on which the head's
group of ``heads / kv_heads`` = 16 query rows does ``2 x 16 x 2 x head_dim``
operations a row — 16 operations a byte in bf16, under the chip's ~240 a
byte at the bf16 peak. :func:`kernel_flops` is kept beside
:func:`kernel_bytes` so that a reader of a trace can check the bound.

The bytes are what the selection names, (token, key-value head) by
(token, key-value head): every selected block once for the row that
selected it, plus each query row in and each output row out. Rows of one
prompt chunk select overlapping blocks and the kernel fetches them again
for each — those are bytes here too, since the selection is per row and a
kernel that shared them would be another algorithm; what is time and not
bytes is the unused rows of a context's last block and the rows of
padding tokens. A row whose only block is the one the row before it had
fetches nothing (a sequence's first 64 positions: under 0.4 % of a 16k
prompt's rows), which is why a reading can pass 100 % by that much and no
more. Bytes and time are taken over the same programs: the step records
that lie whole inside the traced window, and the kernel's events inside
their device intervals.

Every reader returns ``None`` (the metric is left out) without a traced
run, with a program whose records carry no such counts (the parent's, or
another model kind's), or with a runner that states no ``sala_shapes``.
"""

import bisect
import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.program_spans import _serving

KERNEL = re.compile(r"^paged_decode_attention")
COUNTS = ("n_blocks_selected", "n_blocks_context")


def kernel_bytes(blocks_selected, query_rows, sparse_layers, heads, head_dim, block_size, itemsize):
    """Least bytes through HBM for ``blocks_selected`` (token, key-value
    head, sparse layer) block reads and ``query_rows`` tokens: a block of
    one head is ``block_size x head_dim`` keys and as many values; a token
    brings ``heads x head_dim`` query values in and as many out, a sparse
    layer."""
    blocks = blocks_selected * block_size * head_dim * 2
    rows = query_rows * sparse_layers * heads * head_dim * 2
    return itemsize * (blocks + rows)


def kernel_flops(blocks_selected, heads, kv_heads, head_dim, block_size):
    """Multiply-adds counted as two: scores and values, for the key-value
    head's group of query heads, over every row of every selected block."""
    return blocks_selected * block_size * (heads // kv_heads) * 2 * 2 * head_dim


def _counted(records):
    return [r for r in records if r.get("counts") and all(c in r["counts"] for c in COUNTS)]


def sparse_read_share(run, spec):
    found = _serving(run)
    if found is None:
        return None
    records = _counted(found["bursts"] + found["mixed"])
    context = sum(r["counts"]["n_blocks_context"] for r in records)
    if not context:
        return None
    selected = sum(r["counts"]["n_blocks_selected"] for r in records)
    by_kind = {}
    for name, group in (("burst", found["bursts"]), ("mixed", found["mixed"])):
        group = _counted(group)
        ctx = sum(r["counts"]["n_blocks_context"] for r in group)
        if ctx:
            by_kind[name] = 100.0 * sum(r["counts"]["n_blocks_selected"] for r in group) / ctx
    run["facts"]["sparse_read"] = {
        "records": len(records), "blocks_selected": selected, "blocks_context": context,
        "share_by_kind": by_kind,
        "linear_rows": sum(r["counts"].get("n_linear_rows", 0) for r in records)}
    return 100.0 * selected / context


def _whole_records(run):
    """→ the counted records whole inside the trace, with their device
    intervals on the trace's clock, or None."""
    found = _serving(run)
    if found is None or not run.get("facts", {}).get("sala_shapes"):
        return None
    extent = ps.extent_ns(run["trace"])
    chosen = []
    for r in _counted(ps.in_window(run["trace"], ps.records()["steps"], found["offset_ns"])):
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


def sparse_attn_roofline(run, spec):
    chosen = _whole_records(run)
    if not chosen:
        return None
    s = run["facts"]["sala_shapes"]
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
    selected = sum(r["counts"]["n_blocks_selected"] for _, _, r in chosen)
    rows = sum(r["n_tokens"] for _, _, r in chosen)
    moved = kernel_bytes(selected, rows, s["sparse_layers"], s["heads"], s["head_dim"],
                         s["block_size"], s["itemsize"])
    peak = peaks_of(run["device"]["kind"])["hbm_gbytes_per_s"] * 1e9
    run["facts"]["sparse_attn"] = {
        "programs": len(chosen), "blocks_selected": selected, "query_rows": rows, "bytes": moved,
        "kernel_s": seconds, "achieved_gb_s": moved / seconds / 1e9,
        "flops": kernel_flops(selected, s["heads"], s["kv_heads"], s["head_dim"], s["block_size"])}
    return 100.0 * moved / seconds / peak
