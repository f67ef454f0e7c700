"""The Granite 4.0-H sessions cell's own readings (``granite4-h-small-ep4-10l``):
what the prefix cache's snapshots saved, what their copies cost, and the
re-tiled Mamba-2 state step and the held experts' grouped matmul against the
chip's peaks.

The program counts on the device, in every step of the model kind
(``model_runner.GraniteHybridKind.step_counts``): ``n_state_slots`` (the
sequences with a row in the step times the mamba layers: each one slot of one
layer read and written by ``ops/pallas/ssm_state.ssm_state_step``),
``n_fresh_slots`` (of those, the ones whose sequence starts in the step: a
fresh row's tiles are **not fetched**, only written), ``n_ssm_rows``,
``n_picks_held`` and ``n_groups_live``; and on the host, of the step's
snapshots, ``n_snapshots_taken`` / ``n_snapshots_restored``. They ride in the
step record's ``counts``. The runner states the shapes under
``facts.granite_shapes`` / ``facts.expert_share`` and the window's sums under
``facts.sessions`` / ``facts.prefix_cache``.

**The kernels' operations and bytes** are here and nowhere else:

- :func:`ssm_state_bytes`: a live slot of a layer is ``H x P x N`` float32 -
  fetched once unless fresh, written once - so ``(2 x n_state_slots -
  n_fresh_slots) x H x P x N x 4`` bytes (the rows' ``b``, ``c``, ``left`` and
  decays are KBs beside 4.19 MB). Nothing that implements the step moves less,
  so ``ssm_state_roofline`` - those bytes over the own time of the device ops
  named ``ssm_state_step`` over the peak HBM rate - cannot pass 100.
- :func:`expert_flops` / :func:`expert_bytes`: a held pick is one row through
  three matrices ``D x F`` (``2 x D x F x 3`` operations; its input, two hidden
  activations and its output in and out in bf16), and a held expert with at
  least one row has its three matrices read once (``n_groups_live x 3 x D x F x
  2`` bytes). ``expert_matmul_roofline`` is the larger of operations over the
  peak bf16 rate and bytes over the peak HBM rate, over the own time of the ops
  named after ``ragged_dot``.

Bytes and time are taken over the same programs: the engine's step records
that lie whole inside the traced window, and the kernels' events inside their
device intervals. Every reader returns ``None`` (the metric is left out)
without what it reads: a traced run, a program whose records carry such
counts, a runner that states the shapes.
"""

import bisect
import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.program_spans import _serving

STATE_KERNEL = re.compile(r"^ssm_state_step")
EXPERT_KERNEL = re.compile(r"ragged[-_]dot")
COPY_MODULE = re.compile(r"snapshot_copy_slots")
COUNT, FRESH = "n_state_slots", "n_fresh_slots"


def ssm_state_bytes(state_slots, fresh_slots, heads, head_dim, state_size, itemsize=4):
    """Least bytes through HBM for the state step of steps that visited
    ``state_slots`` (sequence, layer) slots, ``fresh_slots`` of them fresh."""
    return (2 * state_slots - fresh_slots) * heads * head_dim * state_size * itemsize


def expert_flops(rows, hidden, width):
    return rows * 2 * hidden * width * 3


def expert_bytes(rows, groups_live, hidden, width, itemsize=2):
    return (groups_live * 3 * hidden * width + rows * (2 * hidden + 2 * width)) * itemsize


def _shapes(run):
    return run.get("facts", {}).get("granite_shapes")


def _whole_records(run):
    """→ [(lo, hi, record)]: the counted engine records whole inside the
    trace, with their device intervals on the trace's clock; or None."""
    found = _serving(run)
    if found is None or not _shapes(run):
        return None
    if "_granite_records" in run:
        return run["_granite_records"]
    extent = ps.extent_ns(run["trace"])
    chosen = []
    for r in ps.in_window(run["trace"], ps.records()["steps"], found["offset_ns"]):
        if r["kind"] not in ps.ENGINE_KINDS or not r.get("counts") or COUNT not in r["counts"]:
            continue
        enter = [t for t, _ in ps.phase_intervals(r, "ds.engine.dispatch")]
        exit_ = [t for _, t in ps.phase_intervals(r, "ds.engine.fetch")]
        if not enter or not exit_:
            continue
        lo, hi = enter[0] + found["offset_ns"], exit_[-1] + found["offset_ns"]
        if lo >= extent[0] and hi <= extent[1]:
            chosen.append((lo, hi, r))
    run["_granite_records"] = chosen = sorted(chosen, key=lambda c: c[0])
    return chosen


def _kernel_ns(run, chosen, kernel):
    """→ the own time of the ops ``kernel`` matches inside each record's
    device interval, averaged over devices, or None."""
    starts = [lo for lo, _, _ in chosen]
    by_device = []
    for events in tr.ops_of(run["trace"]).values():
        ns = [0] * len(chosen)
        for name, start, dur in events:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < chosen[i][1] and kernel.search(name):
                ns[i] += dur
        by_device.append(ns)
    if not by_device:
        return None
    return [sum(col) / len(by_device) for col in zip(*by_device)]


def _peaks(run):
    peaks = peaks_of(run["device"]["kind"])
    return peaks["hbm_gbytes_per_s"] * 1e9, peaks["bf16_tflops"] * 1e12


def state_slots_per_step(run, spec):
    """Sequences whose state a model step reads and writes, in the mean over
    the window's steps."""
    chosen = _whole_records(run)
    steps = sum(r["k"] for _, _, r in chosen or ())
    if not steps:
        return None
    slots = sum(r["counts"][COUNT] for _, _, r in chosen)
    run["facts"]["state_slots"] = {
        "records": len(chosen), "model_steps": steps, COUNT: slots,
        FRESH: sum(r["counts"].get(FRESH, 0) for _, _, r in chosen),
        "n_ssm_rows": sum(r["counts"].get("n_ssm_rows", 0) for _, _, r in chosen),
        "n_snapshots_taken": sum(r["counts"].get("n_snapshots_taken", 0) for _, _, r in chosen),
        "n_snapshots_restored": sum(r["counts"].get("n_snapshots_restored", 0)
                                    for _, _, r in chosen)}
    return slots / (_shapes(run)["mamba_layers"] * steps)


def ssm_state_roofline(run, spec):
    chosen = _whole_records(run)
    if not chosen:
        return None
    kernel_ns = _kernel_ns(run, chosen, STATE_KERNEL)
    if not kernel_ns or sum(kernel_ns) <= 0:
        return None
    s = _shapes(run)
    slots = sum(r["counts"][COUNT] for _, _, r in chosen)
    fresh = sum(r["counts"].get(FRESH, 0) for _, _, r in chosen)
    moved = ssm_state_bytes(slots, fresh, s["heads"], s["head_dim"], s["state_size"])
    ns = sum(kernel_ns)
    run["facts"]["ssm_state_roofline"] = {
        "programs": len(chosen), COUNT: slots, FRESH: fresh, "bytes": moved,
        "kernel_s": ns / 1e9, "achieved_gb_s": moved / ns}
    return 100.0 * moved / (ns / 1e9) / _peaks(run)[0]


def expert_matmul_roofline(run, spec):
    chosen = _whole_records(run)
    share = run.get("facts", {}).get("expert_share")
    if not chosen or not share or "hidden" not in share:
        return None
    kernel_ns = _kernel_ns(run, chosen, EXPERT_KERNEL)
    if not kernel_ns or sum(kernel_ns) <= 0:
        return None
    rows = sum(r["counts"]["n_picks_held"] for _, _, r in chosen)
    live = sum(r["counts"]["n_groups_live"] for _, _, r in chosen)
    hidden, width = share["hidden"], share["expert_width"]
    flops, moved = expert_flops(rows, hidden, width), expert_bytes(rows, live, hidden, width)
    hbm, mxu = _peaks(run)
    least, ns = max(flops / mxu, moved / hbm), sum(kernel_ns)
    run["facts"]["expert_matmul"] = {
        "programs": len(chosen), "rows": rows, "groups_live": live, "flops": flops,
        "bytes": moved, "kernel_s": ns / 1e9, "bound": "mxu" if flops / mxu > moved / hbm
        else "hbm", "least_s": least}
    return 100.0 * least / (ns / 1e9)


def snapshot_copy_share(run, spec):
    """Device time of the slot-to-slot copies (snapshots taken and restored:
    the programs named ``snapshot_copy_slots``) over device busy time, %."""
    if run.get("trace") is None or not _shapes(run):
        return None
    busy = tr.busy_seconds(run["trace"])
    modules = tr.modules_of(run["trace"])
    if busy <= 0 or not modules:
        return None
    per_device = [sum(dur for name, _, dur in events if COPY_MODULE.search(name)) / 1e9
                  for events in modules.values()]
    copies = sum(per_device) / len(per_device)
    run["facts"]["snapshot_copies"] = {
        "device_s": copies, "programs": sum(
            1 for events in modules.values() for name, _, _ in events
            if COPY_MODULE.search(name)) // len(modules)}
    return 100.0 * copies / busy


def prompt_cached_share(run, spec):
    """% of the prompt tokens of the turns that ended inside the window that
    the prefix cache served (their request records' ``prefix_cached_tokens``
    over their prompts' lengths)."""
    sessions = run.get("facts", {}).get("sessions")
    if not sessions or not sessions.get("prompt_tokens_by_record"):
        return None
    return 100.0 * sessions["prompt_cached_tokens"] / sessions["prompt_tokens_by_record"]


def resume_ttft_p50_ms(run, spec):
    """Median time from sending a turn past a session's first to its first
    token, on the client's clock, over the turns that ended inside the window."""
    sessions = run.get("facts", {}).get("sessions")
    return sessions.get("resume_ttft_p50_ms") if sessions else None
