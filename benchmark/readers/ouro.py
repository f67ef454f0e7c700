"""The Ouro cell's own readings: a decode step against the chip's memory
bandwidth, the paged kernel at a query group of one against it, and the
passes a step ran (``ouro-2.6b``).

The program counts on the device, in every step of the model kind whose
stack runs several times (``model_runner.OuroKind.step_counts``):
``n_stack_passes`` (the passes of the stack a step ran, summed over a
burst's steps), ``n_loop_token_layers`` and ``n_exit_early_rows``. They ride
out with the step's result into its step record (``counts``), which also
holds ``n_ctx_tokens``: the context positions the record's sequences
attended to, once a sequence and a step. The runner states what a pass
streams and what a pooled token holds under ``facts.ouro_shapes``.

``decode_hbm_roofline`` = :func:`step_bytes` / (device busy time inside the
record) / (peak HBM bytes/s), in %, over the **decode-only** step records
(bursts, and puts without a prompt row) that lie whole inside the traced
window: the least a decode step has to read - the stack's matrices **once
a pass** (whatever the batch), the head once a step, and every attended
position's keys and values in all ``R L`` pool layers. No implementation
reads less, so the share cannot pass 100; what the program reads again
(a relayout of a weight, a block fetched a head at a time) is time and not
bytes. The embedding's rows, the activations and the logits are left out:
a dozen rows' are KBs against 20 GB.

``paged_attn_roofline`` = :func:`attention_bytes` / (the own time of the
device ops named ``paged_decode_attention`` inside the records) / (peak),
over **every** record whole inside the window: ``paged_attn_roofline.repochat``'s
recipe (``readers/laguna.py``) with all ``R L`` layers - at a query group of
one a key-value row is fetched for one query head.

Every reader returns ``None`` (the metric is left out) without a traced
run, with a program whose records carry no such counts (the parent's, or
another model kind's), or with a runner that states no ``ouro_shapes``.
"""

import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.program_spans import BURSTS, _serving

COUNT = "n_stack_passes"
KERNEL = re.compile(r"^paged_decode_attention")


def step_bytes(stack_passes, model_steps, ctx_tokens, shapes):
    """Least bytes through HBM for ``model_steps`` decode steps that ran the
    stack ``stack_passes`` times in all and attended to ``ctx_tokens``
    positions (once a sequence and a step): the layers' matrices a pass, the
    head a step, a position's keys and values in every pool layer."""
    return (stack_passes * shapes["stack_bytes"] + model_steps * shapes["head_bytes"]
            + ctx_tokens * shapes["state_bytes_per_token"])


def attention_bytes(ctx_tokens, shapes):
    """Least bytes the attention of ``R L`` layers has to fetch for steps
    whose sequences attend to ``ctx_tokens`` positions (once a sequence)."""
    return ctx_tokens * shapes["state_layers"] * shapes["kv_row_bytes"]


def _shapes(run):
    facts = run.get("facts", {})
    shapes = facts.get("ouro_shapes")
    if not shapes or not facts.get("state_bytes_per_token"):
        return None
    return dict(shapes, state_bytes_per_token=facts["state_bytes_per_token"])


def _whole_records(run):
    """→ [(lo, hi, record)]: the counted engine records whole inside the
    trace, with their device intervals on the trace's clock; or None."""
    found = _serving(run)
    if found is None or _shapes(run) is None:
        return None
    extent = ps.extent_ns(run["trace"])
    chosen = []
    for r in ps.in_window(run["trace"], ps.records()["steps"], found["offset_ns"]):
        if (r["kind"] not in ps.ENGINE_KINDS or not r.get("counts") or COUNT not in r["counts"]
                or r.get("n_ctx_tokens") is None):
            continue
        enter = [t for t, _ in ps.phase_intervals(r, "ds.engine.dispatch")]
        exit_ = [t for _, t in ps.phase_intervals(r, "ds.engine.fetch")]
        if not enter or not exit_:
            continue
        lo, hi = enter[0] + found["offset_ns"], exit_[-1] + found["offset_ns"]
        if lo >= extent[0] and hi <= extent[1]:
            chosen.append((lo, hi, r))
    return sorted(chosen, key=lambda c: c[0])


def _device_ns(run, chosen, pick=None):
    """→ ns a record, averaged over devices: the union of the op intervals
    that start inside the record, cut at its end (``pick``: only ops whose
    name it matches, their durations summed - a kernel's own time)."""
    per_device = []
    for events in tr.ops_of(run["trace"]).values():
        events = sorted(events, key=lambda e: e[1])
        ns, i = [], 0
        for lo, hi, _ in chosen:
            while i < len(events) and events[i][1] < lo:
                i += 1
            j, inside = i, []
            while j < len(events) and events[j][1] < hi:
                inside.append(events[j])
                j += 1
            if pick is None:
                # cut to the record: an event whose end the profiler put past its program's
                # (the trace's last, a loop's parent) must not count the programs after it
                ns.append(sum(b - a for a, b in tr.union([s, min(s + d, hi)]
                                                         for _, s, d in inside)))
            else:
                ns.append(sum(d for name, _, d in inside if pick.match(name)))
        per_device.append(ns)
    if not per_device:
        return None
    return [sum(col) / len(per_device) for col in zip(*per_device)]


def _decode_only(r):
    return r["kind"] in BURSTS or not r.get("n_prompt_tokens")


def decode_hbm_roofline(run, spec):
    chosen = _whole_records(run)
    chosen = [c for c in chosen or () if _decode_only(c[2])]
    if not chosen:
        return None
    busy = _device_ns(run, chosen)
    if not busy or sum(busy) <= 0:
        return None
    shapes = _shapes(run)
    passes = sum(r["counts"][COUNT] for _, _, r in chosen)
    steps = sum(r["k"] for _, _, r in chosen)
    ctx = sum(r["n_ctx_tokens"] for _, _, r in chosen)
    moved, ns = step_bytes(passes, steps, ctx, shapes), sum(busy)
    peak = peaks_of(run["device"]["kind"])["hbm_gbytes_per_s"] * 1e9
    run["facts"]["decode_hbm"] = {
        "programs": len(chosen), "model_steps": steps, "stack_passes": passes,
        "n_ctx_tokens": ctx, "bytes": moved, "weight_bytes": passes * shapes["stack_bytes"],
        "cache_bytes": ctx * shapes["state_bytes_per_token"], "device_s": ns / 1e9,
        "step_ms": ns / 1e6 / steps, "achieved_gb_s": moved / ns}
    return 100.0 * moved / (ns / 1e9) / peak


def paged_attn_roofline(run, spec):
    chosen = _whole_records(run)
    if not chosen:
        return None
    kernel = _device_ns(run, chosen, KERNEL)
    if not kernel or sum(kernel) <= 0:
        return None
    shapes = _shapes(run)
    peak = peaks_of(run["device"]["kind"])["hbm_gbytes_per_s"] * 1e9

    def share(pick):
        picked = [(t, r) for t, (_, _, r) in zip(kernel, chosen) if pick(r)]
        ns = sum(t for t, _ in picked)
        if ns <= 0:
            return None
        ctx = sum(r["n_ctx_tokens"] for _, r in picked)
        moved = attention_bytes(ctx, shapes)
        return {"programs": len(picked), "model_steps": sum(r["k"] for _, r in picked),
                "n_ctx_tokens": ctx, "bytes": moved, "kernel_s": ns / 1e9,
                "achieved_gb_s": moved / ns, "roofline_pct": 100.0 * moved / (ns / 1e9) / peak}

    whole = share(lambda r: True)
    run["facts"]["attention_roofline"] = {
        **whole, "query_group": shapes["query_group"],
        "by_kind": {"decode_only": share(_decode_only),
                    "with_prompt_rows": share(lambda r: not _decode_only(r))}}
    return whole["roofline_pct"]


def loop_passes_per_step(run, spec):
    """Passes of the stack a model step ran, in the mean over the counted
    records that started in the traced window."""
    found = _serving(run)
    if found is None:
        return None
    records = [r for r in found["bursts"] + found["mixed"]
               if r.get("counts") and COUNT in r["counts"]]
    steps = sum(r["k"] for r in records)
    if not steps:
        return None
    return sum(r["counts"][COUNT] for r in records) / steps
