"""The Laguna cell's own counts, and its two kinds of attention layer against
the chip's memory bandwidth (``laguna-xs2-ep8-20l``).

The program counts on the device, in every step of the model kind whose
window layers keep their keys and values in a pool of their own
(``model_runner.LagunaKind.step_counts``): ``n_ctx_seq_tokens``, over the
step's sequences the context positions each attends to in a **full** layer,
counted once a sequence however many rows it has in the step, and
``n_win_seq_tokens``, the same for a **window** layer - the positions from
its first row's lower bound (``pos - 511``, or 0) to its last row. They ride
out with the step's result into its step record (``counts``); the runner
states the layers of each kind and the bytes of a token's keys and values a
layer under ``facts.laguna_shapes``.

``window_attn_roofline`` / ``paged_attn_roofline`` = (the least bytes the
layers of that kind have to fetch) / (the own time of the device ops named
after that kind's call: ``paged_window_attention`` / ``paged_decode_attention``)
/ (peak HBM bytes/s), in %. The least bytes are :func:`attention_bytes`:
every position a sequence of the step attends to, **once a sequence and a
layer** - ``count x layers x (K and V of 8 heads x 128 x 2 B = 4 KB)`` -
whatever implements the layer, so the share cannot pass 100: no
implementation reads less (one that read a sequence's whole context in a
window layer would read **under** its share, the bytes being the window's).
Queries in and outputs out are left out: a row's are 16 KB against a
window's 2 MB. Bytes and time are taken over the same programs: the step
records that lie whole inside the traced window, and the kernel's events
inside their device intervals.

``window_blocks_per_seq``: the window pool's high water over the run over
the sequences the engine tracks at once (``facts.window_pool``, the pool's
own counters): what a live sequence held at the pool's fullest instant, in
the mean - 9 blocks of 64 is a decoding sequence's bound at any length, 17
inside a 512-row chunk.

Every reader returns ``None`` (the metric is left out) without a traced
run, with a program whose records carry no such counts (the parent's, or
another model kind's), or with a runner that states no ``laguna_shapes``.
"""

import bisect
import re

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.device import peaks_of
from benchmark.readers.jamba import _counted
from benchmark.readers.program_spans import BURSTS, _serving

KINDS = {"window": ("n_win_seq_tokens", "window_layers", re.compile(r"^paged_window_attention")),
         "full": ("n_ctx_seq_tokens", "full_layers", re.compile(r"^paged_decode_attention"))}


def attention_bytes(seq_tokens, layers, kv_row_bytes):
    """Least bytes through HBM for ``layers`` attention layers of steps whose
    sequences attend to ``seq_tokens`` positions (once a sequence): a
    position's keys and values, ``kv_row_bytes`` a layer."""
    return seq_tokens * layers * kv_row_bytes


def _whole_records(run, count):
    """→ the counted records whole inside the trace, with their device
    intervals on the trace's clock, or None."""
    found = _serving(run)
    if found is None or not run.get("facts", {}).get("laguna_shapes"):
        return None
    extent = ps.extent_ns(run["trace"])
    chosen = []
    for r in _counted(ps.in_window(run["trace"], ps.records()["steps"], found["offset_ns"]),
                      count):
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


def attention_roofline(run, spec):
    """The metric's own file names the kind of layer under ``attention``
    (``window`` | ``full``)."""
    count, layers_of, kernel = KINDS[spec["attention"]]
    chosen = _whole_records(run, count)
    if not chosen:
        return None
    s = run["facts"]["laguna_shapes"]
    starts = [lo for lo, _, _ in chosen]
    by_device = []
    for events in tr.ops_of(run["trace"]).values():
        ns = [0] * len(chosen)
        for name, start, dur in events:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < chosen[i][1] and kernel.match(name):
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
        tokens = sum(r["counts"][count] for _, r in picked)
        moved = attention_bytes(tokens, s[layers_of], s["kv_row_bytes"])
        return {"programs": len(picked), "model_steps": sum(r["k"] for _, r in picked),
                count: tokens, "bytes": moved, "kernel_s": ns / 1e9, "achieved_gb_s": moved / ns,
                "roofline_pct": 100.0 * moved / (ns / 1e9) / peak}

    def decode_only(r):
        return r["kind"] in BURSTS or not r.get("n_prompt_tokens")

    whole = share(lambda r: True)
    if whole is None:
        return None
    run["facts"].setdefault("attention_roofline", {})[spec["attention"]] = {
        **whole, "by_kind": {"decode_only": share(decode_only),
                             "with_prompt_rows": share(lambda r: not decode_only(r))}}
    return whole["roofline_pct"]


def window_blocks_per_seq(run, spec):
    facts = run.get("facts", {})
    pool, shapes = facts.get("window_pool"), facts.get("laguna_shapes")
    if not pool or not shapes or not pool.get("high_water"):
        return None
    return pool["high_water"] / shapes["sequences"]
