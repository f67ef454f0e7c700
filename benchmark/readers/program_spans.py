"""Readers of the program's own step records and request stamps
(``deepspeed_tpu/utils/tracing.py``, joined to the device trace by
``benchmark/harness/program_spans.py``). Every one returns ``None`` —
and the harness leaves the metric out — without a traced run, with a
program that keeps no records, or when the two clocks could not be
joined; none guesses.

The first reader called on a run does the work once and leaves a summary
under ``facts.program_spans`` of the result line: the alignment, the
burst lengths, the owners of the idle gaps, the mean spans of a request,
the phases of a training step.
"""

from statistics import median

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.stats import percentile

BURSTS = ("burst", "burst_async")


def _ms(ns):
    return ns / 1e6


def _serving(run):
    """→ the analysis of a traced serving run, or None. Cached on the run."""
    if "_program_spans" in run:
        return run["_program_spans"]
    out = run["_program_spans"] = None
    recorded = ps.records()
    if recorded is None or run.get("trace") is None or not run.get("trace_window_s"):
        return out
    trace, steps = run["trace"], recorded["steps"]
    found = ps.align(trace, steps)
    if found is None:
        run.setdefault("facts", {})["program_spans"] = {"aligned": False}
        return out
    offset = found["offset_ns"]
    inside = ps.in_window(trace, steps, offset)
    bursts = [r for r in inside if r["kind"] in BURSTS and ps.device_interval_ns(r)]
    mixed = [r for r in inside if r["kind"] == "put" and r["n_prompt_tokens"] > 0
             and ps.device_interval_ns(r)]
    out = {"offset_ns": offset, "end_ns": ps.extent_ns(trace)[1] - offset,
           "bursts": bursts, "mixed": mixed, "requests": recorded["requests"], "idle": None}
    facts = {"aligned": True, **found,
             "records_in_window": _count(r["kind"] for r in inside),
             "burst_k": _count(r["k"] for r in bursts)}
    owners = ps.gap_owners(trace, steps, offset)
    if owners is not None:
        # the traced window's two ends (before the first op, after the last) lie in
        # no gap: they are counted with `outside`, so that the four add up to
        # device_idle's (window - busy)
        edges = run["trace_window_s"] - tr.busy_seconds(trace) - sum(owners.values())
        owners["outside"] += edges
        out["idle"] = {name: 100.0 * s / run["trace_window_s"] for name, s in owners.items()}
        facts["idle_gap_s"] = {**owners, "of_which_window_ends": edges}
        facts["idle_gap_phase_s"] = ps.gap_phases(trace, steps, offset)
    run.setdefault("facts", {})["program_spans"] = facts
    run["_program_spans"] = out
    return out


def _count(values):
    counts = {}
    for v in values:
        counts[str(v)] = counts.get(str(v), 0) + 1
    return counts


def _requests(run, spec):
    """The request records submitted in the ``lookback_s`` before the
    trace ended (the metric's own file says how far back)."""
    found = _serving(run)
    if found is None:
        return None
    lo = found["end_ns"] - int(spec["lookback_s"] * 1e9)
    chosen = [q for q in found["requests"] if lo <= q["submitted_ns"] <= found["end_ns"]]
    facts = run["facts"]["program_spans"]
    if "request_mean_ms" not in facts:
        def mean(a, b):
            values = [_ms(q[b] - q[a]) for q in chosen if q[a] is not None and q[b] is not None]
            return sum(values) / len(values) if values else None
        facts["requests"] = len(chosen)
        facts["request_mean_ms"] = {
            "pump_wait": mean("submitted_ns", "admitted_ns"),
            "sched_wait": mean("admitted_ns", "first_scheduled_ns"),
            "prefill_span": mean("first_scheduled_ns", "first_token_ns"),
            "ttft_from_submit": mean("submitted_ns", "first_token_ns")}
        steps = [q["prefill_steps"] for q in chosen if q["first_token_ns"] is not None]
        facts["prefill_steps_mean"] = sum(steps) / len(steps) if steps else None
    return chosen


# ------------------------------------------------------------------- serving
def decode_step_ms_p50(run, spec):
    found = _serving(run)
    if found is None or not found["bursts"]:
        return None
    return median(_ms(ps.device_interval_ns(r)) / r["k"] for r in found["bursts"])


def mixed_step_ms_p50(run, spec):
    found = _serving(run)
    if found is None or not found["mixed"]:
        return None
    return median(_ms(ps.device_interval_ns(r)) for r in found["mixed"])


def burst_k_mean(run, spec):
    found = _serving(run)
    if found is None or not found["bursts"]:
        return None
    return sum(r["k"] for r in found["bursts"]) / len(found["bursts"])


def pump_wait_p90_ms(run, spec):
    chosen = _requests(run, spec)
    if chosen is None:
        return None
    return percentile([_ms(q["admitted_ns"] - q["submitted_ns"]) for q in chosen
                       if q["admitted_ns"] is not None], 90)


def prefill_span_p90_ms(run, spec):
    chosen = _requests(run, spec)
    if chosen is None:
        return None
    return percentile([_ms(q["first_token_ns"] - q["first_scheduled_ns"]) for q in chosen
                       if q["first_token_ns"] is not None
                       and q["first_scheduled_ns"] is not None], 90)


def idle_share(run, spec):
    """Share of the traced window in which the device idled and the host
    was in the layer the metric's own file names under ``owner``."""
    found = _serving(run)
    if found is None or found["idle"] is None:
        return None
    return found["idle"][spec["owner"]]


# ------------------------------------------------------------------ training
def step_host_ms_p50(run, spec):
    """Per ``train`` record that ended in the traced window (the
    ``trace_window_s`` before the last record: training has no ``bench.*``
    spans and needs no alignment): its length minus ``ds.train.sync``, the
    wait for the step."""
    recorded = ps.records()
    if recorded is None or run.get("trace") is None or not run.get("trace_window_s"):
        return None
    trains = [r for r in recorded["steps"] if r["kind"] == "train"]
    if not trains:
        return None
    lo = trains[-1]["end_ns"] - int(run["trace_window_s"] * 1e9)
    chosen = [r for r in trains if r["end_ns"] >= lo]

    def total(r, name):
        return sum(b - a for a, b in ps.phase_intervals(r, name))

    host = [_ms(r["end_ns"] - r["start_ns"] - total(r, "ds.train.sync")) for r in chosen]
    run.setdefault("facts", {})["program_spans"] = {
        "train_records": len(chosen),
        "phase_ms_p50": {name: median(_ms(total(r, "ds.train." + name)) for r in chosen)
                         for name in ("prepare", "timer_sync", "dispatch", "sync", "post")},
        "record_ms_p50": median(_ms(r["end_ns"] - r["start_ns"]) for r in chosen)}
    return median(host)
