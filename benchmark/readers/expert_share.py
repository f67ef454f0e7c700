"""The expert share's own counts: what an expert layer that holds a part
of the router's experts did with the picks of the traced window.

The program counts on the device, in every step of a model kind whose
expert layer is a share (``model_runner.LongcatKind.step_counts``), over
the step's tokens that are not padding and its expert layers: the picks
whose expert is held here (``n_picks_held``: each becomes one row of a
grouped matmul), the zero-compute picks (``n_picks_zero``: ``w * x``, no
matmul) and the held experts with at least one row (``n_groups_live``: the
groups whose weights a grouped matmul reads). They ride out with the
step's result into its step record (``counts``). The picks that belong to
experts held elsewhere are the rest of ``moe_topk x tokens x layers``; the
runner states ``moe_topk``, the expert layers and the experts held under
``facts.expert_share``.

Every reader sums the step records that started inside the traced window
and returns ``None`` (the metric is left out) without a traced run, with
a program whose records carry no counts (the parent's, or another model
kind's), or with a runner that states no share.
"""

from benchmark.readers.program_spans import _serving


def _sums(run):
    """→ {"held", "zero", "live", "picks", "layer_steps", "experts"} over
    the window's records, or None."""
    if "_expert_share" in run:
        return run["_expert_share"]
    out = run["_expert_share"] = None
    found = _serving(run)
    share = run.get("facts", {}).get("expert_share")
    if found is None or not share:
        return out
    records = [r for r in found["bursts"] + found["mixed"] if r.get("counts")]
    if not records:
        return out
    layers, held = share["expert_layers"], share["experts_held"]
    out = {"held": sum(r["counts"]["n_picks_held"] for r in records),
           "zero": sum(r["counts"]["n_picks_zero"] for r in records),
           "live": sum(r["counts"]["n_groups_live"] for r in records),
           "picks": share["moe_topk"] * layers * sum(r["n_tokens"] for r in records),
           "layer_steps": layers * sum(r["k"] for r in records), "experts": held}
    run["facts"]["expert_share_counts"] = {"records": len(records), **out}
    run["_expert_share"] = out
    return out


def zero_pick_share(run, spec):
    """% of the picks that are zero-compute."""
    s = _sums(run)
    return None if not s or not s["picks"] else 100.0 * s["zero"] / s["picks"]


def held_rows_per_expert(run, spec):
    """Rows a held expert a layer a step: the held picks over (expert
    layers x model steps x experts held)."""
    s = _sums(run)
    return None if not s or not s["layer_steps"] else s["held"] / (s["layer_steps"] * s["experts"])


def held_groups_empty(run, spec):
    """% of the held experts with no row in a step."""
    s = _sums(run)
    if not s or not s["layer_steps"]:
        return None
    return 100.0 * (1.0 - s["live"] / (s["layer_steps"] * s["experts"]))
