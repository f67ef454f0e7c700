"""The Mamba-2 layers' own count (``nemotron3-super-ep4-11l``).

The program counts on the device, in every step of the model kind whose
``M`` layers keep a state a sequence in a slot
(``model_runner.NemotronHKind.step_counts``): ``n_state_slots``, the
sequences with a row in the step times the ``M`` layers - each the read
and the write of one slot of one layer, 4.19 MB of float32 state and the
convolution's tail, whatever the step's rows - and ``n_ssm_rows``, the
token-layers through the packed recurrence. They ride out with the step's
result into its step record (``counts``); the runner states the layers
under ``facts.nemotron_shapes``.

The reader returns ``None`` (the metric is left out) without a traced
run, with a program whose records carry no such count (the parent's, or
another model kind's), or with a runner that states no shapes.
"""

from benchmark.readers.program_spans import _serving


def state_slots_per_step(run, spec):
    """Sequences whose state a model step reads and writes, in the mean
    over the window's steps."""
    found = _serving(run)
    shapes = run.get("facts", {}).get("nemotron_shapes")
    if found is None or not shapes:
        return None
    records = [r for r in found["bursts"] + found["mixed"]
               if r.get("counts") and "n_state_slots" in r["counts"]]
    steps = sum(r["k"] for r in records)
    if not steps:
        return None
    slots = sum(r["counts"]["n_state_slots"] for r in records)
    run["facts"]["state_slots"] = {
        "records": len(records), "model_steps": steps, "n_state_slots": slots,
        "n_ssm_rows": sum(r["counts"].get("n_ssm_rows", 0) for r in records)}
    return slots / (shapes["mamba_layers"] * steps)
