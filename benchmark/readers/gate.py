"""What the admission gate did with the cell's clients, as the benchmark's
own client saw it through the gateway's public ``inflight()``: how many
requests waited at the gate (submitted, not admitted) at the middle of
the window. The ``serve`` runner states it under ``facts.queued_mid``;
``None`` (the metric is left out) where a run does not.
"""


def queued_mid(run, spec):
    return run.get("facts", {}).get("queued_mid")
