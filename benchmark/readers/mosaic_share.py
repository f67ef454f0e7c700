"""Time in Mosaic (compiled Pallas) custom calls over device busy time.
The kernels' names are data: the ``kernels`` pattern of the metric's own
file (the trace names each custom call after its kernel)."""

from benchmark.harness import trace


def read(run, spec):
    if run.get("trace") is None:
        return None
    busy = trace.busy_seconds(run["trace"])
    if busy <= 0:
        return None
    return 100.0 * trace.matching_seconds(run["trace"], spec["kernels"]) / busy
