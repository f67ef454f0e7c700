"""Share of the traced window in which no operation ran on the device."""

from benchmark.harness import trace


def read(run, spec):
    if run.get("trace") is None or not run.get("trace_window_s"):
        return None
    busy = trace.busy_seconds(run["trace"])
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / run["trace_window_s"])
