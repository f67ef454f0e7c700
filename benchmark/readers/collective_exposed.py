"""Share of the traced window spent in collectives that nothing hides."""

from benchmark.harness import trace


def read(run, spec):
    if run.get("trace") is None or not run.get("trace_window_s"):
        return None
    if not trace.ops_of(run["trace"]):
        return None
    return 100.0 * trace.exposed_collective_seconds(run["trace"]) / run["trace_window_s"]
