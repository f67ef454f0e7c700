"""Median device duration of the step and burst programs in the trace."""

from statistics import median

from benchmark.harness import trace


def p50_ms(run, spec):
    if run.get("trace") is None:
        return None
    durations = trace.module_durations_ms(run["trace"])
    return median(durations) if durations else None
