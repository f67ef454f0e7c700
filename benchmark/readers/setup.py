"""What set-up cost, told by the program itself (PR 50).

Since PR 50 the program's recorder (``deepspeed_tpu/utils/tracing.py``)
keeps a ``setup`` record of an engine's constructor and a build table with
a row a program, each compile event counted by its own time, and a serving
gateway counts both in whole milliseconds among its counters. ``serve.py``
copies those counters to ``facts.gateway_counters`` of every result line,
traced or not, so the three metrics here need nothing else of the run:

* ``setup_program_s``: the constructor and the building of the engine's own
  programs - the part of ``setup_s`` a change to the program can move;
* ``setup_build_trace_s``: own trace time inside the engine's records - what
  a warm compile cache does not save;
* ``compile_outside_s``: compile time outside every record of the program -
  in these cells the reference check's, the benchmark's own cost.

Every reader returns ``None`` where the line has no such counter (a parent
of PR 50, the training cell, which has no gateway), so the harness leaves
the metric out. They have files under ``layer_metrics/`` and no entry in
``BENCHMARK.json`` yet (``per_layer`` holds the 128 it may); until a
``benchmark`` PR enters them::

    python3 benchmark/readers/setup.py < result-lines

prints the three of every result line it is given.
"""

import json
import sys

METRICS = ("setup_program_s", "setup_build_trace_s", "compile_outside_s")


def _seconds(run, *names):
    counters = run.get("facts", {}).get("gateway_counters") or {}
    if any(name not in counters for name in names):
        return None
    return sum(counters[name] for name in names) / 1e3


def setup_program_s(run, spec=None):
    return _seconds(run, "setup_init_ms", "setup_build_ms")


def setup_build_trace_s(run, spec=None):
    return _seconds(run, "setup_build_trace_ms")


def compile_outside_s(run, spec=None):
    return _seconds(run, "setup_outside_compile_ms")


def main():
    for text in sys.stdin:
        text = text.strip()
        if not text.startswith("{"):
            continue
        line = json.loads(text)
        print(json.dumps({name: globals()[name](line) for name in METRICS}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
