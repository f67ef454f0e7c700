"""Tests of the three set-up metrics PR 50 adds as files without an entry
(``setup_program_s``, ``setup_build_trace_s``, ``compile_outside_s``) and of
their reader, on a made-up result line with and without the gateway's
counters. Like ``test_benchmark.py`` they are the benchmark's, not tier-1's.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import program_spans, spec  # noqa: E402

NAMES = ("setup_program_s", "setup_build_trace_s", "compile_outside_s")
CHANGE = {"correct": True, "metrics": {}, "facts": {"gateway_counters": {
    "stalls": 0, "setup_init_ms": 4321, "setup_build_ms": 9876, "setup_build_trace_ms": 2500,
    "setup_outside_compile_ms": 16100, "programs_built": 7, "compile_cache_hits": 21,
    "compile_cache_misses": 0}}}
PARENT = {"correct": True, "metrics": {}, "facts": {"gateway_counters": {"stalls": 0}}}
TRAIN = {"correct": True, "metrics": {}, "facts": {}}
WANT = {"setup_program_s": 14.197, "setup_build_trace_s": 2.5, "compile_outside_s": 16.1}


@pytest.mark.parametrize("name", NAMES)
def test_a_file_without_an_entry_and_its_reader(name):
    bench = spec.Benchmark(ROOT)
    assert len(bench.doc["per_layer"]) == 128 and name not in bench.per_layer
    with open(bench.path("layer_metrics", f"{name}.json")) as f:
        metric = json.load(f)
    assert {"layer", "unit", "moves", "source", "reader"} <= set(metric) and "cells" not in metric
    assert metric["layer"] == bench.per_layer["compile_s"]["layer"]
    assert (metric["unit"], metric["moves"], metric["better"]) == ("s", "setup_s", "lower")
    assert metric["source"] in spec.SOURCES and spec.UNIT.match(metric["unit"])
    module, _, attr = metric["reader"].partition(":")
    assert (module, attr) == ("readers.setup", name)
    read = bench.load("readers", "setup", attr)
    assert read(CHANGE, metric) == pytest.approx(WANT[name])
    assert read(PARENT, metric) is None and read(TRAIN, metric) is None


def test_the_reader_as_a_script_prints_the_three_of_every_line():
    lines = "\n".join(["[serve] progress goes to stderr, but a log may be pasted",
                       json.dumps(PARENT), json.dumps(CHANGE)]) + "\n"
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "readers", "setup.py")],
                         input=lines, capture_output=True, text=True, check=True).stdout
    parent, change = [json.loads(line) for line in out.splitlines()]
    assert parent == dict.fromkeys(NAMES) and change == pytest.approx(WANT)


def test_the_other_readers_pass_the_setup_record_by():
    assert "setup" not in program_spans.ENGINE_KINDS and "setup" not in program_spans.SPAN_KIND.values()
