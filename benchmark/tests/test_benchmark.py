"""Tests of the benchmark's own files. They run on the CPU in seconds to a
minute (``python -m pytest benchmark/tests -q``) and are not part of the
repo's tier-1 ``tests/`` tree: the harness is the yardstick, not the
program.
"""

import collections
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import spec, strata, trace  # noqa: E402
from benchmark.harness.stats import percentile  # noqa: E402

DATA = os.path.join(ROOT, "benchmark", "tests", "data")


@pytest.fixture(scope="module")
def bench():
    return spec.Benchmark(ROOT)


def rehearsal_root(tmp_path):
    """A copy of the benchmark with the debug sizes laid over it."""
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for group in ("configs", "traffic"):
        for name in os.listdir(os.path.join(DATA, "debug", group)):
            shutil.copy(os.path.join(DATA, "debug", group, name),
                        os.path.join(root, "benchmark", group, name))
    return root


# ------------------------------------------------------------------- traffic
@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_every_seed_sends_the_same_multiset_in_another_order(bench, mix):
    params = bench.traffic(mix)
    make = bench.load("generators", params["kind"], "generate")
    a, b = (make(params, seed, 30.0, 32000) for seed in (1, 3_000_000_019))
    key = "requests" if params["kind"] == "open_loop" else "deck"

    def sizes(t):
        # the warm population at the head of an open loop's pre-roll has its answers
        # cut by seeded shares; everything due after it is the stratified deck
        return [(len(r["prompt"]), r["max_new"]) for r in t[key]
                if r.get("due_s", 0) > -params.get("preroll_s", 0) or key == "deck"]

    for column in (0, 1):  # prompts and answers are shuffled apart: compare each alone
        assert (collections.Counter(s[column] for s in sizes(a))
                == collections.Counter(s[column] for s in sizes(b)))
    assert sizes(a) != sizes(b)
    again = make(params, 1, 30.0, 32000)
    assert sizes(a) == sizes(again)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a[key], again[key]))
    if params["kind"] == "open_loop":
        def gaps(t):
            due = [r["due_s"] for r in t["requests"] if 0 <= r["due_s"] < 30.0]
            return sorted(round(y - x, 6) for x, y in zip(due, due[1:]))
        inside = [r for r in a["requests"] if 0 <= r["due_s"] < 30.0]
        assert len(inside) == round(params["rate_rps"] * 30.0)
        assert len(gaps(a)) == len(gaps(b))
        assert min(r["due_s"] for r in a["requests"]) >= -params["preroll_s"]
        warm = [r for r in a["requests"] if r["due_s"] == -params["preroll_s"]]
        assert len(warm) == params["warm_live"]
        # every 5 s block of the window carries the same number of requests and
        # nearly the same answer tokens, whatever the seed
        for t in (a, b):
            blocks = [[r for r in t["requests"] if lo <= r["due_s"] < lo + 5.0]
                      for lo in (0.0, 5.0, 10.0, 15.0, 20.0, 25.0)]
            counts = [len(blk) for blk in blocks]
            tokens = [sum(r["max_new"] for r in blk) for blk in blocks]
            assert max(counts) - min(counts) <= 1
            assert max(tokens) < 1.15 * min(tokens)


def test_stratified_lengths_follow_the_distribution():
    dist = {"dist": "lognormal", "median": 160, "sigma": 1.0, "min": 16, "max": 2048}
    values = strata.stratified_lengths(dist, 1001)
    assert values == sorted(values) and values[0] >= 16 and values[-1] <= 2048
    assert values[500] == 160
    uniform = strata.stratified_lengths({"dist": "loguniform", "lo": 128, "hi": 512}, 101)
    assert uniform[50] == 256


def test_percentile_is_a_value_that_occurred():
    values = [5, 1, 4, 2, 3, 10, 9, 8, 7, 6]
    assert percentile(values, 90) == 9 and percentile(values, 50) == 5
    assert percentile(values, 100) == 10 and percentile([], 90) is None


# ------------------------------------------------------------- trace reduction
def synthetic_trace():
    ms = 1_000_000
    ops = [["while.1 while (tuple)", 0, 7 * ms],      # holds the three below; 0.5 ms its own
           ["fusion.1", 0, 2 * ms], ["paged_decode_attention.3", 2 * ms, 2 * ms],
           ["all-gather.2", 4 * ms, 5 * ms // 2],     # nothing else runs meanwhile
           ["fusion.1", 10 * ms, 1 * ms]]             # gap of 3 ms before it
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": [["jit_step", 0, 7 * ms],
                                                          ["jit_tiny", 8 * ms, ms // 2],
                                                          ["jit_step", 10 * ms, 1 * ms]]}},
            "host": [["bench.engine.put", 6 * ms, 3 * ms]]}


def test_reduction_on_a_trace_whose_answers_are_known():
    t = synthetic_trace()
    assert trace.busy_seconds(t) == pytest.approx(8e-3)
    assert trace.op_seconds(t)["fusion.1"] == pytest.approx(3e-3)
    assert trace.op_seconds(t)["while.1 while (tuple)"] == pytest.approx(0.5e-3)
    assert trace.matching_seconds(t, "^paged_decode") == pytest.approx(2e-3)
    assert trace.exposed_collective_seconds(t) == pytest.approx(2.5e-3)
    assert trace.module_durations_ms(t) == [7.0, 1.0]
    gaps = trace.idle_gaps(t)
    assert gaps[0] == ["all gaps: inside engine.put", pytest.approx(3e-3)]
    out = trace.breakdown(t)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(3e-3)]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_reduction_reproduces_busy_idle_and_op_sums_of_the_recorded_trace():
    """The recorded piece of a chip trace, reduced here, against the
    numbers written down when it was recorded (and busy time against a
    plain sweep over the events' end points)."""
    recorded = trace.load(os.path.join(DATA, "trace_small.json.gz"))
    with open(os.path.join(DATA, "trace_small.expected.json")) as f:
        expected = json.load(f)
    assert trace.busy_seconds(recorded) == pytest.approx(expected["busy_s"], rel=1e-9)
    sums = trace.op_seconds(recorded)
    for name, seconds in expected["op_seconds"].items():
        assert sums[name] == pytest.approx(seconds, rel=1e-9)
    events = next(iter(trace.ops_of(recorded).values()))
    points = sorted({e[1] for e in events} | {e[1] + e[2] for e in events})
    covered = sum(b - a for a, b in zip(points, points[1:])
                  if any(e[1] <= a and b <= e[1] + e[2] for e in events))
    assert trace.busy_seconds(recorded) == pytest.approx(covered / 1e9, rel=1e-9)
    assert trace.module_durations_ms(recorded) == pytest.approx(expected["modules_ms"])
    assert trace.breakdown(recorded)["idle_gaps"]


# ------------------------------------------------------------------ the files
def test_every_file_found_by_name_validates(bench):
    assert bench.validate() >= 20
    doc = bench.doc
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[group]:
            assert spec.NAME.match(entry["name"]), entry["name"]
    for metric in doc["end_to_end"] + doc["per_layer"]:
        assert spec.UNIT.match(metric["unit"]) and len(metric["unit"]) <= 16
    for workload in doc["workloads"]:
        assert len(workload["why"]) <= 200
    for base, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in base or ".pytest_cache" in base:
            continue
        for name in files:
            assert all(c.isalnum() or c in "_.-" for c in name), os.path.join(base, name)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_a_cell_a_mix_a_config_and_a_metric_are_added_as_files(tmp_path):
    """New files and one entry each in BENCHMARK.json; no file that was
    there is edited."""
    root = rehearsal_root(tmp_path)
    before = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                before[path] = f.read()
    b = os.path.join(root, "benchmark")

    def write(path, obj):
        with open(path, "w") as f:
            json.dump(obj, f)

    with open(os.path.join(b, "configs", "mistral-7b.json")) as f:
        config = json.load(f)
    write(os.path.join(b, "configs", "other-7b.json"), config)
    with open(os.path.join(b, "traffic", "chat.json")) as f:
        mix = json.load(f)
    write(os.path.join(b, "traffic", "chat-slow.json"), {**mix, "rate_rps": 1.0})
    write(os.path.join(b, "cells", "other7b-chat-slow.json"),
          {"config": "other-7b", "traffic": "chat-slow", "chips": 1, "runner": "serve",
           "why": "added by a test"})
    write(os.path.join(b, "layer_metrics", "bursts_per_s.tpot.json"),
          {"layer": "scheduler (inference/v2/scheduler.py)", "unit": "1/s", "better": "lower",
           "source": "program_counter", "moves": "tpot_mean_ms", "cells": ["other7b-chat-slow"],
           "reader": "readers.bursts_per_s:read"})
    with open(os.path.join(b, "readers", "bursts_per_s.py"), "w") as f:
        f.write("def read(run, spec):\n    return run['facts'].get('bursts')\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "other-7b", "source": config["source"],
                           "file": "benchmark/configs/other-7b.json",
                           "reduced": config["reduced"], "why": "test"})
    doc["workloads"].append({"name": "other7b-chat-slow", "config": "other-7b",
                             "traffic": "chat-slow", "chips": 1, "why": "added by a test"})
    for metric in doc["end_to_end"]:
        if metric["name"] == "tpot_mean_ms":
            metric["workloads"].append("other7b-chat-slow")
    doc["per_layer"].append({"name": "bursts_per_s.tpot", "unit": "1/s", "better": "lower",
                             "source": "program_counter",
                             "layer": "scheduler (inference/v2/scheduler.py)",
                             "moves": "tpot_mean_ms", "workloads": ["other7b-chat-slow"]})
    write(os.path.join(root, "BENCHMARK.json"), doc)

    grown = spec.Benchmark(root)
    grown.validate()
    assert grown.cell("other7b-chat-slow")["traffic"] == "chat-slow"
    assert "bursts_per_s.tpot" in grown.metrics_of("other7b-chat-slow", "per_layer")
    assert grown.reader("bursts_per_s.tpot")({"facts": {"bursts": 3}}, {}) == 3
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, f"{path} was edited"
    # ...and the new cell runs, at debug size, through the unchanged run.py
    out = run_cell(root, "other7b-chat-slow", "--rehearse", "--trace", "1", "--seconds", "2")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and "bursts_per_s.tpot" in line["rehearsal"]["metrics"]


def test_an_inconsistent_file_is_refused(tmp_path):
    root = rehearsal_root(tmp_path)
    path = os.path.join(root, "benchmark", "cells", "mistral7b-chat-r2.json")
    with open(path) as f:
        cell = json.load(f)
    with open(path, "w") as f:
        json.dump({**cell, "chips": 4}, f)
    with pytest.raises(spec.SpecError, match="chips"):
        spec.Benchmark(root).validate()
    with pytest.raises(spec.SpecError, match="not a name"):
        spec.Benchmark(root).traffic("../chat")


# -------------------------------------------------------------------- the run
def run_cell(root, workload, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    return subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                           "--workload", workload, "--seed", "3000000019", "--root", root,
                           *extra], capture_output=True, text=True, env=env, timeout=900)


@pytest.mark.parametrize("workload", ["mistral7b-chat-r2", "mixtral8x7b-batch",
                                      "mistral7b-zero3-x4"])
def test_rehearsal_at_debug_size_on_the_cpu(tmp_path, workload):
    out = run_cell(rehearsal_root(tmp_path), workload, "--rehearse", "--seconds", "2")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert "setup_s" in line["rehearsal"]["metrics"]
    assert line["facts"]["compiled_after_warm_up"] == 0


@pytest.mark.parametrize("workload", ["mistral7b-chat-r2", "mixtral8x7b-batch"])
def test_the_facts_list_every_request_behind_the_tpot_metrics(tmp_path, workload):
    """``facts.tpot_by_request``: a row ``[due_s, prompt_len, tokens, tpot_ms]`` for
    each request the TPOT metrics were taken over: their mean over tokens is
    ``tpot_mean_ms`` (end to end), their 90th percentile ``tpot_req_p90_ms`` (per layer)."""
    chat = workload == "mistral7b-chat-r2"  # the cell judged on TPOT: the traced run reads the tail
    out = run_cell(rehearsal_root(tmp_path), workload, "--rehearse", "--seconds", "4",
                   "--trace", "1" if chat else "0")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    rows, facts = line["facts"]["tpot_by_request"], line["facts"]
    assert 0 < len(rows) <= facts["requests_ended_in_window"]
    assert all(len(r) == 4 and r[2] >= 2 and r[3] >= 0 for r in rows)
    assert percentile([r[3] for r in rows], 50) == facts["tpot_p50_ms"]
    mean = sum(r[3] * (r[2] - 1) for r in rows) / sum(r[2] - 1 for r in rows)
    assert mean == pytest.approx(facts["tpot_mean_ms"], rel=1e-9)
    if chat:  # an open loop, so the window's own arrivals are ttft_by_due's
        tail = line["rehearsal"]["metrics"]["tpot_req_p90_ms"]["value"]
        assert percentile([r[3] for r in rows], 90) == tail
        assert {r[0] for r in rows if r[0] >= 0} <= {d[0] for d in facts["ttft_by_due"]}


def test_the_chat_cell_is_judged_on_the_mean_tpot(tmp_path):
    out = run_cell(rehearsal_root(tmp_path), "mistral7b-chat-r2", "--rehearse", "--seconds", "3")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert sorted(line["rehearsal"]["metrics"]) == ["setup_s", "tpot_mean_ms"]
    assert line["rehearsal"]["metrics"]["tpot_mean_ms"]["value"] == line["facts"]["tpot_mean_ms"]


@pytest.mark.parametrize("config", ["mistral-7b", "mixtral-8x7b"])
def test_the_reference_check_fails_its_control(tmp_path, config):
    """The reference in float8 in the program's place is not correct, and
    the program is (debug size; ``benchmark/tests/control.py`` is the same
    reading at the cell's size on the chip)."""
    from benchmark.tests import control
    bench = spec.Benchmark(rehearsal_root(tmp_path))
    for seed in (3, 3_000_000_019):
        got = control.measure(bench, bench.config(config), seed, rehearse=True)
        assert got["program"] < got["tolerance"] < got["control"], got


def test_a_measurement_without_a_tpu_fails_and_prints_no_result():
    out = run_cell(ROOT, "mistral7b-chat-r2", "--seconds", "1")
    assert out.returncode != 0
    assert "TPU" in out.stderr and not out.stdout.strip()


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    root = str(tmp_path / "alone")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "mistral7b-chat-r2",
                          "--seed", "1", "--seconds", "1"], cwd=root, capture_output=True,
                         text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.returncode != 0 and not out.stdout.strip()
