"""Tests of the readers of the host's time between two programs
(``benchmark/readers/host_time.py``) on synthetic step records. CPU,
seconds; like ``test_program_spans.py`` not part of the repo's tier-1 tree.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import program_spans as ps  # noqa: E402
from benchmark.harness import spec, trace  # noqa: E402
from benchmark.readers import host_time  # noqa: E402
from benchmark.tests.test_benchmark import DATA  # noqa: E402
from benchmark.tests.test_program_spans import CLOCK, ring_around  # noqa: E402

MS = 1_000_000
METRICS = ["gap_gateway_ms", "gap_scheduler_ms", "gap_engine_ms", "host_cpu_share", "stalled_share"]
# .serve lists Mixtral's and Moonlight's cells; test_longcat_cell.py and test_sala_cell.py hold
# their cells' metrics to a suffix of their own
SUFFIXES = ("serve", "tpot", "topics", "longdoc")


MARKED = ("ds.engine.pack", "ds.engine.fetch", "ds.sched.accept")


def cpu_clock(ms):
    """The synthetic pump thread's CPU clock at wall time ``ms``: it runs at
    full speed but through ``ds.sched.accept`` of the first pass (30-34 ms:
    half speed) and the waits for the device (3 ms of 100)."""
    def ran(a, b, speed):
        return max(0.0, min(ms, b) - a) * speed
    waits = ((5.0, 29.0), (39.0, 59.0))
    idle = sum(ran(a, b, 0.97) for a, b in waits) + ran(30.0, 34.0, 0.5)
    return int((ms - idle) * MS)


def record(seq, kind, start, end, phases=(), caused_by=0, waited=0):
    """A step record as ``tracing.snapshot()`` gives it since PR 36; times in
    ms; the marked phases leave ``[name, exit, cpu clock]``."""
    spans = [[name, int(a * MS), int(b * MS)] for name, a, b in phases]
    return {"seq": seq, "engine": 1, "kind": kind, "program": "512", "k": 1, "n_seqs": 8,
            "n_tokens": 8, "n_prompt_tokens": 0, "caused_by": caused_by, "uids": [],
            "start_ns": int(start * MS), "end_ns": int(end * MS), "phases": spans, "thread": 7,
            "cpu_marks": [[name, int(b * MS), cpu_clock(b)] for name, _, b in phases if name in MARKED],
            "gc_ns": 0, "gc_passes": 0, "compile_ns": 0, "compiles": 0,
            "waited_ns": int(waited * MS), "idle_passes": int(waited > 0)}


def put(seq, start, pump, fetch_exit):
    """pack 1.5 ms, dispatch 0.5 ms, fetch to ``fetch_exit``, 1 ms of its own after."""
    return record(seq, "put", start, fetch_exit + 1, caused_by=pump,
                  phases=[("ds.engine.pack", start, start + 1.5),
                          ("ds.engine.dispatch", start + 1.5, start + 2),
                          ("ds.engine.fetch", start + 2, fetch_exit)])


def two_passes():
    """Two pump passes, a ``put`` in each: between the first's fetch exit
    (29) and the second's dispatch enter (38.5) lie 1 ms of the record's own
    code, ``accept`` 4 (half of it CPU), ``deliver`` 1, 0.2 of the pass, 0.3
    between the passes, ``admit`` 0.5, ``plan`` 1 and ``pack`` 1.5."""
    return [
        put(2, 3, 1, 29),
        record(1, "pump", 0, 35.2,
               phases=[("ds.gateway.admit", 0, 1), ("ds.sched.plan", 1, 3),
                       ("ds.sched.accept", 30, 34), ("ds.gateway.deliver", 34, 35)]),
        put(4, 37, 3, 59),
        record(3, "pump", 35.5, 62,
               phases=[("ds.gateway.admit", 35.5, 36), ("ds.sched.plan", 36, 37),
                       ("ds.sched.accept", 60, 62)]),
    ]


def test_the_three_layers_add_up_to_the_gap_by_overlap():
    found = host_time.split(two_passes())
    (gap,) = found["gaps"]
    assert gap["seq"] == 4 and gap["ns"] == 9.5 * MS and found["left_out"] == 0
    assert (gap["scheduler"], gap["engine"], gap["gateway"]) == (5 * MS, 2.5 * MS, 2 * MS)
    assert gap["scheduler"] + gap["engine"] + gap["gateway"] == gap["ns"]
    assert gap["phases"] == {
        "ds.sched.accept": 4 * MS, "ds.gateway.deliver": 1 * MS, "ds.gateway.admit": 0.5 * MS,
        "ds.sched.plan": 1 * MS, "ds.engine.pack": 1.5 * MS,
        host_time.NO_PHASE: pytest.approx(1.2 * MS), host_time.NO_PASS: pytest.approx(0.3 * MS)}
    assert sum(gap["phases"].values()) == pytest.approx(gap["ns"])
    # the pump thread's CPU clock between two marks: fetch exit (29) -> accept exit (34) holds
    # 1 ms at full speed and accept's 4 at half; accept exit -> the next pack exit (38.5) is
    # all the thread's own; pack exit -> fetch exit (59) waits for the device but 3 %
    # (and the second pass's 59 -> 62 is its own)
    assert found["segments"]["accept"] == [pytest.approx((3 + 3) * MS, abs=2), (5 + 3) * MS]
    assert found["segments"]["prepare"] == [pytest.approx(4.5 * MS, abs=2), 4.5 * MS]
    assert found["segments"]["device"] == [pytest.approx((1.0 + 0.03 * 44) * MS, abs=2), 45 * MS]


def test_a_pipelined_step_reads_zero_and_a_wait_for_work_is_left_out():
    steps = two_passes()
    # dispatched while the second put was still being fetched: no gap before it
    steps.append(record(5, "burst_async", 56, 90, caused_by=3,
                        phases=[("ds.engine.pack", 56, 57), ("ds.engine.dispatch", 57, 58),
                                ("ds.engine.fetch", 80, 89)]))
    # then the pump waited 40 ms for work before its next pass ran a step
    steps += [put(7, 131, 6, 150), record(6, "pump", 130, 152, waited=40,
                                          phases=[("ds.sched.plan", 130.5, 131)])]
    found = host_time.split(steps)
    assert [(g["seq"], g["ns"]) for g in found["gaps"]] == [(4, 9.5 * MS), (5, 0)]
    assert found["left_out"] == 1
    pipelined = found["gaps"][1]
    assert pipelined["gateway"] == pipelined["scheduler"] == pipelined["engine"] == 0
    # the segment that spans the 40 ms wait (the burst's fetch exit -> the last put's pack exit)
    # is left out of the CPU shares as the gap is of the gaps
    assert found["segments"]["prepare"][1] == (4.5 + 57 - 38.5) * MS


@pytest.fixture(scope="module")
def recorded():
    return trace.load(os.path.join(DATA, "trace_small.json.gz"))


def ring_with_fields(recorded_trace, fields=True):
    """``test_program_spans.ring_around`` with phases in the burst before
    its ``put`` too (it ends 30 ms before the ``put`` starts, 16 ms before the
    pump pass does), and (``fields``) what a record carries since PR 36."""
    steps = ring_around(recorded_trace)
    burst = steps[2]
    s, e = burst["start_ns"], burst["end_ns"]
    burst["phases"] = [["ds.engine.pack", s, s + MS], ["ds.engine.dispatch", s + MS, s + 2 * MS],
                       ["ds.engine.fetch", s + 2 * MS, e]]
    if fields:
        for r in steps:
            # a CPU clock at half the wall clock's speed
            r.update(thread=7, waited_ns=0, idle_passes=0, gc_ns=0, gc_passes=0, compile_ns=0,
                     compiles=0, cpu_marks=[[name, b, b // 2] for name, _, b in r["phases"]
                                            if name in MARKED])
    return steps


def read_all(recorded_trace, monkeypatch, snapshot):
    monkeypatch.setattr(ps, "records", lambda: snapshot)
    bench = spec.Benchmark(ROOT)
    run = {"trace": recorded_trace, "trace_window_s": 0.25, "observed": {}, "facts": {}}
    values = {f"{name}.{suffix}": bench.reader(f"{name}.{suffix}")(
        run, bench.layer_metric(f"{name}.{suffix}")) for name in METRICS for suffix in SUFFIXES}
    return values, run["facts"]


def test_the_readers_through_the_harness_on_a_ring_with_the_new_fields(recorded, monkeypatch):
    steps = ring_with_fields(recorded)
    burst, put_ = steps[2], steps[4]
    end = ps.extent_ns(recorded)[1] + CLOCK
    events = [{"kind": "stall", "start_ns": end - 20_000 * MS, "end_ns": end - 19_000 * MS,
               "seq": 11, "excess_ms": 600.0},
              {"kind": "stall", "start_ns": end - 10_000 * MS, "end_ns": end - 9_500 * MS,
               "seq": 12, "excess_ms": 300.0},
              {"kind": "stall", "start_ns": end - 60_000 * MS, "end_ns": end - 50_000 * MS,
               "seq": 2, "excess_ms": 9000.0},       # before the 45 s the metric looks back
              {"kind": "gc", "start_ns": end - 5_000 * MS, "end_ns": end - 4_990 * MS, "seq": 12,
               "generation": 2, "collected": 7}]
    got, facts = read_all(recorded, monkeypatch, {"steps": steps, "requests": [], "events": events})
    gap = (put_["start_ns"] + 2 * MS - burst["end_ns"]) / 1e6
    assert gap == pytest.approx(32.0)
    for suffix in SUFFIXES:
        layers = [got[f"gap_{layer}_ms.{suffix}"] for layer in ("gateway", "scheduler", "engine")]
        assert sum(layers) == pytest.approx(gap)
        # 16 ms before the pass and 1 ms of admit; plan to 1 us before the put; 2 ms of pack
        assert layers == [pytest.approx(17.001), pytest.approx(12.999), pytest.approx(2.0)]
        assert got[f"host_cpu_share.{suffix}"] == pytest.approx(50.0, abs=0.5)
        assert got[f"stalled_share.{suffix}"] == pytest.approx(100.0 * 900 / 45_000)
    mine = facts["host_time"]
    assert mine["steps"] == 1 and mine["left_out_waiting"] == 0 and mine["pipelined"] == 0
    assert mine["gap_ms"]["max"] == pytest.approx(gap) == mine["layers_ms"]["sum"]
    assert mine["by_phase_ms"]["ds.engine.pack"] == pytest.approx(2.0)
    assert mine["cpu_by_segment"]["prepare"] == {"ms": pytest.approx(32.0), "cpu_share": pytest.approx(50.0)}
    assert [e["excess_ms"] for e in mine["events"]["stall"]] == [600.0, 300.0]
    assert mine["events"]["compile"] == [] and mine["events"]["gc"]["passes"] == 1
    assert mine["events"]["gc"]["ms"] == 10.0 and mine["events"]["gc"]["longest"][0]["collected"] == 7
    assert mine["longest_record"]["seq"] == 7 and mine["longest_record"]["ms"] == 540.0
    assert mine["longest_gap"] == {"seq": 11, "ms": pytest.approx(gap), "phase": host_time.NO_PASS}


def test_records_without_the_new_fields_give_none(recorded, monkeypatch):
    """The parent of PR 36: its records have phases and no CPU time, its
    snapshot no events; the older readers read them as before."""
    steps = ring_with_fields(recorded, fields=False)
    got, facts = read_all(recorded, monkeypatch, {"steps": steps, "requests": []})
    assert got == dict.fromkeys(got) and "host_time" not in facts
    assert host_time.split(steps) is None
    # without a traced run nothing is read either
    bench = spec.Benchmark(ROOT)
    run = {"trace": None, "trace_window_s": None, "observed": {}, "facts": {}}
    assert bench.reader("gap_engine_ms.serve")(run, bench.layer_metric("gap_engine_ms.serve")) is None
    assert bench.reader("stalled_share.tpot")(run, bench.layer_metric("stalled_share.tpot")) is None
