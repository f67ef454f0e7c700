"""Tests of the join between the program's step records and the device
trace (``benchmark/harness/program_spans.py``) and of the readers built
on it. CPU, seconds; like ``test_benchmark.py`` they are not part of the
repo's tier-1 tree.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import program_spans as ps  # noqa: E402
from benchmark.harness import spec, trace  # noqa: E402
from benchmark.tests.test_benchmark import DATA, rehearsal_root, run_cell  # noqa: E402

MS = 1_000_000
CLOCK = 7_000_000_000_000      # the program's clock reads this when the profiler's reads 0


@pytest.fixture(scope="module")
def recorded():
    return trace.load(os.path.join(DATA, "trace_small.json.gz"))


def step(seq, kind, start, dur, k=1, n_prompt_tokens=0, phases=(), caused_by=0):
    return {"seq": seq, "engine": 1, "kind": kind, "program": kind, "k": k, "n_seqs": 8,
            "n_tokens": 8 * k, "n_prompt_tokens": n_prompt_tokens, "caused_by": caused_by,
            "uids": [], "start_ns": start, "end_ns": start + dur,
            "phases": [[name, a, b] for name, a, b in phases]}


def ring_around(recorded_trace, shift=0, put_dur=None):
    """A synthetic ring on the program's clock around the one
    ``bench.engine.put`` span of the recorded trace: bursts and puts of
    other lengths before and after, the wrapped ``put`` (40 us inside its
    span) under a pump pass whose ``ds.sched.plan`` covers the 12 ms idle
    gap before the device starts on it."""
    (_, start, dur), = recorded_trace["host"]
    at = CLOCK + start + 40_000 + shift
    dur = (dur - 60_000) if put_dur is None else put_dur
    pump = step(10, "pump", at - 14 * MS, dur + 16 * MS,
                phases=[("ds.gateway.admit", at - 14 * MS, at - 13 * MS),
                        ("ds.sched.plan", at - 13 * MS, at - 1000),
                        ("ds.sched.accept", at + dur + 1000, at + dur + MS)])
    put = step(11, "put", at, dur, n_prompt_tokens=300, caused_by=10,
               phases=[("ds.engine.pack", at, at + 2 * MS),
                       ("ds.engine.dispatch", at + 2 * MS, at + 3 * MS),
                       ("ds.engine.fetch", at + 3 * MS, at + dur)])
    return [step(7, "burst", at - 900 * MS, 540 * MS, k=8),
            step(8, "put", at - 350 * MS, 171 * MS, n_prompt_tokens=512),
            step(9, "burst", at - 170 * MS, 140 * MS, k=2),
            pump, put,
            step(12, "burst", at + 200 * MS, 270 * MS, k=4),
            step(13, "put", at + 500 * MS, 66 * MS)]


def test_align_finds_the_offset_of_the_ring_around_the_recorded_trace(recorded):
    found = ps.align(recorded, ring_around(recorded))
    assert found is not None and found["matched"] == found["spans"] == 1
    assert found["offset_ns"] == -(CLOCK + 40_000) and found["residual_ns"] == 0
    inside = ps.in_window(recorded, ring_around(recorded), found["offset_ns"])
    assert [r["seq"] for r in inside] == [10, 11]   # 9 began before the trace, 12 after its end


@pytest.mark.parametrize("ring", ["shifted", "truncated", "mismatched", "ambiguous", "empty"])
def test_align_refuses_a_ring_that_does_not_fit(recorded, ring):
    steps = ring_around(recorded)
    if ring == "shifted":         # the wrapped call lasted 5 ms less than its span
        steps = ring_around(recorded, put_dur=recorded["host"][0][2] - 5 * MS)
    elif ring == "truncated":     # the ring rolled past the call the span wraps
        steps = [r for r in steps if r["seq"] > 11]
    elif ring == "mismatched":    # a burst where the span says put
        steps = [dict(r, kind="burst") if r["seq"] == 11 else r for r in steps]
    elif ring == "ambiguous":     # two calls fit the one span: no guess
        steps = steps + [dict(steps[4], seq=14, start_ns=steps[4]["start_ns"] + 2000 * MS,
                              end_ns=steps[4]["end_ns"] + 2000 * MS)]
    elif ring == "empty":
        steps = []
    assert ps.align(recorded, steps) is None


def test_align_over_many_spans_tolerates_one_in_twenty_and_reports_the_residual():
    spans, steps, t = [], [], 0
    for i in range(40):
        kind, dur = ("put", (60 + 3 * i) * MS) if i % 3 else ("burst", (200 + 7 * i) * MS)
        name = "bench.engine.put" if kind == "put" else "bench.engine.decode_burst"
        jitter = (i % 5) * 10_000          # span start - record start differs by tens of us
        spans.append([name, t, dur + 30_000])
        steps.append(step(i + 1, kind, CLOCK + t + 20_000 - jitter, dur))
        t += dur + 5 * MS
    steps[7] = dict(steps[7], end_ns=steps[7]["end_ns"] + 9 * MS)   # one call does not match
    found = ps.align({"devices": {}, "host": spans}, steps)
    assert found["matched"] == 39 and found["spans"] == 40
    assert found["offset_ns"] == pytest.approx(-CLOCK, abs=50_000)
    assert 0 < found["residual_ns"] <= 20_000 and found["worst_ns"] <= 40_000
    for i in (3, 11, 19):                  # three of forty: under 0.95
        steps[i] = dict(steps[i], end_ns=steps[i]["end_ns"] + 9 * MS)
    assert ps.align({"devices": {}, "host": spans}, steps) is None


def test_gap_owners_add_up_to_the_idle_time_of_the_trace(recorded):
    steps = ring_around(recorded)
    offset = ps.align(recorded, steps)["offset_ns"]
    owners = ps.gap_owners(recorded, steps, offset)
    events = next(iter(trace.ops_of(recorded).values()))
    extent = max(e[1] + e[2] for e in events) - min(e[1] for e in events)
    assert sum(owners.values()) == pytest.approx(extent / 1e9 - trace.busy_seconds(recorded),
                                                 abs=1e-12)
    # the 12 ms gap before the step lies under ds.sched.plan; the nanosecond gaps
    # between ops of the running program lie inside the put record
    assert owners["scheduler"] == pytest.approx(0.01197, abs=1e-4)
    assert 0 < owners["engine"] < 1e-4 and owners["gateway"] == 0
    # without the pump pass and the step, nobody owns them
    assert ps.gap_owners(recorded, steps[:1], offset)["outside"] == pytest.approx(
        sum(owners.values()))
    assert ps.gap_owners({"devices": {}, "host": recorded["host"]}, steps, offset) is None
    # below the layers: the same gaps by the innermost phase over their middle
    phases = ps.gap_phases(recorded, steps, offset)
    assert sum(phases.values()) == pytest.approx(sum(owners.values()))
    assert phases["ds.sched.plan"] == pytest.approx(owners["scheduler"])
    assert set(phases) <= {"ds.sched.plan", "ds.engine.dispatch", "ds.engine.fetch", "(none)"}


NEW = ["decode_step_ms_p50.tpot", "decode_step_ms_p50.serve", "mixed_step_ms_p50.tpot",
       "mixed_step_ms_p50.serve", "burst_k_mean.tpot", "burst_k_mean.serve",
       "pump_wait_p90_ms", "prefill_span_p90_ms", "idle_gateway.tpot", "idle_gateway.serve",
       "idle_scheduler.tpot", "idle_scheduler.serve", "idle_engine.tpot", "idle_engine.serve",
       "step_host_ms_p50.train"]


@pytest.mark.parametrize("metric", NEW)
def test_every_new_reader_returns_none_without_a_trace(metric):
    bench = spec.Benchmark(ROOT)
    run = {"trace": None, "trace_window_s": None, "observed": {}, "facts": {}}
    assert bench.reader(metric)(run, bench.layer_metric(metric)) is None
    assert "program_spans" not in run["facts"]


def test_readers_on_a_ring_written_by_the_program(recorded, monkeypatch):
    """The readers on records as ``tracing.snapshot()`` gives them, with
    the recorded trace: the numbers are the synthetic ring's."""
    steps = ring_around(recorded)
    end = ps.extent_ns(recorded)[1] + CLOCK + 40_000
    requests = [{"uid": i, "status": "completed", "submitted_ns": end - (i + 1) * 1000 * MS,
                 "admitted_ns": end - (i + 1) * 1000 * MS + (i + 1) * 10 * MS,
                 "first_scheduled_ns": end - (i + 1) * 1000 * MS + (i + 1) * 11 * MS,
                 "first_token_ns": end - (i + 1) * 1000 * MS + (i + 1) * 31 * MS,
                 "prefill_steps": 1} for i in range(50)]
    monkeypatch.setattr(ps, "records", lambda: {"steps": steps, "requests": requests})
    bench = spec.Benchmark(ROOT)
    run = {"trace": recorded, "trace_window_s": 0.25, "observed": {}, "facts": {}}

    def read(metric):
        return bench.reader(metric)(run, bench.layer_metric(metric))

    put = steps[4]
    assert read("mixed_step_ms_p50.tpot") == pytest.approx((put["end_ns"] - put["start_ns"]) / 1e6 - 2)
    assert read("decode_step_ms_p50.tpot") is None and read("burst_k_mean.tpot") is None  # no phases
    # 45 requests were submitted in the 45 s before the trace ended; nearest rank
    assert read("pump_wait_p90_ms") == pytest.approx(410.0)
    assert read("prefill_span_p90_ms") == pytest.approx(820.0)
    idle = [read(f"idle_{owner}.tpot") for owner in ("gateway", "scheduler", "engine")]
    facts = run["facts"]["program_spans"]
    assert facts["aligned"] and facts["requests"] == 45
    outside = 100.0 * facts["idle_gap_s"]["outside"] / 0.25
    assert sum(idle) + outside == pytest.approx(100.0 * (1 - trace.busy_seconds(recorded) / 0.25))


def test_a_traced_rehearsal_reports_the_new_metrics_that_need_no_device(tmp_path):
    """``--rehearse --trace 1`` of the chat cell on the CPU: the program's
    own records, joined to the CPU profile's ``bench.*`` host spans."""
    out = run_cell(rehearsal_root(tmp_path), "mistral7b-chat-r2", "--rehearse", "--trace", "1",
                   "--seconds", "8")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["metrics"] == {}
    got, facts = line["rehearsal"]["metrics"], line["facts"].get("program_spans")
    assert facts is not None, "the readers did not run"
    if not facts["aligned"]:
        pytest.fail("the CPU profile carried no bench.* host spans that fit the program's "
                    f"records ({facts}); the readers are checked on a ring in the tests above")
    assert facts["matched"] >= 0.95 * facts["spans"] and facts["residual_ns"] < 1_000_000
    for name in ("burst_k_mean.tpot", "decode_step_ms_p50.tpot", "mixed_step_ms_p50.tpot",
                 "pump_wait_p90_ms", "prefill_span_p90_ms"):
        assert name in got and got[name]["value"] > 0, (name, got, facts)
    assert not any(name.startswith("idle_") for name in got)   # no device plane on the CPU
    assert sum(facts["burst_k"].values()) >= 1 and facts["requests"] >= 1
