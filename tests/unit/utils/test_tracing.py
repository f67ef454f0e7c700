"""The recorder of step records and request stamps (utils/tracing.py):
bounded rings, phases that stamp the record open on the thread, no lock
on the write path, snapshot()/dump() that read what was written."""

import gc
import json
import sys
import threading
import time

import pytest

from deepspeed_tpu.utils import tracing


@pytest.mark.parametrize("ring,written", [("steps", 11), ("requests", 7)])
def test_rings_are_bounded_and_keep_the_newest(ring, written):
    rec = tracing.Recorder(step_ring=4, request_ring=3)
    for i in range(written):
        if ring == "steps":
            with rec.step("put", n_tokens=i):
                pass
        else:
            rec.request(uid=i)
    snap = rec.snapshot()[ring]
    size = 4 if ring == "steps" else 3
    assert len(snap) == size
    key = "n_tokens" if ring == "steps" else "uid"
    assert [r[key] for r in snap] == list(range(written - size, written))
    assert tracing.RECORDER.steps.maxlen == tracing.STEP_RING == 8192
    assert tracing.RECORDER.requests.maxlen == tracing.REQUEST_RING == 4096


def test_phases_stamp_the_innermost_open_record_in_order():
    rec = tracing.Recorder()
    with rec.step("pump", span="gateway.pump") as pump:
        with rec.phase("gateway.admit"):
            pass
        with rec.phase("sched.plan"):
            pass
        with rec.step("put", engine=3, k=1, n_seqs=2, n_tokens=9, uids=(5, 6)) as put:
            with rec.phase("engine.pack"):
                with rec.phase("engine.pack.inner"):     # a phase inside a phase
                    pass
            with rec.phase("engine.dispatch"):
                pass
            assert rec.current() is put
        assert rec.current() is pump
        with rec.phase("sched.accept"):
            pass
    assert rec.current() is None
    first, second = rec.snapshot()["steps"]          # the inner record ended first
    assert (first["kind"], second["kind"]) == ("put", "pump")
    assert first["caused_by"] == second["seq"] and second["caused_by"] == 0
    assert first["engine"] == 3 and first["uids"] == [5, 6] and first["n_tokens"] == 9
    assert [p[0] for p in second["phases"]] == ["ds.gateway.admit", "ds.sched.plan",
                                                "ds.sched.accept"]
    # the inner phase closes first; every phase lies inside its record, in order
    assert [p[0] for p in first["phases"]] == ["ds.engine.pack.inner", "ds.engine.pack",
                                               "ds.engine.dispatch"]
    inner, pack, dispatch = first["phases"]
    assert first["start_ns"] <= pack[1] <= inner[1] <= inner[2] <= pack[2] <= dispatch[1] \
        <= dispatch[2] <= first["end_ns"]
    assert second["start_ns"] <= first["start_ns"] and first["end_ns"] <= second["end_ns"]


def test_a_phase_outside_any_record_is_a_no_op():
    rec = tracing.Recorder()
    with rec.phase("sched.plan"):
        pass
    assert rec.current() is None
    assert rec.snapshot() == {"steps": [], "requests": [], "events": []}


def test_a_record_is_dropped_when_its_block_raises_or_clears_keep():
    rec = tracing.Recorder()
    with pytest.raises(ValueError):
        with rec.step("put"):
            with rec.phase("engine.pack"):
                raise ValueError("rejected batch")
    assert rec.current() is None
    with rec.step("pump") as idle:
        idle.keep = False
    assert rec.snapshot()["steps"] == []


def test_a_suspended_record_stays_open_across_other_records():
    """An async burst: opened at dispatch, ended by the fetch, with another
    record written in between."""
    rec = tracing.Recorder()
    burst = rec.begin("burst_async", k=4)
    with rec.phase("engine.dispatch"):
        pass
    rec.suspend(burst)
    assert rec.current() is None and rec.snapshot()["steps"] == []
    with rec.step("put"):
        pass
    rec.resume(burst)
    with rec.phase("engine.fetch"):
        pass
    rec.end(burst)
    kinds = [(r["kind"], [p[0] for p in r["phases"]]) for r in rec.snapshot()["steps"]]
    assert kinds == [("put", []), ("burst_async", ["ds.engine.dispatch", "ds.engine.fetch"])]
    assert rec.snapshot()["steps"][1]["seq"] < rec.snapshot()["steps"][0]["seq"]


def test_snapshot_and_dump_round_trip(tmp_path):
    rec = tracing.Recorder()
    with rec.step("burst", engine=2, program="burst8", k=8, n_seqs=3, n_tokens=24, uids=(1, 2, 3)):
        with rec.phase("engine.fetch"):
            pass
    rec.request(uid=1, status="completed", submitted_ns=5, admitted_ns=9, first_token_ns=None)
    snap = rec.snapshot()
    assert json.loads(json.dumps(snap)) == snap          # plain lists and dicts
    path = str(tmp_path / "records.jsonl")
    assert rec.dump(path) == 2
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert [line.pop("record") for line in lines] == ["step", "request"]
    assert lines == [snap["steps"][0], snap["requests"][0]]


def test_writers_take_no_lock_and_lose_no_record():
    """More threads than cores write at once, with the interpreter told
    to switch threads as often as it can: every record arrives, each with
    a seq of its own, and each thread's phases land in its own records."""
    rec = tracing.Recorder(step_ring=100_000, request_ring=100_000)
    locks = (type(threading.Lock()), type(threading.RLock()))
    assert not any(isinstance(v, locks) for v in vars(rec).values())
    threads, each = 16, 400

    def write(t):
        for i in range(each):
            with rec.step("put", engine=t, n_tokens=i):
                with rec.phase(f"engine.t{t}"):
                    pass
            rec.request(uid=(t, i))

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=write, args=(t,)) for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not any(w.is_alive() for w in workers)
    snap = rec.snapshot()
    assert len(snap["steps"]) == len(snap["requests"]) == threads * each
    assert len({r["seq"] for r in snap["steps"]}) == threads * each
    assert all([p[0] for p in r["phases"]] == [f"ds.engine.t{r['engine']}"] for r in snap["steps"])
    assert sorted(q["uid"] for q in snap["requests"]) == [
        (t, i) for t in range(threads) for i in range(each)]


# ------------------------------------------- what a record says of its thread's time
def busy(ms):
    """Burn CPU on this thread for about ``ms`` milliseconds."""
    until = time.thread_time_ns() + int(ms * 1e6)
    while time.thread_time_ns() < until:
        pass


def test_marked_phases_leave_the_threads_cpu_clock_and_the_others_do_not():
    rec = tracing.Recorder()
    with rec.step("pump") as pump:
        with rec.phase("gateway.admit"):
            busy(1)
        with rec.step("put") as put:
            with rec.phase("engine.pack"):
                busy(3)
            with rec.phase("engine.dispatch"):
                pass
            with rec.phase("engine.fetch"):
                time.sleep(0.02)                  # waiting costs wall time and no CPU time
        with rec.phase("sched.accept"):
            busy(2)
    inner, outer = rec.snapshot()["steps"]
    assert all(len(p) == 3 for r in (inner, outer) for p in r["phases"])    # still [name, enter, exit]
    assert inner["thread"] == outer["thread"] == threading.get_ident()
    assert [m[0] for m in inner["cpu_marks"]] == ["ds.engine.pack", "ds.engine.fetch"]
    assert [m[0] for m in outer["cpu_marks"]] == ["ds.sched.accept"]
    packed, fetched, accepted = inner["cpu_marks"] + outer["cpu_marks"]
    exits = {name: exit_ for r in (inner, outer) for name, _, exit_ in r["phases"]}
    assert [m[1] for m in (packed, fetched, accepted)] == [
        exits["ds.engine.pack"], exits["ds.engine.fetch"], exits["ds.sched.accept"]]
    # between two marks: the wait for the device is wall time without CPU time, accept is both
    assert fetched[1] - packed[1] >= 20e6 and 0 <= fetched[2] - packed[2] < 10e6
    assert 2e6 <= accepted[2] - fetched[2] <= accepted[1] - fetched[1] + 1e6
    assert put.cpu_marks[0] == tuple(packed) and pump.waited_ns == pump.idle_passes == 0
    assert {"ds.engine.pack", "ds.engine.fetch", "ds.sched.accept"} <= tracing.CPU_MARKED
    assert not {"ds.engine.dispatch", "ds.gateway.admit", "ds.sched.plan"} & tracing.CPU_MARKED


def events_of(rec, kind, seq):
    return [e for e in rec.snapshot()["events"] if e["kind"] == kind and e["seq"] == seq]


def test_a_collector_pass_inside_a_record_is_counted_and_leaves_an_event():
    rec = tracing.Recorder()
    junk = [[i] for i in range(200_000)]                     # a full pass takes over a millisecond
    with rec.step("pump") as outer:
        with rec.step("put") as inner:
            gc.collect()
    del junk
    put, pump = rec.snapshot()["steps"]
    assert put["gc_passes"] >= 1 and put["gc_ns"] > 0
    # a record holds what happened inside the records it caused, too
    assert pump["gc_passes"] >= put["gc_passes"] and pump["gc_ns"] >= put["gc_ns"]
    assert tracing.process_counters()[1] >= pump["gc_passes"]
    full = [e for e in events_of(rec, "gc", inner.seq) if e["generation"] == 2]
    assert full and all(e["end_ns"] - e["start_ns"] >= tracing.EVENT_MIN_NS for e in full)
    assert put["start_ns"] <= full[0]["start_ns"] <= full[0]["end_ns"] <= put["end_ns"]
    assert "collected" in full[0] and events_of(rec, "gc", outer.seq) == []


def test_a_compile_inside_a_record_is_counted_once_and_leaves_an_event():
    import jax
    import jax.numpy as jnp
    rec = tracing.Recorder()
    fresh = jax.jit(lambda x: x * 3 + len(rec.steps))        # a function nobody has compiled
    x = jnp.ones(7)
    with rec.step("put") as first:
        fresh(x)
    with rec.step("put") as second:
        fresh(x)
    one, two = rec.snapshot()["steps"]
    assert one["compiles"] >= 1 and one["compile_ns"] > 0
    assert two["compiles"] == 0 and two["compile_ns"] == 0
    compiled = [e for e in events_of(rec, "compile", first.seq)
                if e["name"] == "backend_compile_duration"]
    assert len(compiled) == one["compiles"] and "lambda" in compiled[-1]["program"]
    assert compiled[-1]["end_ns"] - compiled[-1]["start_ns"] == int(compiled[-1]["seconds"] * 1e9)
    assert one["start_ns"] <= compiled[-1]["start_ns"] and compiled[-1]["end_ns"] <= one["end_ns"]
    assert events_of(rec, "compile", second.seq) == []


def test_the_events_ring_is_bounded_and_dumped(tmp_path):
    rec = tracing.Recorder(event_ring=5)
    with rec.step("put") as record:
        for i in range(9):
            rec.event("stall", i, i + 1, excess_ms=float(i))
    rec.event("gc", 20, 30, seq=77, generation=2, collected=0)
    events = rec.snapshot()["events"]
    assert [e["start_ns"] for e in events] == [5, 6, 7, 8, 20]
    assert [e["seq"] for e in events] == [record.seq] * 4 + [77]
    assert tracing.RECORDER.events.maxlen == tracing.EVENT_RING == 1024
    path = str(tmp_path / "records.jsonl")
    assert rec.dump(path) == 6
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert [line["record"] for line in lines] == ["step"] + ["event"] * 5
    assert lines[-1] == {"record": "event", **events[-1]}
    assert (tracing.STALL_NS, tracing.STALL_MIN_RECORDS, tracing.STALL_MEDIAN_OF) == (250_000_000, 8, 32)
