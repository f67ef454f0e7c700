"""The recorder of step records and request stamps (utils/tracing.py):
bounded rings, phases that stamp the record open on the thread, no lock
on the write path, snapshot()/dump() that read what was written."""

import json
import sys
import threading

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
    assert rec.current() is None and rec.snapshot() == {"steps": [], "requests": []}


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
