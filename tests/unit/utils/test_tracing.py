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
    snap = rec.snapshot()
    assert (snap["steps"], snap["requests"], snap["events"]) == ([], [], [])
    assert [row["kind"] for row in snap["builds"]] == ["outside"]


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
    # a collector pass of a millisecond or more, on any thread of the process, is told
    # every live recorder's events ring: force one, so that the counts below hold with it
    cycles = []
    for _ in range(200_000):
        cell = []
        cell.append(cell)
        cycles.append(cell)
    del cycles, cell
    gc.collect()
    snap = rec.snapshot()
    assert any(e["kind"] == "gc" for e in snap["events"])
    assert json.loads(json.dumps(snap)) == snap          # plain lists and dicts
    path = str(tmp_path / "records.jsonl")
    assert rec.dump(path) == sum(len(group) for group in snap.values())
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    # the events are whatever the process did meanwhile; the step and the request are ours
    labels = [line.pop("record") for line in lines]
    assert [label for label in labels if label in ("step", "request")] == ["step", "request"]
    assert set(labels) <= {"step", "request", "event", "build"}
    assert lines[:2] == [snap["steps"][0], snap["requests"][0]]
    assert [line for line, label in zip(lines, labels) if label == "event"] == snap["events"]
    assert [line for line, label in zip(lines, labels) if label == "build"] == snap["builds"]


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
    assert rec.dump(path) == 7
    with open(path) as f:
        lines = [json.loads(line) for line in f]
    assert [line["record"] for line in lines] == ["step"] + ["event"] * 5 + ["build"]
    assert lines[-2] == {"record": "event", **events[-1]}
    assert (tracing.STALL_NS, tracing.STALL_MIN_RECORDS, tracing.STALL_MEDIAN_OF) == (250_000_000, 8, 32)


# ------------------------------------------------ set-up: own compile time, the build table
class Clock:
    """``tracing.now_ns`` by hand, for compile events fed to the listener."""

    def __init__(self):
        self.ns = 1_000_000_000

    def __call__(self):
        return self.ns

    def event(self, name, ms, fun_name, took_ms=None):
        """``ms`` pass, then JAX reports an event of ``took_ms`` (default: ``ms``)."""
        self.ns += int(ms * 1e6)
        tracing._on_duration(name, (ms if took_ms is None else took_ms) / 1e3, fun_name=fun_name)


@pytest.fixture
def clock(monkeypatch):
    """A clock of its own and a thread with no compile events behind it."""
    clock = Clock()
    monkeypatch.setattr(tracing, "now_ns", clock)
    tracing._Process.thread.__dict__.clear()
    yield clock
    tracing._Process.thread.__dict__.clear()


def test_a_nested_trace_counts_its_own_time_and_the_outermost_alone_is_told(clock):
    """``outer`` traces for 105 ms, 100 of them inside fifty ``inner`` functions that JAX
    reports themselves: 105 ms are counted, and the ring holds one trace, not 51."""
    rec = tracing.Recorder()
    before = tracing.process_counters()
    parts = tracing.compile_counters()
    with rec.step("put", engine=5, program="512") as record:
        for i in range(50):
            clock.event(tracing.TRACE_EVENT, 2, f"inner{i % 5}")
        clock.event(tracing.TRACE_EVENT, 5, "outer", took_ms=105)
        clock.event(tracing.LOWER_EVENT, 0.5, "jit(outer)")
        clock.event(tracing.COMPILE_EVENT, 40, "jit(outer)")
    wall = record.end_ns - record.start_ns
    assert wall == int(145.5e6) and record.compile_ns == wall and record.compiles == 1
    after = tracing.process_counters()
    assert (after[2] - before[2], after[3] - before[3]) == (wall, 1)
    assert [now - then for now, then in zip(tracing.compile_counters(), parts)] == [
        105_000_000, 500_000, 40_000_000]
    (row, outside) = rec.snapshot()["builds"]
    assert (row["engine"], row["kind"], row["program"], row["seq"], row["builds"]) == (
        5, "put", "512", record.seq, 1)
    assert (row["trace_ns"], row["lower_ns"], row["backend_ns"]) == (105_000_000, 500_000, 40_000_000)
    assert (row["compiles"], row["hits"], row["misses"]) == (1, 0, 0)
    assert row["functions"] == [[f"inner{i}", 10, 20_000_000, 20_000_000] for i in range(5)] + [
        ["outer", 1, 5_000_000, 105_000_000]]
    assert outside["kind"] == "outside" and outside["trace_ns"] == outside["compiles"] == 0
    told = [(e["name"], e["program"], e["own_ns"], e["end_ns"] - e["start_ns"])
            for e in events_of(rec, "compile", record.seq)]
    assert told == [("jaxpr_trace_duration", "outer", 5_000_000, 105_000_000),
                    ("backend_compile_duration", "jit(outer)", 40_000_000, 40_000_000)]


def test_a_nest_of_nests_and_what_a_lowering_traces_are_counted_once(clock):
    rec = tracing.Recorder()
    with rec.step("burst", engine=1, program="burst4") as record:
        clock.event(tracing.TRACE_EVENT, 1, "leaf")
        clock.event(tracing.TRACE_EVENT, 1, "middle", took_ms=2)      # holds leaf
        clock.event(tracing.TRACE_EVENT, 3, "leaf")                   # a sibling of middle
        clock.event(tracing.TRACE_EVENT, 1, "outer", took_ms=6)       # holds all three
        clock.event(tracing.TRACE_EVENT, 2, "rule")                   # traced by a lowering rule
        clock.event(tracing.LOWER_EVENT, 1, "jit(outer)", took_ms=3)
        clock.event(tracing.COMPILE_EVENT, 10, "jit(outer)")
    assert record.compile_ns == record.end_ns - record.start_ns == 19_000_000
    row = rec.snapshot()["builds"][0]
    assert (row["trace_ns"], row["lower_ns"], row["backend_ns"]) == (8_000_000, 1_000_000, 10_000_000)
    assert {name: (times, own, whole) for name, times, own, whole in row["functions"]} == {
        "leaf": (2, 4_000_000, 4_000_000), "middle": (1, 1_000_000, 2_000_000),
        "outer": (1, 1_000_000, 6_000_000), "rule": (1, 2_000_000, 2_000_000)}
    # the trace of a whole program and its lowering are told, what lay inside either is not
    assert [(e["program"], e["own_ns"]) for e in events_of(rec, "compile", record.seq)] == [
        ("outer", 1_000_000), ("jit(outer)", 1_000_000), ("jit(outer)", 10_000_000)]
    # a second record of the same program adds to its row; another program gets its own
    with rec.step("burst", engine=1, program="burst4"):
        clock.event(tracing.COMPILE_EVENT, 5, "jit(outer)")
    with rec.step("burst", engine=1, program="burst8"):
        clock.event(tracing.COMPILE_EVENT, 5, "jit(outer)")
    first, second, _ = rec.snapshot()["builds"]
    assert (first["builds"], first["backend_ns"], first["compiles"], first["seq"]) == (
        2, 15_000_000, 2, record.seq)
    assert (second["program"], second["builds"], second["backend_ns"]) == ("burst8", 1, 5_000_000)


def test_the_cache_says_hit_or_miss_of_the_backend_event_that_follows(clock):
    rec = tracing.Recorder()
    with rec.step("put", engine=2, program="64") as record:
        tracing._on_event("/jax/compilation_cache/compile_requests_use_cache")
        tracing._on_event("/jax/compilation_cache/cache_hits")
        clock.event(tracing.COMPILE_EVENT, 3, "jit(a)")
        tracing._on_event("/jax/compilation_cache/cache_misses")
        clock.event(tracing.COMPILE_EVENT, 30, "jit(b)")
        clock.event(tracing.COMPILE_EVENT, 1, "jit(c)")          # too small for the cache to keep
    row = rec.snapshot()["builds"][0]
    assert (row["compiles"], row["hits"], row["misses"]) == (3, 1, 1)
    assert [e["cache"] for e in events_of(rec, "compile", record.seq)] == ["hit", "miss", None]
    assert record.build.describe().startswith(
        "0.03 s (trace 0.00 own, lower 0.00, backend 0.03, cache hit)")


def test_the_build_table_is_bounded_and_no_record_open_means_outside(clock):
    rec = tracing.Recorder()
    clock.event(tracing.TRACE_EVENT, 4, "callers_own")
    clock.event(tracing.COMPILE_EVENT, 6, "jit(callers_own)")
    for i in range(tracing.BUILD_ROWS + 5):
        with rec.step("put", engine=1, program=str(i)):
            clock.event(tracing.COMPILE_EVENT, 1, "jit(f)")
    rows = rec.snapshot()["builds"]
    assert len(rows) == tracing.BUILD_ROWS + 2
    # the newest rows stay, the oldest five are one
    assert [row["program"] for row in rows[:-2]] == [str(i) for i in range(5, tracing.BUILD_ROWS + 5)]
    assert (rows[-2]["kind"], rows[-2]["builds"], rows[-2]["compiles"]) == ("other", 5, 5)
    outside = rows[-1]
    assert (outside["kind"], outside["trace_ns"], outside["backend_ns"], outside["compiles"]) == (
        "outside", 4_000_000, 6_000_000, 1)
    assert outside["functions"] == [["callers_own", 1, 4_000_000, 4_000_000]]
    # a row keeps the functions traced longest, however many a program traces
    with rec.step("put", engine=1, program="5"):
        for i in range(40):
            clock.event(tracing.TRACE_EVENT, 1 + i, f"f{i}")
    assert [name for name, *_ in rec.snapshot()["builds"][0]["functions"]] == [
        f"f{i}" for i in range(39, 39 - tracing.BUILD_FUNCTIONS, -1)]


def test_a_wide_nest_is_folded_and_still_counted_once(clock, monkeypatch):
    monkeypatch.setattr(tracing, "NEST_MAX", 8)
    rec = tracing.Recorder()
    with rec.step("put") as record:
        for _ in range(30):
            clock.event(tracing.TRACE_EVENT, 1, "leaf")
        assert len(tracing._Process.thread.nest) <= 8
        clock.event(tracing.TRACE_EVENT, 2, "outer", took_ms=32)
    assert record.compile_ns == record.build.trace_ns == 32_000_000
    assert tracing._Process.thread.nest == [(clock.ns - 16_000_000, 32_000_000, (
        clock.ns - 32_000_000, clock.ns, "outer", 0.032, 2_000_000))]


def test_a_real_nest_is_counted_once():
    """A jitted ``outer`` that calls a jitted ``inner`` twice, through the compiler."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        for _ in range(40):
            x = jnp.sin(x) + 1
        return x

    @jax.jit
    def outer(x):
        return inner(x) * 2 + inner(x + 1)

    x = jnp.ones(5)
    rec = tracing.Recorder()
    with rec.step("put", engine=9, program="8") as record:
        outer(x)
    assert 0 < record.compile_ns <= record.end_ns - record.start_ns
    whole = [e for e in events_of(rec, "compile", record.seq) if "outer" in e["program"]]
    assert [e["name"] for e in whole] == ["jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                                          "backend_compile_duration"]
    assert record.compile_ns == pytest.approx(sum(e["seconds"] for e in whole) * 1e9, rel=0.15)
    row = rec.snapshot()["builds"][0]
    assert row["trace_ns"] + row["lower_ns"] + row["backend_ns"] == record.compile_ns
    traced = {name: (times, own) for name, times, own, _ in row["functions"]}
    assert traced["inner"][0] in (1, 2) and traced["outer"][0] == 1
    assert traced["inner"][1] + traced["outer"][1] <= whole[0]["seconds"] * 1e9


def test_a_setup_record_has_contiguous_phases_and_the_process_age():
    rec = tracing.Recorder()
    with rec.step("train", engine=4) as train:
        with rec.setup(4, program="state") as setup:
            setup.phase("setup.params")
            busy(1)
            setup.phase("setup.partition")
            setup.phase("setup.optimizer")
            busy(1)
    record = setup.record
    assert (record.kind, record.engine, record.program, record.caused_by) == (
        "setup", 4, "state", train.seq)
    assert names_of(record) == ["ds.setup.params", "ds.setup.partition", "ds.setup.optimizer"]
    stamps = [t for _, enter, exit_ in record.phases for t in (enter, exit_)]
    assert stamps == sorted(stamps) and stamps[1:-1:2] == stamps[2::2]      # no gap between two
    assert stamps[-1] - stamps[0] >= 0.95 * (record.end_ns - record.start_ns)
    assert record.process_age_ns is None or record.process_age_ns > 0
    if sys.platform == "linux":
        assert 0 < record.process_age_ns < 86_400e9
    assert list(rec.setups) == [record] and rec.snapshot()["steps"][0]["kind"] == "setup"
    assert rec.snapshot()["steps"][0]["process_age_ns"] == record.process_age_ns
    assert rec.snapshot()["steps"][1]["process_age_ns"] is None
    summary = rec.setup_summary(4)
    assert summary["init_ns"] == record.end_ns - record.start_ns
    assert sum(summary["phases_ns"].values()) == stamps[-1] - stamps[0]
    assert summary["process_age_ns"] == record.process_age_ns
    assert rec.setup_summary(5)["init_ns"] == 0 and rec.setup_summary(5)["process_age_ns"] is None
    # a constructor that raises leaves no record, and none open on the thread
    with pytest.raises(RuntimeError):
        with rec.setup(6) as failed:
            failed.phase("setup.params")
            raise RuntimeError("no such device")
    assert rec.current() is None and len(rec.setups) == 1 and len(rec.steps) == 2


def names_of(record):
    return [name for name, _, _ in record.phases]


def test_the_summary_sets_an_engines_programs_beside_its_constructor(clock):
    rec = tracing.Recorder()
    with rec.setup(3) as setup:
        setup.phase("setup.params")
        clock.event(tracing.COMPILE_EVENT, 20, "jit(init)")
    tracing._on_event("/jax/compilation_cache/cache_hits")
    with rec.step("pump", engine=3):
        clock.event(tracing.COMPILE_EVENT, 1, "jit(schedulers_own)")
        with rec.step("put", engine=3, program="64"):
            clock.event(tracing.TRACE_EVENT, 7, "step")
            clock.event(tracing.COMPILE_EVENT, 2, "jit(step)")
    with rec.step("put", engine=8, program="64"):
        clock.event(tracing.COMPILE_EVENT, 100, "jit(another_engines)")
    clock.event(tracing.LOWER_EVENT, 9, "jit(reference)")
    summary = rec.setup_summary(3)
    assert summary["init_ns"] == 20_000_000 and summary["phases_ns"] == {"ds.setup.params": 20_000_000}
    assert summary["init_build"] == {"trace_ns": 0, "lower_ns": 0, "backend_ns": 20_000_000,
                                     "compiles": 1, "hits": 0, "misses": 0}
    assert summary["build"] == {"programs": 1, "trace_ns": 7_000_000, "lower_ns": 0,
                                "backend_ns": 3_000_000, "compiles": 2, "hits": 1, "misses": 0}
    assert summary["outside"]["lower_ns"] == 9_000_000 and summary["outside"]["compiles"] == 0


def test_a_hundred_thousand_empty_records_cost_what_they_did():
    """What a record that built nothing pays for the build table: one comparison at its
    end (``PERF.md`` has the parent's time beside this tree's)."""
    rec = tracing.Recorder()
    started = time.perf_counter()
    for _ in range(20_000):
        with rec.step("put"):
            with rec.phase("engine.pack"):
                pass
    per_record_us = (time.perf_counter() - started) / 20_000 * 1e6
    assert per_record_us < 100 and rec.builds == {} and rec.outside.ns == 0
