"""Step records and request stamps as the engine, the scheduler, the
gateway and the trainer write them (deepspeed_tpu/utils/tracing.py):
one record per program run on every path that runs one, request stamps
that are ordered and point at records that exist, and a gateway snapshot
that keeps every key it had when gauges were pushed each pump pass."""

import contextlib
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, DynamicSplitFuseScheduler,
                                        InferenceEngineV2, PrefixCacheConfig,
                                        RaggedInferenceEngineConfig, SpecDecodeConfig)
from deepspeed_tpu.inference.v2.config_v2 import AsyncBurstConfig
from deepspeed_tpu.models import build_llama
from deepspeed_tpu.serving import ServingConfig, ServingGateway
from deepspeed_tpu.serving.gateway import logger as gateway_logger
from deepspeed_tpu.utils import tracing

PROMPT = (np.arange(1, 13) % 250).astype(np.int32)           # 12 tokens
REPETITIVE = np.tile(np.array([7, 8, 9, 10], np.int32), 6)   # 24 tokens: the drafter finds drafts


@pytest.fixture(scope="module")
def model_and_params():
    model = build_llama("debug")
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(model_and_params, depth=0, spec=False, prefix=False, n_seqs=4,
                batch=32, max_context=96):
    model, params = model_and_params
    cfg = RaggedInferenceEngineConfig(
        kv_block_size=8, num_kv_blocks=0,
        async_burst=AsyncBurstConfig(depth=depth),
        spec_decode=SpecDecodeConfig(enabled=spec),
        prefix_cache=PrefixCacheConfig(enabled=prefix),
        state_manager=DSStateManagerConfig(max_ragged_batch_size=batch,
                                           max_ragged_sequence_count=n_seqs,
                                           max_tracked_sequences=n_seqs,
                                           max_context=max_context))
    return InferenceEngineV2(model=model, config=cfg, params=params, dtype=jnp.float32)


def records_of(engine_id, after=0):
    return [r for r in tracing.snapshot()["steps"]
            if r["engine"] == engine_id and r["seq"] > after]


def last_seq():
    steps = tracing.RECORDER.steps
    return steps[-1].seq if steps else 0


def names(record):
    return [p[0] for p in record["phases"]]


def ordered(record):
    """Phases follow one another inside the record."""
    stamps = [record["start_ns"]] + [t for p in record["phases"] for t in p[1:]] + [record["end_ns"]]
    return stamps == sorted(stamps)


# ------------------------------------------------- every path that runs a program
def run_put(engine):
    engine.put([1, 2], [PROMPT, PROMPT[:1]], sample="greedy")
    return dict(kind="put", k=1, n_seqs=2, n_tokens=13, n_prompt_tokens=12, program="32",
                phases=["ds.engine.pack", "ds.engine.dispatch", "ds.engine.fetch"])


def run_burst(engine):
    first = engine.put([1, 2], [PROMPT, PROMPT + 1], sample="greedy")
    mark = last_seq()
    engine.decode_burst([1, 2], list(first), 4)
    return dict(kind="burst", k=4, n_seqs=2, n_tokens=8, n_prompt_tokens=0, program="burst4",
                after=mark, phases=["ds.engine.pack", "ds.engine.dispatch", "ds.engine.fetch",
                                    "ds.engine.log"])


def run_async(engine):
    first = engine.put([1, 2], [PROMPT, PROMPT + 1], sample="greedy")
    mark = last_seq()
    handle = engine.decode_burst_async([1, 2], [[t] for t in first], 2)
    assert records_of(engine.trace_id, mark) == [], "an unfetched burst has written nothing yet"
    engine.put([3], [PROMPT[:5]], sample="greedy")      # another program in between
    assert [r["kind"] for r in records_of(engine.trace_id, mark)] == ["put"]
    handle.fetch()
    handle.fetch()                                      # idempotent: still one record
    # the burst's seq is older than the put's (it was opened first); it ended later
    assert [r["kind"] for r in records_of(engine.trace_id, mark)] == ["put", "burst_async"]
    return dict(kind="burst_async", k=2, n_seqs=2, n_tokens=4, n_prompt_tokens=0,
                program="burst2", after=mark,
                phases=["ds.engine.pack", "ds.engine.dispatch", "ds.engine.fetch"])


def run_verify(engine):
    first = engine.put([1], [REPETITIVE], sample="greedy")
    mark = last_seq()
    engine.verify_burst([1], [[int(first[0])]], [[8, 9, 10]])
    return dict(kind="verify", k=1, n_seqs=1, n_tokens=4, n_prompt_tokens=0, program="verify3",
                after=mark, phases=["ds.engine.pack", "ds.engine.dispatch", "ds.engine.fetch",
                                    "ds.engine.log"])


@pytest.mark.parametrize("path", ["put", "decode_burst", "async_burst_and_fetch",
                                  "verify_burst", "train_batch"])
def test_every_path_that_runs_a_program_writes_one_record(model_and_params, path):
    if path == "train_batch":
        import deepspeed_tpu
        from deepspeed_tpu.utils import groups
        from tests.unit.simple_model import SimpleModel
        groups.destroy_mesh()
        engine, *_ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=16), config={
                "train_batch_size": 16, "gradient_accumulation_steps": 2,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "mesh": {"data_parallel_size": 8}})
        x = np.random.RandomState(0).randn(16, 16).astype(np.float32)
        y = np.arange(16) % 16
        engine.train_batch(batch=(x, y))
        mark = last_seq()
        engine.train_batch(batch=(x, y))
        want = dict(kind="train", k=2, n_seqs=16, n_tokens=2 * 8 * 16, n_prompt_tokens=0,
                    program="train_batch", after=mark,
                    phases=["ds.train.prepare", "ds.train.timer_sync", "ds.train.dispatch",
                            "ds.train.sync", "ds.train.timer_sync", "ds.train.post"])
    else:
        engine = make_engine(model_and_params, depth=2 * (path == "async_burst_and_fetch"),
                             spec=path == "verify_burst")
        want = {"put": run_put, "decode_burst": run_burst, "async_burst_and_fetch": run_async,
                "verify_burst": run_verify}[path](engine)
    wrote = [r for r in records_of(engine.trace_id, want.pop("after", 0))
             if r["kind"] == want["kind"]]
    assert len(wrote) == 1, [r["kind"] for r in wrote]
    record, phases = wrote[0], want.pop("phases")
    assert {key: record[key] for key in want} == want
    assert names(record) == phases and ordered(record)
    assert record["caused_by"] == 0                      # nobody's pump pass
    if path != "train_batch":
        assert engine.last_step.seq == record["seq"]
    engine.destroy()


def test_a_rejected_batch_writes_no_record(model_and_params):
    engine = make_engine(model_and_params)
    mark = last_seq()
    with pytest.raises(ValueError):
        engine.put([1], [np.zeros(500, np.int32)])       # over the token budget
    with pytest.raises(ValueError):
        engine.decode_burst_async([9], [[1]], 2)         # unknown sequence
    assert records_of(engine.trace_id, mark) == [] and tracing.current() is None
    engine.destroy()


@pytest.mark.parametrize("path", ["put", "decode_burst", "verify_burst"])
def test_a_dispatched_program_is_fetched_whatever_the_work_meanwhile_raises(
        model_and_params, monkeypatch, path):
    """``engine.while_running`` (the scheduler's hand-over) runs between a
    program's dispatch and its fetch; if it raises, the fetch still
    happens, the exception reaches the caller, and the engine goes on."""
    engine = make_engine(model_and_params, spec=path == "verify_burst")
    first = engine.put([1], [REPETITIVE], sample="greedy")
    run = {"put": lambda tok: engine.put([1], [[tok]], sample="greedy"),
           "decode_burst": lambda tok: engine.decode_burst([1], [tok], 2),
           "verify_burst": lambda tok: engine.verify_burst([1], [[tok]], [[8, 9, 10]])}[path]
    entered, real_phase, me = [], tracing.phase, threading.get_ident()

    def phase(name):
        if threading.get_ident() == me:     # another test's pump thread may still be polling
            entered.append(name)
        return real_phase(name)
    monkeypatch.setattr(tracing, "phase", phase)

    def work():
        entered.append("work")
        raise RuntimeError("a stream's queue broke")
    engine.while_running = work
    mark, seen = last_seq(), engine.query(1)[0]
    with pytest.raises(RuntimeError, match="queue broke"):
        run(int(first[0]))
    assert entered[-3:] == ["engine.dispatch", "work", "engine.fetch"]
    assert records_of(engine.trace_id, mark) == [] and tracing.current() is None
    # the program ran and its rows are written; a verify counts them once it knows how many
    # were accepted, which it was not told: the same rows are written again
    assert (engine.query(1)[0] > seen) == (path != "verify_burst")
    engine.while_running = lambda: entered.append("work")
    del entered[:]
    run(int(first[0]))                          # ... and the next runs, the work inside it
    assert entered.index("engine.dispatch") < entered.index("work") < entered.index("engine.fetch")
    assert len(records_of(engine.trace_id, mark)) == 1
    engine.destroy()


def test_the_scheduler_says_which_tokens_are_prompt(model_and_params):
    """A one-token prompt chunk looks like a decode token to the engine;
    ``_plan`` knows better and corrects the record."""
    engine = make_engine(model_and_params, batch=32)
    sched = DynamicSplitFuseScheduler(engine, token_budget=16, max_burst=1)
    sched.add_request(1, np.arange(1, 18, dtype=np.int32), max_new_tokens=2)   # 16 + 1
    mark = last_seq()
    sched.run_to_completion()
    puts = records_of(engine.trace_id, mark)
    assert [r["n_prompt_tokens"] for r in puts] == [16, 1, 0]
    assert [r["n_tokens"] for r in puts] == [16, 1, 1]
    request = sched.requests[1]
    assert request.prefill_steps == 2 and request.first_scheduled_seq == puts[0]["seq"]
    assert request.first_token_seq == puts[1]["seq"]
    engine.destroy()


# ------------------------------------------------------------------ the gateway
def serve(model_and_params, prompts, max_new=6, depth=0, **config):
    engine = make_engine(model_and_params, depth=depth, n_seqs=8, batch=16)
    mark = last_seq()
    gw = ServingGateway(engine, config=ServingConfig(token_budget=16, **config))
    handles = [gw.submit(p, max_new_tokens=max_new) for p in prompts]
    for h in handles:
        h.result(timeout=120)
    return engine, gw, handles, mark


@pytest.mark.parametrize("depth", [0, 2])
def test_request_stamps_are_ordered_and_point_at_records_that_exist(model_and_params, depth):
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (5, 40, 12, 3)]   # 40 = three chunks
    engine, gw, handles, mark = serve(model_and_params, prompts, depth=depth)
    gw.drain(timeout=60)
    steps = records_of(engine.trace_id, mark)
    by_seq = {r["seq"]: r for r in steps}
    requests = {q["uid"]: q for q in tracing.snapshot()["requests"]
                if q["engine"] == engine.trace_id}
    assert sorted(requests) == sorted(h.uid for h in handles)
    for h in handles:
        q = requests[h.uid]
        assert q["status"] == "completed" and q["generated"] == 6
        assert q["prompt_len"] == len(h.prompt)
        assert q["submitted_ns"] <= q["admitted_ns"] <= q["first_scheduled_ns"] \
            < q["first_token_ns"] <= q["ended_ns"]
        assert h.queue_wait_s == pytest.approx((q["admitted_ns"] - q["submitted_ns"]) / 1e9)
        assert h.ttft_s == pytest.approx((q["first_token_ns"] - q["submitted_ns"]) / 1e9)
        # the links: a pump pass admitted it, a put first held it, a step gave its first token
        assert by_seq[q["admitted_seq"]]["kind"] == "pump"
        first, token = by_seq[q["first_scheduled_seq"]], by_seq[q["first_token_seq"]]
        assert first["kind"] == "put" and h.uid in first["uids"] and h.uid in token["uids"]
        assert first["start_ns"] <= token["start_ns"] and token["end_ns"] <= q["first_token_ns"]
        assert by_seq[first["caused_by"]]["kind"] == "pump"
        held = [r for r in steps if r["kind"] == "put" and h.uid in r["uids"]
                and r["seq"] <= q["first_token_seq"]]
        assert q["prefill_steps"] == len(held) >= -(-len(h.prompt) // 16)
        assert all(r["n_prompt_tokens"] > 0 for r in held)
    # every engine record of the run was caused by a pump pass that is in the ring
    pumps = {r["seq"] for r in steps if r["kind"] == "pump"}
    engine_records = [r for r in steps if r["kind"] != "pump"]
    assert engine_records and all(r["caused_by"] in pumps for r in engine_records)
    assert {r["kind"] for r in engine_records} >= {"put", "burst_async" if depth else "burst"}
    # a pump pass holds admit, then the scheduler's phases around the engine call, then deliver
    busy = [r for r in steps if r["kind"] == "pump" and "ds.sched.plan" in names(r)]
    assert busy and all(names(r)[0] == "ds.gateway.admit" and names(r)[-1] == "ds.gateway.deliver"
                        and ordered(r) for r in busy)
    # a step's tokens are handed over inside the next engine record, while its program runs
    # (a pipeline, which fences a burst late as it is, hands over as it accepts)
    counters = gw.snapshot()["counters"]
    flown, idle = counters["tokens_delivered_in_flight"], counters["tokens_delivered_idle"]
    assert flown + idle == counters["tokens_generated"] == 6 * len(handles)
    delivering = [r for r in engine_records if "ds.sched.deliver" in names(r)]
    if depth:
        assert delivering == [] and flown == 0
        return
    assert {r["kind"] for r in delivering} == {"put", "burst"} and flown > idle
    for r in delivering:
        at = {name: (enter, exit_) for name, enter, exit_ in r["phases"]}
        assert at["ds.engine.dispatch"][1] <= at["ds.sched.deliver"][0]
        assert at["ds.sched.deliver"][1] <= at["ds.engine.fetch"][0] and ordered(r)


def test_an_idle_gateway_writes_nothing_and_a_request_that_never_ran_has_no_step(model_and_params):
    engine = make_engine(model_and_params, n_seqs=8, batch=16)
    gw = ServingGateway(engine, config=ServingConfig(token_budget=16), auto_start=False)
    mark = last_seq()
    for _ in range(20):
        assert gw._pump_once() is False
    assert records_of(engine.trace_id, mark) == []
    handle = gw.submit(PROMPT, max_new_tokens=4)
    handle.cancel()
    gw._pump_once()
    (q,) = [q for q in tracing.snapshot()["requests"] if q["engine"] == engine.trace_id]
    assert q["status"] == "cancelled" and q["admitted_ns"] is None
    assert q["first_scheduled_ns"] is None and q["first_token_ns"] is None
    assert q["submitted_ns"] <= q["ended_ns"] and q["prefill_steps"] == 0
    gw.shutdown()


GAUGES = {"queue_depth", "queue_depth_peak", "running", "paused", "kv_free_blocks",
          "kv_occupancy"}
TOP = {"counters", "gauges", "external", "ttft", "token_latency", "queue_wait", "state"}


def test_the_snapshot_keeps_every_key_with_the_pulled_sources(model_and_params):
    engine = make_engine(model_and_params, spec=True, prefix=True, n_seqs=8, batch=16)
    mark = last_seq()
    gw = ServingGateway(engine, config=ServingConfig(token_budget=16))
    for p in (REPETITIVE, PROMPT, PROMPT[:4]):
        gw.submit(p, max_new_tokens=6).result(timeout=120)
    live = gw.snapshot()
    assert set(live) >= TOP and set(live["gauges"]) == GAUGES
    assert set(live["external"]) == {"Serve/PrefixCache", "Serve/Spec", "Serve/Engine"}
    assert set(live["external"]["Serve/Engine"]) == {"host_syncs", "tokens_emitted",
                                                     "syncs_per_token", "async_burst"}
    assert live["external"]["Serve/PrefixCache"] == engine.prefix_cache.stats()
    assert live["external"]["Serve/Engine"]["tokens_emitted"] == engine.tokens_emitted > 0
    assert live["gauges"]["running"] == 0 and live["gauges"]["queue_depth"] == 0
    assert set(live["counters"]) == set(gw.metrics.COUNTERS)
    # the new keys: the two spans after admission, and the step records' summary
    assert live["sched_wait"]["count"] == live["prefill_span"]["count"] == live["ttft"]["count"] == 3
    assert live["ttft"]["mean_ms"] == pytest.approx(
        live["queue_wait"]["mean_ms"] + live["sched_wait"]["mean_ms"]
        + live["prefill_span"]["mean_ms"], rel=1e-6)
    kinds = [r["kind"] for r in records_of(engine.trace_id, mark)]
    assert live["steps"]["counts"] == {k: kinds.count(k) for k in set(kinds)}
    assert live["steps"]["counts"]["put"] >= 3 and live["steps"]["mixed_step_ms_p50"] > 0
    tags = {tag for tag, _, _ in gw.metrics.events()}
    assert {"serving/gauge/kv_free_blocks", "Serve/Engine/host_syncs", "serving/ttft/p99_ms",
            "serving/sched_wait/p50_ms", "serving/prefill_span/p95_ms",
            "serving/steps/burst_k_mean", "serving/steps/count/put"} <= tags
    # nothing is pushed from the pump any more, and the values outlive the engine
    gw.drain(timeout=60)
    assert engine.kv_cache is None
    after = gw.snapshot()
    assert after["gauges"] == live["gauges"] and after["state"] == "stopped"
    assert after["external"]["Serve/Engine"] == live["external"]["Serve/Engine"]
    assert set(after["external"]) == set(live["external"])


def test_the_trainer_sets_up_in_two_records_and_its_state_is_built_inside_the_first_step():
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    from tests.unit.simple_model import SimpleModel
    groups.destroy_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config={
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2}, "mesh": {"data_parallel_size": 8}})
    x = np.random.RandomState(0).randn(16, 16).astype(np.float32)
    engine.train_batch(batch=(x, np.arange(16) % 16))
    built, state, step = records_of(engine.trace_id)      # in the order they ended
    assert [(r["kind"], r["program"]) for r in (built, state, step)] == [
        ("setup", "engine"), ("setup", "state"), ("train", "train_batch")]
    assert names(built) == ["ds.setup.partition", "ds.setup.optimizer", "ds.setup.services"]
    assert names(state) == ["ds.setup.params", "ds.setup.partition", "ds.setup.optimizer"]
    assert (built["caused_by"], state["caused_by"]) == (0, step["seq"])
    for record in (built, state):
        assert ordered(record) and record["process_age_ns"] > 0
        covered = record["phases"][-1][2] - record["phases"][0][1]
        assert covered >= 0.95 * (record["end_ns"] - record["start_ns"])
    assert step["phases"][0][1] <= state["start_ns"] <= state["end_ns"] <= step["phases"][0][2]
    # the state's programs are the state's row, the step's own program the step's
    rows = {(row["kind"], row["program"]): row for row in tracing.snapshot()["builds"]
            if row["engine"] == engine.trace_id}
    assert rows["setup", "state"]["compiles"] >= 2 and rows["train", "train_batch"]["compiles"] >= 1
    assert rows["setup", "state"]["seq"] == state["seq"]
    summary = tracing.setup_summary(engine.trace_id)
    assert summary["build"]["programs"] == 1 and summary["process_age_ns"] == built["process_age_ns"]
    assert summary["init_ns"] == sum(r["end_ns"] - r["start_ns"] for r in (built, state))
    engine.train_batch(batch=(x, np.arange(16) % 16))
    assert [r["kind"] for r in records_of(engine.trace_id)].count("setup") == 2


def test_the_snapshot_says_what_set_up_cost_in_counters_that_only_grow(model_and_params):
    from deepspeed_tpu.serving.metrics import SETUP_COUNTERS
    engine = make_engine(model_and_params, n_seqs=8, batch=16)
    gw = ServingGateway(engine, config=ServingConfig(token_budget=16))
    idle = gw.snapshot()
    assert idle["setup"]["build"]["programs"] == idle["counters"]["programs_built"] == 0
    gw.submit(PROMPT, max_new_tokens=6).result(timeout=120)
    live = gw.snapshot()
    setup, counters = live["setup"], live["counters"]
    assert setup == tracing.setup_summary(engine.trace_id) and setup["engine"] == engine.trace_id
    assert list(setup["phases_ns"]) == ["ds.setup.params", "ds.setup.pools", "ds.setup.kind",
                                        "ds.setup.programs"]
    assert set(SETUP_COUNTERS) == {
        "setup_init_ms", "setup_build_ms", "setup_build_trace_ms", "setup_outside_compile_ms",
        "programs_built", "compile_cache_hits", "compile_cache_misses"} <= set(counters)
    assert all(type(counters[name]) is int for name in SETUP_COUNTERS)
    assert counters["setup_init_ms"] == setup["init_ns"] // 1_000_000
    built = setup["build"]
    assert counters["setup_build_ms"] == (built["trace_ns"] + built["lower_ns"]
                                          + built["backend_ns"]) // 1_000_000
    assert counters["setup_build_ms"] > counters["setup_build_trace_ms"] > 0
    assert counters["programs_built"] == built["programs"] >= 2       # a prompt's and a decode's
    programs = {row["program"] for row in tracing.snapshot()["builds"]
                if row["engine"] == engine.trace_id and row["kind"] not in ("setup", "pump")}
    assert len(programs) == built["programs"]
    assert {f"serving/count/{name}" for name in SETUP_COUNTERS} <= {
        tag for tag, _, _ in gw.metrics.events()}
    # the stall rule and the steps' summary pass the constructor's record by
    assert "setup" not in live["steps"]["counts"] and counters["stalls"] == 0
    gw.drain(timeout=60)
    after = gw.snapshot()["counters"]
    assert all(after[name] >= counters[name] for name in SETUP_COUNTERS)


# ------------------------------------------------------------------------ stalls
class SkippingClock:
    """``tracing.now_ns`` with seconds that can be skipped: a delay on the
    records' clock that costs the test no sleep and the thread no CPU."""

    def __init__(self, monkeypatch):
        self.skipped = 0
        real = tracing.now_ns
        monkeypatch.setattr(tracing, "now_ns", lambda: real() + self.skipped)

    def skip(self, ms):
        self.skipped += int(ms * 1e6)


def stall_gateway(model_and_params, **config):
    """Every step a ``put``: after its prefill a request runs one program
    (``8``) again and again, so the ninth decode step has eight before it."""
    engine = make_engine(model_and_params, n_seqs=8, batch=16)
    return engine, ServingGateway(engine, config=ServingConfig(token_budget=16, max_burst=1, **config),
                                  auto_start=False)


def pump_until(gw, done, limit=500):
    for _ in range(limit):
        if done():
            return
        gw._pump_once()
    raise AssertionError("the pump did not get there")


def stalls_of(engine):
    mine = {r.seq for r in tuple(tracing.RECORDER.steps) if r.engine == engine.trace_id}
    return [e for e in tracing.snapshot()["events"] if e["kind"] == "stall" and e["seq"] in mine]


# where a delay lies -> (the stall's ``where``, the phase that held most of it)
DELAYS = {"inside": ("inside", "ds.engine.fetch"), "between": ("between", "ds.sched.accept"),
          "deliver": ("inside", "ds.sched.deliver")}


@pytest.mark.parametrize("delay", list(DELAYS))
def test_a_delay_after_eight_ordinary_steps_is_a_stall_with_its_place_named(
        model_and_params, monkeypatch, delay):
    where, held = DELAYS[delay]
    clock = SkippingClock(monkeypatch)
    engine, gw = stall_gateway(model_and_params)
    handle = gw.submit(PROMPT, max_new_tokens=24)
    armed = {"at": None}

    def skip_once():
        if armed["at"] == len(handle._collected):
            armed["at"] = None
            clock.skip(400)
    if delay == "inside":          # 0.4 s pass inside one put's wait for the device
        real_phase = tracing.phase

        @contextlib.contextmanager
        def phase(name):
            with real_phase(name):
                if name == "engine.fetch":
                    skip_once()
                yield
        monkeypatch.setattr(tracing, "phase", phase)
    elif delay == "between":       # ... or between two puts, where a step's token is accepted
        accept = gw.scheduler._accept_token

        def accept_token(r, tok, **kwargs):
            skip_once()
            accept(r, tok, **kwargs)
        gw.scheduler._accept_token = accept_token
    else:                          # ... or in its delivery, which the next put's record holds
        deliver = gw.scheduler.on_tokens

        def on_tokens(rows, in_flight):
            skip_once()
            deliver(rows, in_flight)
        gw.scheduler.on_tokens = on_tokens
    # the warm-up's steps: a delay there (as the compiles themselves are) is no stall
    armed["at"] = 3
    pump_until(gw, lambda: len(handle._collected) >= 10)
    assert armed["at"] is None and gw.snapshot()["counters"]["stalls"] == 0
    assert stalls_of(engine) == []
    armed["at"] = 12
    warned = []
    monkeypatch.setattr(gateway_logger, "warning", warned.append)
    pump_until(gw, lambda: handle.done)
    counters = gw.snapshot()["counters"]
    assert counters["stalls"] == 1 and abs(counters["stalled_ms"] - 400) < 100
    (stall,) = stalls_of(engine)
    record = next(r for r in tracing.snapshot()["steps"] if r["seq"] == stall["seq"])
    assert (stall["record_kind"], stall["program"], stall["n_seqs"], stall["n_tokens"]) == \
        (record["kind"], record["program"], 1, 1) == ("put", "8", 1, 1)
    assert stall["where"] == where and stall["phase"] == held
    assert abs(stall["excess_ms"] - 400) < 100 and 0 < stall["expected_ms"] < 100
    assert stall["end_ns"] == record["end_ns"] and stall["end_ns"] - stall["start_ns"] >= 400e6
    # the pump thread did not compute through it, nothing was compiled or collected for long
    assert stall["cpu_ms"] < 100 and stall["compiles"] == 0 and stall["waited_ms"] == 0
    assert stall["gc_ms"] < 100 and stall["compile_ms"] == 0
    (line,) = warned
    assert f"{where} put record {stall['seq']}" in line and stall["phase"] in line
    assert "program 8" in line and "pump thread cpu" in line
    gw.shutdown()


def test_a_gateway_without_requests_waits_and_that_is_no_stall(model_and_params, monkeypatch):
    clock = SkippingClock(monkeypatch)
    engine = make_engine(model_and_params, n_seqs=8, batch=16)
    gw = ServingGateway(engine, config=ServingConfig(token_budget=16, max_burst=1))
    gw.submit(PROMPT, max_new_tokens=16).result(timeout=120)     # program 8, fifteen times
    mark = last_seq()
    clock.skip(500)                                              # half a second with nothing to do
    gw.submit(PROMPT[:1], max_new_tokens=3).result(timeout=120)  # its first step is program 8 too
    assert gw.snapshot()["counters"]["stalls"] == 0 and stalls_of(engine) == []
    first, *rest = [r for r in records_of(engine.trace_id, mark) if r["kind"] == "pump"]
    assert first["waited_ns"] >= 500e6 and first["idle_passes"] >= 1
    assert all(r["waited_ns"] == 0 and r["idle_passes"] == 0 for r in rest)
    served = [r for r in records_of(engine.trace_id) if r["kind"] == "put"]
    assert sum(r["program"] == "8" for r in served) >= 16
    gw.drain(timeout=60)
