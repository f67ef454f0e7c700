"""When a step's tokens reach their streams (scheduler.hand_over →
ServingGateway._on_tokens): while the NEXT step's program runs, as one
batch - or, where no dispatch follows, before whatever stops it from
coming. What frees room is not deferred: a finished request is retired
in the pass that accepted its last token.

Engine-agnostic, so these run on fake engines: ``RunningEngine`` runs
``while_running`` between a program's "dispatch" and its "fetch" as
``InferenceEngineV2`` does; the plain ``FakeEngine`` never looks at it,
so the scheduler hands over itself when the engine call returns (the
fallback, which is also the parent's order: a hand-over with no program
on the device)."""

import time
import types

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import DynamicSplitFuseScheduler
from deepspeed_tpu.serving import (DeadlineExceededError, GatewayClosedError,
                                   GatewayFailedError, RequestCancelledError)
from unit.inference.serving.test_admission import FakeEngine, make_gateway, pump_until


class RunningEngine(FakeEngine):
    """FakeEngine that also decodes in bursts and verifies drafts, with the
    same token arithmetic (a stream does not depend on the path), and runs
    ``while_running`` while its "program runs". ``calls``: what ran, in
    order, with ``"work"`` where the hook ran."""
    while_running = None
    honours = True

    def __init__(self, drafts=0, **kwargs):
        kwargs.setdefault("max_ctx_tokens", 256)
        kwargs.setdefault("free_blocks", 256)
        super().__init__(**kwargs)
        self.calls = []
        self.n_drafts = drafts
        # truthy only where drafts are asked for: the scheduler tries a verify burst first
        self.spec = types.SimpleNamespace(stats=dict) if drafts else None

    def _token(self, uid):
        return (uid * 7 + self._seen[uid]) % 97

    def _ran(self, kind):
        self.calls.append(kind)
        if self.honours and self.while_running is not None:
            self.calls.append("work")
            self.while_running()

    def put(self, uids, chunks, sample=None):
        out = super().put(uids, chunks, sample=sample)
        self._ran("put")
        return out

    def can_burst(self, uids, k):
        return True

    def decode_burst(self, uids, entry, k, sample=None):
        toks = np.zeros((k, len(uids)), np.int32)
        for step in range(k):
            for j, uid in enumerate(uids):
                self._seen[uid] += 1
                toks[step, j] = self._token(uid)
        self._ran("burst")
        return toks

    def _next(self, uid, n):
        return [(uid * 7 + self._seen[uid] + i + 1) % 97 for i in range(n)]

    def propose_drafts(self, uids, entries, max_lens):
        # the first draft is right, the second wrong: one accepted, one bonus token a row
        return [[t if i == 0 else (t + 1) % 97 for i, t in
                 enumerate(self._next(uid, min(cap, self.n_drafts)))]
                for uid, cap in zip(uids, max_lens)]

    def verify_burst(self, uids, entry, drafts, sample=None):
        d = max(len(dr) for dr in drafts)
        toks, acc = np.zeros((len(uids), d + 1), np.int32), np.zeros(len(uids), np.int64)
        for j, (uid, dr) in enumerate(zip(uids, drafts)):
            true = self._next(uid, d + 1)
            a = 0
            while a < len(dr) and dr[a] == true[a]:
                a += 1
            acc[j], toks[j] = a, true
            self._seen[uid] += a + 1
        self._ran("verify")
        return toks, acc

    def rewind(self, uid, n):
        self._seen[uid] -= n


class DeafEngine(RunningEngine):
    """The same engine, never running ``while_running``."""
    honours = False


class PipelinedEngine(RunningEngine):
    """The same engine at ``async_burst.depth`` 1: a burst's tokens are
    accepted a burst late, or when whoever needs a request's final state
    (cancel, pause) drains the pipeline."""
    async_burst_depth = 1

    def decode_burst_async(self, uids, entry, k, sample=None, prev=None):
        toks = self.decode_burst(uids, entry, k, sample=sample)
        return types.SimpleNamespace(fetch=lambda: toks)


PATHS = {"stepwise": dict(max_burst=1), "burst": dict(max_burst=4),
         "speculative": dict(max_burst=1, drafts=2)}
REQUESTS = [([1, 2, 3], 9), ([4, 5], 6), ([6, 7, 8, 9], 11), ([3], 1)]


def gateway_on(engine_cls, path, **cfg):
    opts = dict(PATHS[path])
    engine = engine_cls(drafts=opts.pop("drafts", 0))
    gw = make_gateway(engine, **opts, **cfg)
    batches = []
    deliver = gw.scheduler.on_tokens

    def on_tokens(rows, in_flight):
        batches.append((list(rows), in_flight))
        deliver(rows, in_flight)
    gw.scheduler.on_tokens = on_tokens
    return engine, gw, batches


def serve(engine_cls, path):
    engine, gw, batches = gateway_on(engine_cls, path)
    handles = [gw.submit(prompt, max_new_tokens=n) for prompt, n in REQUESTS]
    pump_until(gw, lambda: all(h.done for h in handles))
    return engine, gw, batches, handles


@pytest.mark.parametrize("engine_cls", [RunningEngine, DeafEngine], ids=["in_flight", "fallback"])
@pytest.mark.parametrize("path", list(PATHS))
def test_rows_reach_a_stream_in_order_and_its_done_row_last(path, engine_cls):
    engine, gw, batches, handles = serve(engine_cls, path)
    assert {"burst": "burst", "speculative": "verify"}.get(path, "put") in engine.calls
    for h, (prompt, n) in zip(handles, REQUESTS):
        want = FakeEngine.expected_tokens(h.uid, len(prompt), n)
        assert h.result(timeout=1) == list(h.tokens(timeout=1)) == want
        rows = [(tok, done) for batch, _ in batches for uid, tok, done in batch if uid == h.uid]
        assert [tok for tok, _ in rows] == want
        assert [done for _, done in rows] == [False] * (n - 1) + [True]
        assert h.status == "completed" and h.first_token_ns <= h.last_token_ns
    counters = gw.snapshot()["counters"]
    total = sum(n for _, n in REQUESTS)
    in_flight, idle = counters["tokens_delivered_in_flight"], counters["tokens_delivered_idle"]
    assert in_flight + idle == counters["tokens_generated"] == total
    assert sum(len(batch) for batch, flying in batches if flying) == in_flight
    if engine_cls is DeafEngine:
        assert in_flight == 0       # an engine that ignores the callable loses nothing
    else:                           # all but what the last step accepted rode a dispatch
        assert idle == len(batches[-1][0]) and not batches[-1][1] and in_flight > idle
        assert all(flying for _, flying in batches[:-1])
    gw.shutdown()


@pytest.mark.parametrize("path", list(PATHS))
def test_when_a_token_is_seen_changes_never_which(path):
    """The same requests through the hand-over in flight and through the
    scheduler's own (no program on the device, as before this hook was a
    batch): the same streams, token for token, and the same batches."""
    flown = serve(RunningEngine, path)
    fallen = serve(DeafEngine, path)
    assert [h._collected for h in flown[3]] == [h._collected for h in fallen[3]]
    assert [batch for batch, _ in flown[2]] == [batch for batch, _ in fallen[2]]
    assert flown[0].calls != fallen[0].calls and "work" not in fallen[0].calls
    for _, gw, _, _ in (flown, fallen):
        gw.shutdown()


def test_a_token_is_in_its_stream_after_the_next_dispatch_and_never_later():
    engine, gw, batches = gateway_on(RunningEngine, "stepwise")
    h = gw.submit([1, 2, 3], max_new_tokens=4)
    gw._pump_once()                          # pass 1: the prompt, the first token accepted
    assert len(gw.scheduler.requests[h.uid].generated) == 1
    assert h._collected == [] and h.first_token_ns is None
    for seen in (1, 2, 3):                   # passes 2-4: each dispatch carries the pass before's
        gw._pump_once()
        assert len(h._collected) == seen
        assert engine.calls[-2:] == ["put", "work"] and batches[-1][1]
    assert not h.done and gw.inflight()["active"] == 0      # retired; its last token on the way
    gw._pump_once()                          # pass 5 dispatches nothing: handed over there
    assert h.done and len(h._collected) == 4 and not batches[-1][1]
    assert engine.calls.count("put") == 4
    counters = gw.snapshot()["counters"]
    assert (counters["tokens_delivered_in_flight"], counters["tokens_delivered_idle"]) == (3, 1)
    gw.shutdown()


def test_a_step_that_dispatches_nothing_hands_over_first():
    """Every request paused: the scheduler's step plans nothing, and what
    the last step accepted does not wait for a dispatch that never comes."""
    engine, batches = RunningEngine(), []
    sched = DynamicSplitFuseScheduler(engine, max_burst=1,
                                      on_tokens=lambda rows, flying: batches.append((rows, flying)))
    sched.add_request(5, [1, 2, 3], max_new_tokens=8)
    assert sched.step() == [5] and sched.step() == [5]
    assert [len(rows) for rows, _ in batches] == [1] and batches[0][1]     # the first step's rode the second
    sched.requests[5].paused = True
    assert sched.step() == [] and engine.calls.count("put") == 2
    assert [(len(rows), flying) for rows, flying in batches] == [(1, True), (1, False)]
    assert [tok for rows, _ in batches for _, tok, _ in rows] == sched.requests[5].generated
    assert sched.hand_over() == 0


def test_a_scheduler_without_a_hook_keeps_nothing_and_leaves_no_callable():
    engine = RunningEngine()
    DynamicSplitFuseScheduler(engine, on_tokens=lambda rows, flying: None)
    assert engine.while_running is not None
    sched = DynamicSplitFuseScheduler(engine, max_burst=1)      # e.g. run_to_completion
    assert engine.while_running is None     # not the scheduler before's
    sched.add_request(1, [1, 2], max_new_tokens=3)
    while sched.step():
        pass
    assert sched.requests[1].done and len(sched.requests[1].generated) == 3
    assert sched.ended == [] and sched._rows == [] and "work" not in engine.calls


def end_by(how, gw, h, monkeypatch):
    """→ (the error the handle ends with, or None for a clean end)."""
    if how == "cancel":
        h.cancel()
        gw._pump_once()
        return RequestCancelledError
    if how == "deadline":
        h.deadline = time.perf_counter() - 1.0
        gw._pump_once()
        return DeadlineExceededError
    if how == "pause":              # a higher priority takes its place: suspended, not ended
        gw.scheduler.pause(h.uid)
        return "paused"
    if how == "drain":
        gw.drain(timeout=10)
        return None
    if how == "shutdown":
        gw.shutdown()
        return GatewayClosedError
    assert how == "pump_failure"

    def put(uids, chunks, sample=None):
        raise RuntimeError("the device fell over")
    monkeypatch.setattr(gw.engine, "put", put)
    with pytest.raises(RuntimeError, match="fell over"):
        gw._pump_once()
    gw._fail_outstanding(GatewayFailedError("serving pump died"))   # what _run does with it
    return GatewayFailedError


@pytest.mark.parametrize("how", ["cancel", "deadline", "pause", "drain", "shutdown",
                                 "pump_failure"])
def test_what_was_pending_is_delivered_before_the_handle_ends(how, monkeypatch):
    engine, gw, batches = gateway_on(RunningEngine, "stepwise")
    h = gw.submit([1, 2, 3], max_new_tokens=8)
    pump_until(gw, lambda: len(h._collected) >= 3)
    request = gw.scheduler.requests[h.uid]
    assert len(request.generated) == len(h._collected) + 1     # one row waits for a dispatch
    error = end_by(how, gw, h, monkeypatch)
    generated = list(request.generated)
    want = FakeEngine.expected_tokens(h.uid, 3, 8)
    if how == "pause":
        assert not h.done and h._collected == generated == want[:len(generated)]
        gw.shutdown()
        return
    assert h.done and h._collected == generated == want[:len(generated)]
    stream = h.tokens(timeout=1)
    if error is None:
        assert list(stream) == want and h.status == "completed"
    else:
        got = []
        with pytest.raises(error) as raised:    # every token first, then the typed end
            for tok in stream:
                got.append(tok)
        assert got == generated and len(got) >= 4
        if how in ("cancel", "deadline"):       # the message counts what the stream holds
            assert f"{len(got)} tokens" in str(raised.value)
    counters = gw.snapshot()["counters"]
    assert counters["tokens_delivered_in_flight"] + counters["tokens_delivered_idle"] \
        == counters["tokens_generated"] == len(h._collected)
    if how != "drain":
        gw.shutdown()


@pytest.mark.parametrize("how", ["cancel", "deadline", "preempt"])
def test_a_request_that_finishes_inside_the_drain_completes_and_the_pump_lives(how):
    """At depth 1 the burst in flight holds a request's last tokens. The
    drain inside ``scheduler.cancel`` / ``pause`` accepts them, the hand-over
    retires and ends the request - and whoever asked for the drain finds it
    gone, not a request to retire a second time. The other request lives on."""
    engine, gw, batches = gateway_on(PipelinedEngine, "burst", allow_preemption=True)
    h = gw.submit([1, 2, 3], max_new_tokens=5, priority=0)
    other = gw.submit([4, 5], max_new_tokens=40, priority=1)
    pump_until(gw, lambda: gw.scheduler._pipeline)          # a burst of 4 in flight
    assert len(h._collected) == 1 and gw.scheduler.requests[h.uid]._inflight == 4
    if how == "cancel":
        h.cancel()
    elif how == "deadline":
        h.deadline = time.perf_counter() - 1.0
    else:       # a full gate, and a higher priority picks h, the lowest, to suspend
        gw.gate.max_tracked = 2
        urgent = gw.submit([7], max_new_tokens=2, priority=2)
    gw._pump_once()
    assert h.done and h.status == "completed"
    assert h.result(timeout=1) == list(h.tokens(timeout=1)) == FakeEngine.expected_tokens(h.uid, 3, 5)
    assert h.uid not in gw._active and h.uid not in gw.scheduler.requests and gw._ending == {}
    assert gw._paused == [] and gw.gate.active == 1         # its room given back once
    pump_until(gw, lambda: other.done)                      # the pump did not die
    assert other.result(timeout=1) == FakeEngine.expected_tokens(other.uid, 2, 40)
    if how == "preempt":
        assert urgent.result(timeout=1) == FakeEngine.expected_tokens(urgent.uid, 1, 2)
    counters = gw.snapshot()["counters"]
    assert counters["completed"] == (3 if how == "preempt" else 2)
    assert counters["cancelled"] == counters["deadline_expired"] == counters["preemptions"] == 0
    assert counters["tokens_delivered_in_flight"] + counters["tokens_delivered_idle"] \
        == counters["tokens_generated"] == 45 + 2 * (how == "preempt")
    # nothing held once every request has ended: no place, no prompt awaited, no worst case
    assert gw.gate.active == 0 and gw.gate.committed_blocks == 0 == gw.gate.committed_worst
    gw.shutdown()


def test_a_done_row_releases_the_gate_in_the_pass_that_accepted_it():
    """Room for one request at a time: the one queued behind it is admitted
    in the very next pass, before the first's last token is handed over -
    it rides that pass's dispatch."""
    engine, gw, batches = gateway_on(RunningEngine, "stepwise", max_queue_depth=4)
    gw.gate.max_tracked = 1
    first = gw.submit([1, 2, 3], max_new_tokens=3)
    second = gw.submit([4, 5], max_new_tokens=2)
    pump_until(gw, lambda: first.uid not in gw._active and first.admitted_ns is not None)
    assert not first.done and len(first._collected) == 2        # its done row still waits
    assert second.admitted_ns is None and gw.gate.active == 0   # ... and its room is free
    assert gw.snapshot()["counters"]["completed"] == 0
    gw._pump_once()
    assert second.admitted_ns is not None and second.uid in gw._active
    assert first.done and first.result(timeout=1) == FakeEngine.expected_tokens(first.uid, 3, 3)
    assert batches[-1][1]                   # handed over while second's prompt ran
    pump_until(gw, lambda: second.done)
    assert second.result(timeout=1) == FakeEngine.expected_tokens(second.uid, 2, 2)
    gw.shutdown()


def test_a_hook_that_raises_reaches_the_crash_path_and_fails_every_handle():
    engine, gw, batches = gateway_on(RunningEngine, "stepwise")
    h = gw.submit([1, 2, 3], max_new_tokens=8)
    pump_until(gw, lambda: len(h._collected) >= 2)

    def on_tokens(rows, in_flight):
        raise RuntimeError("a client's queue broke")
    gw.scheduler.on_tokens = on_tokens
    with pytest.raises(RuntimeError, match="queue broke"):
        gw._pump_once()
    gw._fail_outstanding(GatewayFailedError("serving pump died"))
    assert h.done and h.status == "failed"
    with pytest.raises(GatewayFailedError):
        list(h.tokens(timeout=1))
    assert gw._active == {} and gw._ending == {} and gw.scheduler._rows == []
