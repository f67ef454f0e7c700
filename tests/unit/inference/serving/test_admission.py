"""Serving gateway: admission control, policies, lifecycle, metrics.

The client-facing contracts — typed rejection, shed/block/reject
policies, deadlines, cancellation, crash-safety — are engine-agnostic,
so these run against a deterministic in-process :class:`FakeEngine`
(the exact surface the gateway + scheduler touch, zero device work).
Real-engine integration (streams, preemption, drain) lives in
``test_gateway.py``.
"""

import threading
import time
import types

import numpy as np
import pytest

from deepspeed_tpu.serving import (CapacityGate, DeadlineExceededError,
                                   GatewayClosedError, GatewayFailedError,
                                   QueueFullError, RequestCancelledError,
                                   RequestShedError, RequestTooLargeError,
                                   ServingConfig, ServingGateway, ServingMetrics,
                                   get_serving_config)


class FakeEngine:
    """InferenceEngineV2 stand-in: real bookkeeping surface (put/query/
    flush/suspend/resume/destroy), deterministic token arithmetic. Its pool
    is kept like the engine's: a sequence holds ``ceil(seen / block_size)``
    of ``free_blocks`` blocks, and a ``put`` that asks for more than are
    free raises the engine's error."""

    def __init__(self, max_tokens=64, max_seqs=8, block_size=8,
                 max_ctx_tokens=64, free_blocks=16, max_tracked=8):
        self.max_tokens = max_tokens
        self.max_seqs = max_seqs
        self.block_size = block_size
        self.max_ctx_tokens = max_ctx_tokens
        self.total_blocks = free_blocks
        self.state_manager = types.SimpleNamespace(
            max_tracked_sequences=max_tracked)
        self._seen = {}       # uid -> tokens ingested
        self._suspended = {}  # uid -> seen_tokens at suspend
        self.destroyed = False

    @staticmethod
    def expected_tokens(uid, prompt_len, n):
        """The deterministic stream ``put`` produces for a request."""
        return [(uid * 7 + prompt_len + i) % 97 for i in range(n)]

    def _blocks(self, seen):
        return -(-seen // self.block_size)

    @property
    def free_blocks(self):
        return self.total_blocks - sum(map(self._blocks, self._seen.values()))

    def put(self, uids, chunks, sample=None):
        need = sum(self._blocks(self._seen.get(uid, 0) + len(toks))
                   - self._blocks(self._seen.get(uid, 0)) for uid, toks in zip(uids, chunks))
        if need > self.free_blocks:
            raise RuntimeError(f"KV pool exhausted: need {need} blocks, "
                               f"{self.free_blocks} reclaimable")
        out = []
        for uid, toks in zip(uids, chunks):
            self._seen[uid] = self._seen.get(uid, 0) + len(toks)
            out.append((uid * 7 + self._seen[uid]) % 97)
        return np.asarray(out, np.int32)

    def query(self, uid):
        if uid not in self._seen:
            return None
        seen = self._seen[uid]
        return seen, self._blocks(seen) * self.block_size - seen

    def flush(self, uid):
        suspended = self._suspended.pop(uid, None) is not None
        if uid in self._seen:
            del self._seen[uid]
        elif not suspended:
            raise KeyError(uid)

    def suspend(self, uid):
        self._suspended[uid] = self._seen.pop(uid)

    def is_suspended(self, uid):
        return uid in self._suspended

    def suspended_blocks(self, uid):
        return -(-self._suspended[uid] // self.block_size)

    def resume(self, uid):
        self._seen[uid] = self._suspended.pop(uid)

    def can_burst(self, uids, k):
        return False

    def destroy(self):
        self.destroyed = True


def make_gateway(engine=None, auto_start=False, **cfg):
    cfg.setdefault("max_burst", 1)
    return ServingGateway(engine or FakeEngine(),
                          config=ServingConfig(**cfg), auto_start=auto_start)


def pump_until(gw, cond, n=200):
    for _ in range(n):
        if cond():
            return
        gw._pump_once()
        time.sleep(0.001)  # let client threads run between iterations
    raise AssertionError(f"condition not reached in {n} pump iterations")


class TestCapacityGate:

    def test_footprint_and_commit_accounting(self):
        """A request commits its prompt's blocks, then what it holds: the
        gate reads the engine's count less the prompt blocks not laid yet."""
        engine = FakeEngine(block_size=8, free_blocks=8)
        gate = CapacityGate(engine, 8)                     # a reserve of 1 block + the burst's
        assert gate.footprint(8, 8) == 2 and gate.footprint(9, 8) == 3
        assert gate.prompt_blocks(7) == 1 and gate.prompt_blocks(8) == 2
        assert gate.reserve(3) == 1 + 1 and CapacityGate(engine, 64, max_burst=16).reserve(3) == 8 + 6
        assert gate.try_commit(0, 15, 40) and gate.committed_blocks == 2     # worst case 7
        assert gate.headroom() == 6 and gate.active == 1
        engine.put([0], [list(range(10))])                 # two blocks laid: the engine counts them
        assert engine.free_blocks == 6 and gate.headroom() == 6 and gate.committed_blocks == 0
        assert gate.try_commit(1, 15, 40) and gate.committed_blocks == 2     # 14 > 8 at the worst
        assert gate.try_commit(2, 7, 40) and gate.headroom() == 3
        assert not gate.try_commit(3, 8, 40)               # 3 - 2 is under the reserve of four live
        assert gate.refused_by == {"kv_blocks": 1, "window_blocks": 0, "sequences": 0}
        gate.release(1)
        assert gate.try_commit(3, 8, 40)
        for uid in (0, 2, 3):
            gate.release(uid)
        assert gate.active == 0 and gate.committed_blocks == 0 and gate.committed_worst == 0
        assert gate.headroom() == engine.free_blocks == 6

    def test_worst_cases_that_fit_and_a_first_request_meet_no_reserve(self):
        gate = CapacityGate(FakeEngine(block_size=8, free_blocks=4), 64, max_burst=16)
        assert gate.reserve(1) == 8 + 2                    # more than the pool
        assert gate.try_commit(0, 8, 8) and gate.try_commit(1, 8, 8)         # 2 + 2 of 4
        assert not gate.try_commit(2, 1, 1)                # beyond the worst cases: the reserve
        gate.release(0), gate.release(1)
        assert gate.try_commit(2, 20, 12)                  # alone: check_feasible let it in

    def test_max_tracked_bounds_admission(self):
        gate = CapacityGate(FakeEngine(free_blocks=100, max_tracked=1), 64)
        assert gate.try_commit(0, 1, 1)
        assert not gate.try_commit(1, 1, 1)  # blocks free, but tracking full

    def test_feasibility_errors_are_actionable(self):
        gate = CapacityGate(FakeEngine(max_ctx_tokens=64, free_blocks=4), 64)
        with pytest.raises(RequestTooLargeError, match="empty prompt"):
            gate.check_feasible(0, 8)
        with pytest.raises(RequestTooLargeError, match="context window"):
            gate.check_feasible(60, 8)
        with pytest.raises(RequestTooLargeError, match="KV blocks"):
            gate.check_feasible(32, 16)  # 6 blocks > 4 in the pool


class TestAdmissionPolicies:

    def test_too_large_rejected_at_submit(self):
        gw = make_gateway()
        with pytest.raises(RequestTooLargeError):
            gw.submit(list(range(60)), max_new_tokens=8)
        assert gw.snapshot()["counters"]["rejected_too_large"] == 1

    def test_reject_policy_queue_full(self):
        gw = make_gateway(max_queue_depth=2)
        gw.submit([1, 2])
        gw.submit([3, 4])
        with pytest.raises(QueueFullError, match="max_queue_depth"):
            gw.submit([5, 6])
        assert gw.snapshot()["counters"]["rejected_queue_full"] == 1

    def test_shed_policy_evicts_lowest_priority(self):
        gw = make_gateway(max_queue_depth=2, admission_policy="shed")
        h_old = gw.submit([1, 2], priority=0)
        h_young = gw.submit([3, 4], priority=0)
        h_hi = gw.submit([5, 6], priority=5)  # sheds the YOUNGEST prio-0
        assert h_young.status == "shed" and h_old.status == "queued"
        with pytest.raises(RequestShedError):
            h_young.result(timeout=1)
        # no strictly-lower-priority victim left -> typed rejection
        with pytest.raises(QueueFullError):
            gw.submit([7, 8], priority=0)
        snap = gw.snapshot()["counters"]
        assert snap["shed"] == 1 and snap["rejected_queue_full"] == 1
        assert not h_hi.done

    def test_block_policy_times_out(self):
        gw = make_gateway(max_queue_depth=1, admission_policy="block",
                          block_timeout_s=0.15)
        gw.submit([1, 2])
        t0 = time.monotonic()
        with pytest.raises(QueueFullError, match="policy=block"):
            gw.submit([3, 4])
        assert time.monotonic() - t0 >= 0.13

    def test_block_policy_unblocks_on_admission(self):
        gw = make_gateway(max_queue_depth=1, admission_policy="block",
                          block_timeout_s=10.0)
        h1 = gw.submit([1, 2], max_new_tokens=2)
        handles = {}

        def second_client():
            handles["h2"] = gw.submit([3, 4], max_new_tokens=2)

        t = threading.Thread(target=second_client)
        t.start()
        time.sleep(0.05)  # let it reach the blocking wait
        assert t.is_alive()  # parked on the full queue
        pump_until(gw, lambda: not t.is_alive())  # admitting h1 makes room
        t.join(timeout=5)
        pump_until(gw, lambda: h1.done and handles["h2"].done)
        assert h1.status == handles["h2"].status == "completed"

    def test_deadline_expires_in_queue(self):
        gw = make_gateway()
        h = gw.submit([1, 2], deadline_ms=10)
        time.sleep(0.03)
        gw._pump_once()  # deadlines are processed before admission
        assert h.status == "deadline"
        with pytest.raises(DeadlineExceededError):
            h.result(timeout=1)
        assert gw.snapshot()["counters"]["deadline_expired"] == 1


class TestLifecycle:

    def test_fake_engine_end_to_end_streams(self):
        engine = FakeEngine()
        gw = make_gateway(engine, auto_start=True)
        handles = [gw.submit([10 + i] * (4 + i), max_new_tokens=3 + i)
                   for i in range(5)]
        for i, h in enumerate(handles):
            assert h.result(timeout=10) == FakeEngine.expected_tokens(
                h.uid, 4 + i, 3 + i)
            assert h.ttft_s is not None and h.ttft_s >= 0
        assert gw.gate.committed_blocks == 0 and gw.gate.active == 0
        snap = gw.snapshot()
        assert snap["counters"]["completed"] == 5
        assert snap["counters"]["tokens_generated"] == sum(3 + i
                                                           for i in range(5))
        gw.drain(timeout=10)
        assert engine.destroyed and gw.state == "stopped"

    def test_cancel_queued_and_running(self):
        gw = make_gateway()
        h_q = gw.submit([1, 2], max_new_tokens=4)
        h_run = gw.submit([3, 4], max_new_tokens=16)
        h_q.cancel()
        gw._pump_once()
        assert h_q.status == "cancelled"
        with pytest.raises(RequestCancelledError):
            h_q.result(timeout=1)
        pump_until(gw, lambda: len(h_run._collected) >= 2)
        h_run.cancel()
        gw._pump_once()
        assert h_run.status == "cancelled"
        assert 2 <= len(h_run._collected) < 16  # partial stream preserved
        assert gw.gate.committed_blocks == 0  # both released
        assert gw.snapshot()["counters"]["cancelled"] == 2

    def test_submit_after_drain_raises(self):
        engine = FakeEngine()
        gw = make_gateway(engine)
        gw.drain(timeout=5)
        assert engine.destroyed
        with pytest.raises(GatewayClosedError):
            gw.submit([1, 2])

    def test_pump_crash_fails_outstanding_handles(self):
        engine = FakeEngine()

        def boom(uids, chunks, sample=None):
            raise RuntimeError("synthetic engine fault")

        engine.put = boom
        gw = make_gateway(engine, auto_start=True)
        h = gw.submit([1, 2], max_new_tokens=4)
        with pytest.raises(GatewayFailedError, match="synthetic engine fault"):
            h.result(timeout=10)
        assert gw.state == "failed"
        with pytest.raises(GatewayFailedError):
            gw.submit([3, 4])
        assert gw.snapshot()["counters"]["failed"] == 1

    def test_shutdown_fails_inflight(self):
        engine = FakeEngine()
        gw = make_gateway(engine)
        h = gw.submit([1, 2], max_new_tokens=4)
        gw.shutdown()
        assert engine.destroyed and gw.state == "stopped"
        with pytest.raises(GatewayClosedError):
            h.result(timeout=1)


class TestErrorTaxonomy:
    """Machine-readable rejection contract: every ServingError carries a
    stable ``reason`` + ``retry_elsewhere`` routing verdict, and the
    capacity/queue raise sites attach numeric hints — what the fleet
    router consumes instead of string-matching messages."""

    def test_reason_and_retry_elsewhere_matrix(self):
        from deepspeed_tpu.serving import ServingError
        matrix = {
            GatewayClosedError: ("gateway_closed", True),
            QueueFullError: ("queue_full", True),
            RequestTooLargeError: ("too_large", False),
            RequestShedError: ("shed", True),
            RequestCancelledError: ("cancelled", False),
            DeadlineExceededError: ("deadline", False),
            GatewayFailedError: ("gateway_failed", True),
        }
        for cls, (reason, retry) in matrix.items():
            err = cls("x")
            assert isinstance(err, ServingError)
            assert err.reason == reason, cls.__name__
            assert err.retry_elsewhere is retry, cls.__name__
            assert err.details == {}
        assert ServingError("x", depth=3).details == {"depth": 3}

    def test_queue_full_carries_wait_hints_through_submit(self):
        gw = make_gateway(max_queue_depth=2)
        gw.submit([1, 2])
        gw.submit([3, 4])
        with pytest.raises(QueueFullError) as ei:
            gw.submit([5, 6])
        d = ei.value.details
        assert d["queue_depth"] == 2 and d["policy"] == "reject"
        assert d["evictable_blocks"] == 0  # FakeEngine has no prefix cache
        assert d["active"] == 0            # nothing admitted yet
        assert d["est_wait_s"] is None     # no completed waits observed yet
        # after traffic flows, the estimate turns numeric
        pump_until(gw, lambda: gw.snapshot()["counters"]["completed"] == 2)
        gw.submit([1, 2])
        gw.submit([3, 4])
        with pytest.raises(QueueFullError) as ei:
            gw.submit([5, 6])
        assert ei.value.details["est_wait_s"] >= 0.0

    def test_too_large_carries_capacity_hints(self):
        gate = CapacityGate(FakeEngine(max_ctx_tokens=64, free_blocks=4), 64)
        with pytest.raises(RequestTooLargeError) as ei:
            gate.check_feasible(60, 8)
        assert ei.value.details == {"total_tokens": 68, "max_ctx_tokens": 64,
                                    "pool": "unified"}
        with pytest.raises(RequestTooLargeError) as ei:
            gate.check_feasible(32, 16)
        assert ei.value.details == {"needed_blocks": 6, "usable_blocks": 4,
                                    "pool": "unified"}

    def test_block_policy_timeout_carries_depth(self):
        gw = make_gateway(max_queue_depth=1, admission_policy="block",
                          block_timeout_s=0.05)
        gw.submit([1, 2])
        with pytest.raises(QueueFullError) as ei:
            gw.submit([3, 4])
        assert ei.value.details["queue_depth"] == 1
        assert ei.value.details["policy"] == "block"


class TestConfigAndMetrics:

    def test_serving_config_block_validates(self):
        cfg = get_serving_config({"serving": {
            "max_queue_depth": 8, "admission_policy": "shed",
            "sampling": {"temperature": 0.7, "top_p": 0.9}}})
        assert cfg.max_queue_depth == 8 and cfg.admission_policy == "shed"
        assert get_serving_config({}).admission_policy == "reject"
        with pytest.raises(ValueError):
            get_serving_config({"serving": {"admission_policy": "drop"}})
        with pytest.raises(Exception):
            get_serving_config({"serving": {"sampling": {"top_p": 7.0}}})
        with pytest.raises(Exception):
            get_serving_config({"serving": {"max_queue_depth": 0}})

    def test_metrics_snapshot_and_histograms(self):
        m = ServingMetrics(window=64)
        m.count("submitted", 3)
        for ms in (1.0, 2.0, 3.0, 100.0):
            m.observe_ttft(ms / 1e3)
        m.gauge(queue_depth=4)
        m.gauge_peak("queue_depth_peak", 4)
        m.gauge_peak("queue_depth_peak", 2)  # peak never regresses
        snap = m.snapshot()
        assert snap["counters"]["submitted"] == 3
        assert snap["gauges"]["queue_depth_peak"] == 4
        assert snap["ttft"]["count"] == 4
        assert snap["ttft"]["p50_ms"] == pytest.approx(2.0, abs=1.01)
        assert snap["ttft"]["max_ms"] == pytest.approx(100.0)
        assert sum(snap["ttft"]["buckets"]) == 4

    def test_metrics_route_through_monitor_write_events(self, tmp_path):
        from deepspeed_tpu.monitor.config import DeepSpeedMonitorConfig
        from deepspeed_tpu.monitor.monitor import csvMonitor
        m = ServingMetrics()
        m.count("tokens_generated", 10)
        m.observe_ttft(0.005)
        mon = csvMonitor(DeepSpeedMonitorConfig(**{"csv_monitor": {
            "enabled": True, "output_path": str(tmp_path),
            "job_name": "serve"}}).csv_monitor)
        m.write_events(mon, step=1)
        import csv as _csv
        rows = list(_csv.reader(open(
            tmp_path / "serve" / "serving_count_tokens_generated.csv")))
        assert rows[1] == ["1", "10.0"]
        assert (tmp_path / "serve" / "serving_ttft_p50_ms.csv").exists()
