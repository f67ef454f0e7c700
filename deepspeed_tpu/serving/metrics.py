"""SLO metrics for the serving gateway.

Counters + latency distributions a serving operator actually pages on:
TTFT (submit -> first token), per-token decode latency, queue depth, KV
occupancy, admission outcomes, preemptions. Everything is exported two
ways: ``snapshot()`` (a plain dict — tests and the CLI read it) and
``write_events(monitor)`` which routes ``(tag, value, step)`` tuples
through the existing ``deepspeed_tpu/monitor`` ``Monitor.write_events``
interface, so serving metrics land in the same TensorBoard/WandB/CSV
backends as training metrics.

Gauges and subsystem stats (prefix cache, KV tier, …) are *pulled*: the
gateway hands over two callables once (:meth:`attach_sources`) and they
are read when ``snapshot()`` / ``events()`` is called, not pushed on every
pump pass. The ``steps`` group is a summary of the process's newest step
records (``deepspeed_tpu/utils/tracing.py``) for this gateway's engine —
the same records, quantities and clock the benchmark's per-layer metrics
read — and ``setup`` what setting that engine up cost (the recorder's
``setup`` records and build table), also counted in ``SETUP_COUNTERS``.

Thread-safe: ``submit()`` runs on client threads while the pump thread
records step/token events.
"""

import bisect
import itertools
import threading
from collections import deque
from statistics import median

from deepspeed_tpu.utils import tracing

# log-ish bucket upper bounds in milliseconds; the last bucket is +inf
LATENCY_BUCKETS_MS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                      100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class _LatencyHistogram:
    """Fixed-bucket histogram + bounded reservoir for percentiles."""

    def __init__(self, window):
        self.buckets = [0] * (len(LATENCY_BUCKETS_MS) + 1)
        self.count = 0
        self.total_ms = 0.0
        self.max_ms = 0.0
        self._recent = deque(maxlen=window)

    def observe(self, ms):
        self.buckets[bisect.bisect_left(LATENCY_BUCKETS_MS, ms)] += 1
        self.count += 1
        self.total_ms += ms
        self.max_ms = max(self.max_ms, ms)
        self._recent.append(ms)

    def percentile(self, q):
        """q in [0, 100], over the recent window (exact, not bucketed)."""
        if not self._recent:
            return 0.0
        xs = sorted(self._recent)
        idx = min(len(xs) - 1, int(round(q / 100.0 * (len(xs) - 1))))
        return xs[idx]

    def to_dict(self):
        return {
            "count": self.count,
            "mean_ms": self.total_ms / self.count if self.count else 0.0,
            "p50_ms": self.percentile(50),
            "p95_ms": self.percentile(95),
            "p99_ms": self.percentile(99),
            "max_ms": self.max_ms,
            "bucket_bounds_ms": list(LATENCY_BUCKETS_MS),
            "buckets": list(self.buckets),
        }


def summarize_steps(records):
    """Step records (``tracing.StepRecord``) → the ``steps`` group:
    counts by kind, mean ``k`` of the decode bursts, and the median
    milliseconds from dispatch to the end of the fetch — per burst step,
    and per ``put`` that carried prompt tokens."""
    counts, burst_k, burst_ms, mixed_ms = {}, [], [], []
    for rec in records:
        if rec.kind == "setup":     # a constructor, no program: the ``setup`` group's
            continue
        counts[rec.kind] = counts.get(rec.kind, 0) + 1
        ns = tracing.device_ns(rec)
        if ns is None:
            continue
        ms = ns / 1e6
        if rec.kind in ("burst", "burst_async"):
            burst_k.append(rec.k)
            burst_ms.append(ms / rec.k)
        elif rec.kind == "put" and rec.n_prompt_tokens > 0:
            mixed_ms.append(ms)
    return {"counts": counts,
            "burst_k_mean": sum(burst_k) / len(burst_k) if burst_k else 0.0,
            "decode_step_ms_p50": median(burst_ms) if burst_ms else 0.0,
            "mixed_step_ms_p50": median(mixed_ms) if mixed_ms else 0.0}


# What setting this gateway's engine up cost (``tracing.setup_summary``), as counters:
# whole milliseconds and numbers that only grow, read when a snapshot is asked for.
# ``setup_init_ms``: the engine's ``setup`` records; ``setup_build_ms``: own trace +
# lowering + backend time inside its other records (its programs, as their first steps
# built them), ``setup_build_trace_ms`` the tracing of that; ``setup_outside_compile_ms``:
# the process's compile time under no record at all - the caller's own ``jit``s
SETUP_COUNTERS = ("setup_init_ms", "setup_build_ms", "setup_build_trace_ms",
                  "setup_outside_compile_ms", "programs_built", "compile_cache_hits",
                  "compile_cache_misses")


def setup_counters(setup):
    """``tracing.setup_summary``'s dict → ``SETUP_COUNTERS``' values."""
    init, build, outside = setup["init_build"], setup["build"], setup["outside"]

    def ms(*ns):
        return sum(ns) // 1_000_000

    return {"setup_init_ms": ms(setup["init_ns"]),
            "setup_build_ms": ms(build["trace_ns"], build["lower_ns"], build["backend_ns"]),
            "setup_build_trace_ms": ms(build["trace_ns"]),
            "setup_outside_compile_ms": ms(outside["trace_ns"], outside["lower_ns"],
                                           outside["backend_ns"]),
            "programs_built": build["programs"],
            "compile_cache_hits": build["hits"] + init["hits"],
            "compile_cache_misses": build["misses"] + init["misses"]}


class ServingMetrics:

    COUNTERS = ("submitted", "admitted", "completed", "cancelled",
                "rejected_queue_full", "rejected_too_large", "shed",
                "deadline_expired", "preemptions", "resumes",
                "tokens_generated", "engine_steps", "failed",
                # of tokens_generated: handed to their streams while the next
                # program ran, and with none dispatched (gateway._on_tokens)
                "tokens_delivered_in_flight", "tokens_delivered_idle",
                # of engine_steps: a put of more than max_seqs tokens (it carried a
                # prompt), and those that ran below the budget-sized program
                "prompt_steps", "prompt_steps_on_rung",
                # a pool run dry: decode rows that waited a step for a block, requests
                # preempted by recompute, and the tokens they had in the cache
                "rows_held_back", "preempted_for_room", "recomputed_tokens",
                "handoffs_exported", "handoffs_imported",
                "weight_refreshes", "rejected_unknown_adapter",
                "rejected_adapter",
                # stalls the gateway found (gateway._check_stall): their number
                # and, in whole milliseconds, what they took beyond the step
                "stalls", "stalled_ms") + SETUP_COUNTERS

    def __init__(self, window=1024):
        self._window = window
        self._lock = threading.Lock()
        self._counters = {name: 0 for name in self.COUNTERS}
        self.ttft = _LatencyHistogram(window)
        self.token_latency = _LatencyHistogram(window)  # inter-token gap
        self.queue_wait = _LatencyHistogram(window)     # submit -> admitted
        self.sched_wait = _LatencyHistogram(window)     # admitted -> first scheduled
        self.prefill_span = _LatencyHistogram(window)   # first scheduled -> first token
        # gauges (last observed; *_peak are high-water marks)
        self._gauges = {"queue_depth": 0, "queue_depth_peak": 0, "running": 0,
                        "paused": 0, "kv_free_blocks": 0, "kv_occupancy": 0.0}
        # external gauge groups published under their own tag prefix
        # (e.g. "Serve/PrefixCache" -> {"hit_rate": ..., ...})
        self._external = {}
        # pulled sources (attach_sources): read under _pull_lock, so that
        # detach_sources() returns only when no read is in flight
        self._pull_lock = threading.Lock()
        self._gauge_source = self._external_source = None
        self._engine_id = None

    # ---------------------------------------------------------------- events
    def count(self, name, n=1):
        with self._lock:
            self._counters[name] += n

    def observe_ttft(self, seconds):
        with self._lock:
            self.ttft.observe(seconds * 1e3)

    def observe_first_tokens(self, firsts):
        """First tokens of requests, a batch at a time: ``(ttft_s,
        sched_wait_s, prefill_span_s)`` each - TTFT and the two spans of it
        that follow admission (None: the scheduler stamped none)."""
        with self._lock:
            for ttft_s, sched_wait_s, prefill_span_s in firsts:
                self.ttft.observe(ttft_s * 1e3)
                if sched_wait_s is not None:
                    self.sched_wait.observe(sched_wait_s * 1e3)
                if prefill_span_s is not None:
                    self.prefill_span.observe(prefill_span_s * 1e3)

    def observe_token_latencies(self, seconds):
        """Gaps between two tokens of a stream, a batch at a time."""
        with self._lock:
            for s in seconds:
                self.token_latency.observe(s * 1e3)

    def observe_queue_wait(self, seconds):
        with self._lock:
            self.queue_wait.observe(seconds * 1e3)

    def gauge(self, **kwargs):
        with self._lock:
            self._gauges.update(kwargs)

    def gauge_peak(self, name, value):
        """High-water-mark gauge (e.g. queue_depth_peak)."""
        with self._lock:
            self._gauges[name] = max(self._gauges.get(name, 0), value)

    def set_external(self, tag_prefix, values):
        """Publish a subsystem's gauge dict under its own tag prefix —
        events come out as ``{tag_prefix}/{key}`` (the prefix-cache
        surface: ``Serve/PrefixCache/{hit_rate,tokens_saved,...}``)."""
        with self._lock:
            self._external[tag_prefix] = dict(values)

    def counter(self, name):
        with self._lock:
            return self._counters[name]

    # --------------------------------------------------------------- sources
    def attach_sources(self, gauges=None, external=None, engine_id=None):
        """``gauges()`` → a dict of gauge values; ``external()`` →
        ``{tag_prefix: stats dict}``. Both are called on every
        ``snapshot()`` / ``events()`` and never otherwise. ``engine_id``
        selects this gateway's step records for the ``steps`` group."""
        with self._pull_lock:
            self._gauge_source, self._external_source = gauges, external
            self._engine_id = engine_id

    def detach_sources(self):
        """Read the sources one last time and let go of them (the engine
        behind them is about to be destroyed): the last observed values
        stay in every later snapshot."""
        self._pull()
        with self._pull_lock:
            self._gauge_source = self._external_source = None

    def _pull(self):
        with self._pull_lock:
            gauges = self._gauge_source() if self._gauge_source is not None else {}
            external = self._external_source() if self._external_source is not None else {}
        with self._lock:
            self._gauges.update(gauges)
            for prefix, values in external.items():
                self._external[prefix] = dict(values)

    # ---------------------------------------------------------------- export
    def snapshot(self):
        """Plain-dict view of everything (tests / CLI / debugging)."""
        self._pull()
        mine = self._engine_id
        # the newest `window` step records, like the histograms' percentiles
        recent = itertools.islice(reversed(tuple(tracing.RECORDER.steps)), self._window)
        steps = summarize_steps(r for r in recent if mine is None or r.engine == mine)
        setup = tracing.setup_summary(mine) if mine is not None else None
        with self._lock:
            if setup is not None:
                for name, value in setup_counters(setup).items():
                    self._counters[name] = max(self._counters[name], int(value))
            return {
                "setup": setup,
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "external": {p: dict(v) for p, v in self._external.items()},
                "ttft": self.ttft.to_dict(),
                "token_latency": self.token_latency.to_dict(),
                "queue_wait": self.queue_wait.to_dict(),
                "sched_wait": self.sched_wait.to_dict(),
                "prefill_span": self.prefill_span.to_dict(),
                "steps": steps,
            }

    def events(self, step=None):
        """Flatten to the monitor event wire format: (tag, value, step)."""
        snap = self.snapshot()
        step = snap["counters"]["engine_steps"] if step is None else step
        out = []
        for name, val in snap["counters"].items():
            out.append((f"serving/count/{name}", val, step))
        for name, val in snap["gauges"].items():
            out.append((f"serving/gauge/{name}", val, step))
        for prefix, vals in snap["external"].items():
            for name, val in vals.items():
                out.append((f"{prefix}/{name}", val, step))
        for hist in ("ttft", "token_latency", "queue_wait", "sched_wait", "prefill_span"):
            for stat in ("mean_ms", "p50_ms", "p95_ms", "p99_ms", "max_ms"):
                out.append((f"serving/{hist}/{stat}", snap[hist][stat], step))
        for kind, n in snap["steps"]["counts"].items():
            out.append((f"serving/steps/count/{kind}", n, step))
        for name in ("burst_k_mean", "decode_step_ms_p50", "mixed_step_ms_p50"):
            out.append((f"serving/steps/{name}", snap["steps"][name], step))
        return out

    def write_events(self, monitor, step=None):
        """Publish through any ``deepspeed_tpu.monitor`` backend (or
        ``MonitorMaster``) — the same interface training metrics use."""
        monitor.write_events(self.events(step))
