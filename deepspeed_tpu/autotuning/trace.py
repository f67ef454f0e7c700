"""Serving traffic traces: record, synthesize, replay.

The serving autotuner tunes against *workloads*, not microbenchmarks,
so this module gives every layer the same currency — a
:class:`ServingTrace`: an ordered list of requests with arrival
offsets, token-level prompts, generation budgets, priorities, and
prefix-share structure, serialized as one JSON object per line
(``*.trace.jsonl``, header line first) so traces diff cleanly and
stream without loading.

Three ways to get one:

- **record** real gateway traffic: attach a :class:`TraceRecorder` via
  ``ServingGateway.attach_recorder()`` — every feasible ``submit()``
  is stamped with its arrival offset and prefix-share group;
- **synthesize** with :func:`synthesize_trace` — seeded ``steady`` /
  ``bursty`` / ``prefix_heavy`` mixes for tuning before production
  traffic exists;
- **load** a saved ``.trace.jsonl``.

And two ways to replay one:

- :func:`replay_lockstep` — single-threaded, virtual-time replay
  against a manual-pump gateway (``auto_start=False``). Bit-exact
  deterministic: the same trace replayed twice produces identical
  greedy streams AND identical admission decisions, which is what the
  record→replay tests pin.
- :func:`replay_realtime` — paced replay (``speed`` scales recorded
  inter-arrival gaps) against a live gateway; the offline tuner's
  measurement path.

Stdlib-only by design: traces must load in tooling contexts (ds_lint,
sweep drivers) without importing jax.
"""

import dataclasses
import json
import random
import time
from typing import Callable, Dict, List, Optional

# v2 added the optional per-request adapter_id field; v3 adds optional
# per-request sample (resolved on-device sampling spec) and schema
# (raw grammar/JSON-schema constraint). v1/v2 traces still load.
TRACE_VERSION = 3
TRACE_KINDS = ("recorded", "steady", "bursty", "prefix_heavy")
# leading tokens that define a prefix-share group when recording (one
# KV block at the default block size — shorter shares aren't reusable)
_PREFIX_GROUP_LEN = 16


@dataclasses.dataclass
class TraceRequest:
    """One request in a trace. ``arrival_s`` is the offset from the
    trace start; ``prefix_group`` labels requests sharing a common
    prompt prefix (the prefix-cache-relevant structure)."""
    uid: int
    arrival_s: float
    prompt: List[int]
    max_new_tokens: int
    priority: int = 0
    prefix_group: Optional[int] = None
    # multi-tenant LoRA: which adapter served the request (None = base).
    # Trace v2; v1 traces load with None — replay then routes to base.
    adapter_id: Optional[int] = None
    # trace v3: the RESOLVED sampling spec (the gateway backfills the
    # seed before recording, so a replay draws the bit-identical
    # stream) and the RAW schema constraint (dict or regex string —
    # replay recompiles it over the replaying config's vocab)
    sample: Optional[Dict] = None
    schema: Optional[object] = None

    def to_json(self) -> Dict:
        out = {"uid": self.uid, "arrival_s": round(self.arrival_s, 6),
               "prompt": list(self.prompt),
               "max_new_tokens": self.max_new_tokens,
               "priority": self.priority,
               "prefix_group": self.prefix_group}
        if self.adapter_id is not None:
            # only written when set, so base-only v2 traces stay line-
            # identical to v1 payloads (clean diffs across versions);
            # same rule for the v3 sample/schema fields below
            out["adapter_id"] = int(self.adapter_id)
        if self.sample is not None:
            out["sample"] = dict(self.sample)
        if self.schema is not None:
            out["schema"] = self.schema
        return out

    @classmethod
    def from_json(cls, d: Dict) -> "TraceRequest":
        aid = d.get("adapter_id")
        return cls(uid=int(d["uid"]), arrival_s=float(d["arrival_s"]),
                   prompt=[int(t) for t in d["prompt"]],
                   max_new_tokens=int(d["max_new_tokens"]),
                   priority=int(d.get("priority", 0)),
                   prefix_group=d.get("prefix_group"),
                   adapter_id=int(aid) if aid is not None else None,
                   sample=d.get("sample"), schema=d.get("schema"))


class ServingTrace:
    """An ordered request workload plus its provenance metadata."""

    def __init__(self, requests: List[TraceRequest], meta: Optional[Dict] = None):
        self.requests = sorted(requests, key=lambda r: (r.arrival_s, r.uid))
        self.meta = dict(meta or {})
        self.meta.setdefault("version", TRACE_VERSION)
        self.meta.setdefault("kind", "recorded")

    def __len__(self):
        return len(self.requests)

    def __iter__(self):
        return iter(self.requests)

    def duration_s(self) -> float:
        return self.requests[-1].arrival_s if self.requests else 0.0

    def prefix(self, n: int) -> "ServingTrace":
        """The first ``n`` requests (successive-halving rungs replay
        growing prefixes of one trace, never different samples)."""
        return ServingTrace(self.requests[:n], dict(self.meta))

    def summary(self) -> Dict:
        n = len(self.requests)
        if not n:
            return {"requests": 0}
        shared = sum(1 for r in self.requests if r.prefix_group is not None)
        return {
            "kind": self.meta.get("kind"),
            "requests": n,
            "duration_s": round(self.duration_s(), 3),
            "mean_prompt_len": round(
                sum(len(r.prompt) for r in self.requests) / n, 1),
            "mean_max_new": round(
                sum(r.max_new_tokens for r in self.requests) / n, 1),
            "prefix_share": round(shared / n, 3),
        }

    # -------------------------------------------------------------- io
    def save(self, path: str) -> str:
        with open(path, "w") as fd:
            fd.write(json.dumps({"trace_meta": self.meta}) + "\n")
            for req in self.requests:
                fd.write(json.dumps(req.to_json()) + "\n")
        return path

    @classmethod
    def load(cls, path: str) -> "ServingTrace":
        meta, requests = {}, []
        with open(path) as fd:
            for i, line in enumerate(fd):
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if i == 0 and "trace_meta" in d:
                    meta = d["trace_meta"]
                    if int(meta.get("version", 0)) > TRACE_VERSION:
                        raise ValueError(
                            f"trace {path} is version {meta['version']}; "
                            f"this build reads <= {TRACE_VERSION}")
                    continue
                requests.append(TraceRequest.from_json(d))
        return cls(requests, meta)


class TraceRecorder:
    """Thread-safe recorder the gateway calls once per feasible
    ``submit()``. The clock starts at the first recorded request, so a
    saved trace always begins at offset 0.

    Thread-shared: client threads record concurrently while an
    operator thread may snapshot/save.
    """

    def __init__(self, prefix_group_len: int = _PREFIX_GROUP_LEN):
        import threading

        from deepspeed_tpu.utils.sanitize import tracked_lock
        self._lock = tracked_lock(threading.Lock(), "TraceRecorder._lock")
        self.prefix_group_len = int(prefix_group_len)
        self._t0 = None
        self._requests = []
        self._groups = {}  # leading-token tuple -> group id
        self.recorded = 0

    def record(self, prompt, max_new_tokens, priority, adapter_id=None,
               sample=None, schema=None) -> None:
        now = time.monotonic()
        key = (tuple(prompt[:self.prefix_group_len])
               if len(prompt) >= self.prefix_group_len else None)
        with self._lock:
            if self._t0 is None:
                self._t0 = now
            group = None
            if key is not None:
                group = self._groups.setdefault(key, len(self._groups))
            self._requests.append(TraceRequest(
                uid=len(self._requests), arrival_s=now - self._t0,
                prompt=list(prompt), max_new_tokens=int(max_new_tokens),
                priority=int(priority), prefix_group=group,
                adapter_id=int(adapter_id) if adapter_id else None,
                sample=dict(sample) if sample else None, schema=schema))
            self.recorded += 1

    def trace(self, meta: Optional[Dict] = None) -> ServingTrace:
        with self._lock:
            requests = list(self._requests)
        base = {"kind": "recorded", "requests": len(requests)}
        base.update(meta or {})
        return ServingTrace(requests, base)

    def save(self, path: str, meta: Optional[Dict] = None) -> str:
        return self.trace(meta).save(path)


# ------------------------------------------------------------ synthesis
def synthesize_trace(kind: str, n_requests: int, *, seed: int = 0,
                     vocab_size: int = 256, rate_rps: float = 32.0,
                     mean_prompt_len: int = 24, mean_new_tokens: int = 12,
                     prefix_groups: int = 4,
                     prefix_share_len: int = 16) -> ServingTrace:
    """Seeded synthetic workload of one of three shapes:

    - ``steady``: Poisson arrivals at ``rate_rps``, geometric prompt
      and generation lengths around their means — the baseline mix;
    - ``bursty``: the same request marginals but arrivals clumped into
      bursts (~8 requests each) with idle gaps, alternating
      long-prefill/short-gen and short-prefill/long-gen bursts — the
      admission/budget stress shape;
    - ``prefix_heavy``: steady arrivals where requests cluster into
      ``prefix_groups`` families sharing a ``prefix_share_len``-token
      prompt prefix — the prefix-cache-relevant shape.
    """
    if kind not in ("steady", "bursty", "prefix_heavy"):
        raise ValueError(f"unknown trace kind {kind!r} (expected steady, "
                         f"bursty, or prefix_heavy)")
    if vocab_size < 8:
        raise ValueError(f"vocab_size must be >= 8, got {vocab_size}")
    rng = random.Random(seed)
    lo, hi = 3, vocab_size - 1  # avoid 0/1/2 (pad/eos conventions)

    def tok():
        return rng.randint(lo, hi)

    def length(mean):
        return max(1, min(4 * mean, int(rng.expovariate(1.0 / mean)) + 1))

    requests, t = [], 0.0
    shared = [[tok() for _ in range(prefix_share_len)]
              for _ in range(max(1, prefix_groups))]
    burst_left, burst_long_prefill = 0, False
    for uid in range(n_requests):
        if kind == "bursty":
            if burst_left == 0:
                burst_left = rng.randint(4, 12)
                burst_long_prefill = not burst_long_prefill
                t += rng.expovariate(rate_rps / 8.0)  # inter-burst gap
            else:
                t += rng.expovariate(rate_rps * 4.0)  # intra-burst
            burst_left -= 1
            if burst_long_prefill:
                plen, new = length(3 * mean_prompt_len), length(
                    max(2, mean_new_tokens // 3))
            else:
                plen, new = length(max(2, mean_prompt_len // 3)), length(
                    2 * mean_new_tokens)
            prompt, group = [tok() for _ in range(plen)], None
        elif kind == "prefix_heavy":
            t += rng.expovariate(rate_rps)
            group = rng.randrange(len(shared))
            tail = [tok() for _ in range(length(mean_prompt_len))]
            prompt, new = shared[group] + tail, length(mean_new_tokens)
        else:  # steady
            t += rng.expovariate(rate_rps)
            prompt, new = [tok() for _ in range(length(mean_prompt_len))], \
                length(mean_new_tokens)
            group = None
        requests.append(TraceRequest(
            uid=uid, arrival_s=t, prompt=prompt, max_new_tokens=new,
            priority=rng.choice((0, 0, 0, 1)), prefix_group=group))
    return ServingTrace(requests, {
        "kind": kind, "seed": seed, "vocab_size": vocab_size,
        "rate_rps": rate_rps, "requests": n_requests})


# -------------------------------------------------------------- replay
@dataclasses.dataclass
class ReplayReport:
    """Outcome of one trace replay against one gateway config."""
    requests: List[Dict]          # per-request: uid, status, tokens/reason
    admitted_order: List[int]     # trace uids in admission order
    completed: int
    rejected: int
    failed: int
    gen_tokens: int
    wall_s: float
    gen_tok_s: float
    p50_ttft_ms: Optional[float]
    p99_ttft_ms: Optional[float]
    snapshot: Dict

    def streams(self) -> Dict[int, List[int]]:
        """trace uid -> generated token stream (completed requests)."""
        return {r["uid"]: r["tokens"] for r in self.requests
                if r["status"] == "completed"}

    def admission_decisions(self) -> List[Dict]:
        """The decision log determinism tests compare: per-request
        terminal admission outcome, in trace order."""
        return [{"uid": r["uid"], "status": r["status"],
                 "reason": r.get("reason")} for r in self.requests]

    def to_json(self) -> Dict:
        return {"completed": self.completed, "rejected": self.rejected,
                "failed": self.failed, "gen_tokens": self.gen_tokens,
                "wall_s": round(self.wall_s, 4),
                "gen_tok_s": round(self.gen_tok_s, 2),
                "p50_ttft_ms": self.p50_ttft_ms,
                "p99_ttft_ms": self.p99_ttft_ms}


def _finalize(gateway, per_request, admitted_order, handles, wall_s):
    for rec, handle in zip(per_request, handles):
        if handle is None:
            continue  # rejected at submit
        try:
            rec["tokens"] = handle.result(timeout=0)
            rec["status"] = "completed"
        except TimeoutError:
            rec["status"], rec["reason"] = "failed", "unfinished"
        except Exception as e:  # typed ServingError terminal state
            rec["status"] = handle.status
            rec["reason"] = getattr(e, "reason", type(e).__name__)
    completed = sum(1 for r in per_request if r["status"] == "completed")
    rejected = sum(1 for r in per_request if r["status"] == "rejected")
    failed = len(per_request) - completed - rejected
    gen_tokens = sum(len(r.get("tokens", ())) for r in per_request)
    snap = gateway.snapshot()
    ttft = snap.get("ttft", {})
    return ReplayReport(
        requests=per_request, admitted_order=admitted_order,
        completed=completed, rejected=rejected, failed=failed,
        gen_tokens=gen_tokens, wall_s=wall_s,
        gen_tok_s=gen_tokens / wall_s if wall_s > 0 else 0.0,
        p50_ttft_ms=ttft.get("p50_ms"), p99_ttft_ms=ttft.get("p99_ms"),
        snapshot=snap)


def _submit(gateway, req):
    kw = {}
    aid = getattr(req, "adapter_id", None)
    if aid is not None:
        # only forwarded when recorded: base-only traces keep replaying
        # against gateways/routers that predate adapter routing
        kw["adapter_id"] = aid
    # v3 fields, same set-only rule — greedy traces replay unchanged
    # against pre-sampling gateways. The recorded sample already holds
    # its resolved seed, so the replayed stream is bit-identical.
    if getattr(req, "sample", None) is not None:
        kw["sample"] = req.sample
    if getattr(req, "schema", None) is not None:
        kw["schema"] = req.schema
    return gateway.submit(req.prompt, max_new_tokens=req.max_new_tokens,
                          priority=req.priority, **kw)


def replay_lockstep(gateway, trace: ServingTrace,
                    pump_per_arrival: int = 1) -> ReplayReport:
    """Deterministic single-threaded replay: the gateway must be in
    manual-pump mode (``auto_start=False``). Requests are submitted in
    arrival order with ``pump_per_arrival`` pump iterations between
    arrivals (a virtual clock — one arrival gap, one pump quantum),
    then the pump runs until everything retires. Admission order is
    read off the pump's own ``_active`` transitions, so two replays of
    one trace compare exactly."""
    if gateway._pump_thread is not None:
        raise ValueError("replay_lockstep needs a manual-pump gateway "
                         "(auto_start=False)")
    per_request, handles = [], []
    admitted_order, seen = [], set()
    by_gw_uid = {}
    t0 = time.monotonic()

    def note_admissions():
        for gw_uid in gateway._active:  # dict: admission-ordered
            if gw_uid not in seen:
                seen.add(gw_uid)
                admitted_order.append(by_gw_uid.get(gw_uid, gw_uid))
        # a request can be admitted AND retire within one pump quantum
        # (short prompt, tiny max_new) — it never shows in ``_active``;
        # sweep handles that reached the scheduler, in submit order (a
        # deterministic rule, so two replays still compare exactly)
        for handle in handles:
            if handle is not None and handle.uid not in seen \
                    and handle.status in ("running", "completed"):
                seen.add(handle.uid)
                admitted_order.append(by_gw_uid[handle.uid])

    for req in trace:
        rec = {"uid": req.uid, "status": "submitted"}
        per_request.append(rec)
        try:
            handle = _submit(gateway, req)
            by_gw_uid[handle.uid] = req.uid
            handles.append(handle)
        except Exception as e:
            rec["status"] = "rejected"
            rec["reason"] = getattr(e, "reason", type(e).__name__)
            handles.append(None)
            continue
        for _ in range(pump_per_arrival):
            gateway._pump_once()
            note_admissions()
    while gateway._active or gateway._ending or len(gateway.queue) > 0:
        gateway._pump_once()    # _ending: retired, its last tokens not streamed yet
        note_admissions()
    return _finalize(gateway, per_request, admitted_order, handles,
                     time.monotonic() - t0)


def replay_realtime(gateway, trace: ServingTrace, *, speed: float = 1.0,
                    timeout_s: float = 120.0,
                    on_submit: Optional[Callable] = None) -> ReplayReport:
    """Paced replay against a LIVE gateway (pump thread running):
    recorded inter-arrival gaps are honored, divided by ``speed``
    (2.0 = twice the recorded load). The measurement path for the
    offline tuner and the bench lane."""
    if speed <= 0:
        raise ValueError(f"speed must be > 0, got {speed}")
    per_request, handles = [], []
    t0 = time.monotonic()
    for req in trace:
        target = t0 + req.arrival_s / speed
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec = {"uid": req.uid, "status": "submitted"}
        per_request.append(rec)
        try:
            handle = _submit(gateway, req)
            handles.append(handle)
            if on_submit is not None:
                on_submit(req, handle)
        except Exception as e:
            rec["status"] = "rejected"
            rec["reason"] = getattr(e, "reason", type(e).__name__)
            handles.append(None)
    deadline = time.monotonic() + timeout_s
    for handle in handles:
        if handle is None:
            continue
        remaining = deadline - time.monotonic()
        try:
            handle.result(timeout=max(remaining, 0.001))
        except Exception:
            pass  # terminal state harvested in _finalize
    wall_s = time.monotonic() - t0
    # admission order is not observable from outside the pump; realtime
    # reports leave it empty (lockstep replay is the determinism path)
    return _finalize(gateway, per_request, [], handles, wall_s)
