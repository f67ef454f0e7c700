"""Runner ``serve``: one ``InferenceEngineV2`` behind one
``ServingGateway``, built as ``bin/ds_serve`` builds them, under an open
or a closed loop from a single client thread.

The client is this file: it submits on the traffic's schedule, polls
every live request's stream with the gateway's public ``tokens()``
iterator a few hundred times a second, and stamps what it receives with
its own clock. Time to first token counts from when a request was
**due**, not from when it was sent, so a stalled generator or a full
queue shows as latency. The gateway's own histograms are not read.
"""

import queue
import time

import numpy as np

from benchmark.harness import reference, spans, trace
from benchmark.harness.device import log
from benchmark.harness.stats import percentile

POLL_S = 0.003          # the client looks at every stream this often
TRACE_S = 6.0           # the traced part of a --trace 1 window: its last seconds


def llama_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``) → the program's ``LlamaConfig``."""
    from deepspeed_tpu.models.llama import LlamaConfig
    head_dim = model["hidden_size"] // model["num_attention_heads"]
    if model.get("head_dim", head_dim) != head_dim:
        raise ValueError(f"head_dim {model['head_dim']} is not hidden_size / heads = {head_dim}")
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        max_position_embeddings=model["max_position_embeddings"],
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        tie_word_embeddings=model.get("tie_word_embeddings", False),
        moe_num_experts=model.get("num_local_experts", 0),
        moe_top_k=model.get("num_experts_per_tok", 2), remat=False)


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_llama
    e = config["engine"]
    # the Pallas paged kernel is pinned, as the bring-up pins it: a shape it
    # cannot take raises instead of running the XLA gather in its place
    pin = {} if rehearse else {"attention": "pallas_paged"}
    return InferenceEngineV2(
        model=build_llama(llama_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            implementation_overrides=pin,
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        # the chip's own generator ("rbg") makes billions of weights in a second or
        # two; the default counter-based one takes most of ten
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


def rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def reference_sample(config, seed):
    """The seeded sequences of the reference check → (the sequences, all
    of them in one zero-padded batch for the reference: attention is
    causal, so what follows a sequence's last token does not reach its
    logits)."""
    rng = np.random.default_rng(seed)
    vocab = config["model"]["vocab_size"]
    seqs = [rng.integers(0, vocab, n, dtype=np.int32)
            for n in config["reference"]["sample_lengths"]]
    padded = np.zeros((len(seqs), max(len(s) for s in seqs)), np.int32)
    for i, s in enumerate(seqs):
        padded[i, :len(s)] = s
    return seqs, padded


def reference_check(engine, config, seed):
    """Prefill, then one decode step through the cache, for a seeded
    sample of sequences in one ragged batch, against the float32
    reference's full forward on the same weights. Logits, not tokens:
    with random weights the largest logit changes on rounding."""
    import jax.numpy as jnp
    ref = config["reference"]
    seqs, padded = reference_sample(config, seed)
    full = np.asarray(reference.logits(engine.params, jnp.asarray(padded), config["model"]))
    want = [full[i, :len(s)] for i, s in enumerate(seqs)]
    uids = [-(i + 1) for i in range(len(seqs))]
    prefill = engine.put(uids, [s[:-1] for s in seqs])
    decode = engine.put(uids, [s[-1:] for s in seqs])
    for uid in uids:
        engine.flush(uid)
    errs = {"prefill": max(rel_err(prefill[i], want[i][-2]) for i in range(len(seqs))),
            "decode": max(rel_err(decode[i], want[i][-1]) for i in range(len(seqs)))}
    finite = bool(np.isfinite(prefill).all() and np.isfinite(decode).all())
    return errs, finite and max(errs.values()) < ref["tolerance"]


def poll(handle):
    """→ (tokens received now, ended, error)."""
    from deepspeed_tpu.serving.admission import ServingError
    n = 0
    try:
        for _ in handle.tokens(timeout=0):
            n += 1
        return n, True, None
    except queue.Empty:
        return n, False, None
    except ServingError as e:
        return n, True, e


class Flight:
    """One request as its client sees it."""
    __slots__ = ("handle", "due", "sent", "prompt_len", "max_new", "first", "last",
                 "tokens", "measured", "client", "ended", "error")

    def __init__(self, handle, due, sent, prompt_len, max_new, measured, client=None):
        self.handle, self.due, self.sent = handle, due, sent
        self.prompt_len, self.max_new = prompt_len, max_new
        self.first = self.last = None
        self.tokens = 0
        self.measured, self.client = measured, client
        self.ended, self.error = None, None


class Client:
    """The single client thread's state: live flights, and what the
    window adds up. ``open_at`` / ``close_at`` bound the window."""

    def __init__(self, gateway, clock):
        self.gateway, self.clock = gateway, clock
        self.live, self.done = [], []
        self.open_at = self.close_at = None
        self.gaps_ms = []            # between deliveries of one stream, inside the window
        self.generated_in_window = 0
        self.high_water = {"active": 0, "queued": 0}
        self.queued_mid = self.queued_end = None

    def submit(self, request, due, measured, client=None, max_new=None):
        max_new = request["max_new"] if max_new is None else max_new
        handle = self.gateway.submit(request["prompt"], max_new_tokens=max_new)
        flight = Flight(handle, due, self.clock(), len(request["prompt"]), max_new,
                        measured, client)
        self.live.append(flight)
        return flight

    def in_window(self, t):
        return self.open_at is not None and self.open_at <= t < self.close_at

    def sweep(self):
        """Look at every live stream once. → the flights that ended."""
        now = self.clock()
        ended = []
        for f in self.live:
            n, end, error = poll(f.handle)
            if n:
                inside = self.in_window(now)
                if f.first is None:
                    f.first = now
                elif inside:
                    self.gaps_ms.append((now - f.last) * 1e3)
                f.last = now
                f.tokens += n
                if inside:
                    self.generated_in_window += n
            if end:
                f.ended, f.error = now, error
                ended.append(f)
        if ended:
            self.live = [f for f in self.live if f.ended is None]
            self.done.extend(ended)
        load = self.gateway.inflight()
        self.high_water["active"] = max(self.high_water["active"], load["active"])
        self.high_water["queued"] = max(self.high_water["queued"], load["queued"])
        return ended


def run_open(client, traffic, seconds, tracer):
    """Pre-roll, window, tail. → (t_open, measured flights)."""
    clock = client.clock
    requests = traffic["requests"]
    t_open = clock() + traffic["preroll_s"]
    client.open_at, client.close_at = t_open, t_open + seconds
    deadline = client.close_at + traffic["tail_s"]
    measured, i = [], 0
    while True:
        now = clock()
        while i < len(requests) and t_open + requests[i]["due_s"] <= now and now < deadline:
            due = t_open + requests[i]["due_s"]
            inside = 0 <= requests[i]["due_s"] < seconds
            flight = client.submit(requests[i], due, inside)
            if inside:
                measured.append(flight)
            i += 1
        client.sweep()
        now = clock()
        if client.queued_mid is None and now >= t_open + seconds / 2:
            client.queued_mid = client.gateway.inflight()["queued"]
        if now >= client.close_at:
            if client.queued_end is None:
                client.queued_end = client.gateway.inflight()["queued"]
                tracer.stop_at_close()
            if now >= deadline or all(f.first is not None or f.ended is not None
                                      for f in measured):
                break
        tracer.maybe_start(now, client.close_at)
        next_due = t_open + requests[i]["due_s"] if i < len(requests) else now + POLL_S
        time.sleep(max(0.0, min(POLL_S, next_due - clock())))
    return t_open, measured


def run_closed(client, traffic, seconds, tracer):
    """Every client always has one request in flight. → (t_open, flights
    that ended inside the window)."""
    clock = client.clock
    deck, dealt = traffic["deck"], 0
    t_open = clock() + traffic["preroll_s"]
    client.open_at, client.close_at = t_open, t_open + seconds
    deadline = client.close_at + traffic["tail_s"]
    for c in range(traffic["clients"]):
        client.submit(deck[dealt % len(deck)], clock(), False, client=c,
                      max_new=traffic["first_max_new"][c])
        dealt += 1
    measured = []
    while True:
        for f in client.sweep():
            if client.in_window(f.ended):
                measured.append(f)
            client.submit(deck[dealt % len(deck)], clock(), False, client=f.client)
            dealt += 1
        now = clock()
        if client.queued_mid is None and now >= t_open + seconds / 2:
            client.queued_mid = client.gateway.inflight()["queued"]
        if now >= client.close_at:
            if client.queued_end is None:
                client.queued_end = client.gateway.inflight()["queued"]
                tracer.stop_at_close()
            # the load stays on until every prompt sent inside the window has
            # its first token, so that its tokens can be laid against the window
            if now >= deadline or all(f.first is not None for f in client.live
                                      if f.sent < client.close_at):
                break
        tracer.maybe_start(now, client.close_at)
        time.sleep(POLL_S)
    return t_open, measured


def window_tokens(client):
    """Tokens served inside the window: every generated token received in
    it, and of each prompt the share of the time between sending the
    request and its first token that lies in it (the client cannot see a
    prefill advance, so a prompt's tokens are spread evenly over that
    time; they count whether computed or served from a cache)."""
    prompts = 0.0
    for f in client.done + client.live:
        if f.first is None or f.first <= f.sent:
            continue
        inside = min(f.first, client.close_at) - max(f.sent, client.open_at)
        if inside > 0:
            prompts += f.prompt_len * inside / (f.first - f.sent)
    return client.generated_in_window + prompts


class WindowTracer:
    """Traces the last ``TRACE_S`` seconds of the window when asked to."""

    def __init__(self, enabled, clock, keep):
        self.capture = trace.Capture(keep=keep) if enabled else None
        self.clock = clock

    def maybe_start(self, now, close_at):
        if self.capture is not None and not self.capture.started and now >= close_at - TRACE_S:
            self.capture.start(self.clock, background=True)

    def stop_at_close(self):
        if self.capture is not None and self.capture.started and self.capture.trace is None:
            self.capture.stop(self.clock)


def warm_up(gateway, config):
    """One request alone that walks through every program the cell can
    run: a prompt longer than the token budget (the budget-sized program,
    then the rest of the prompt in the sequence-count-sized one) and an
    answer whose remaining length steps through every power-of-two burst
    (47 = 16+16+8+4+2+1)."""
    vocab = config["model"]["vocab_size"]
    budget = config["engine"]["token_budget"]
    prompt = np.arange(budget + budget // 4, dtype=np.int32) % vocab
    tokens = gateway.submit(prompt, max_new_tokens=48).result(timeout=900)
    if len(tokens) != 48:
        raise RuntimeError(f"warm-up request returned {len(tokens)} tokens of 48")


def run(ctx):
    from deepspeed_tpu.serving import ServingConfig, ServingGateway
    clock = time.perf_counter
    config, seconds = ctx.config, ctx.seconds
    traffic = ctx.generate(vocab=config["model"]["vocab_size"])
    engine = build_engine(config, ctx.seed, ctx.rehearse)
    log(f"[serve] engine built at {ctx.age():.1f}s; compiles {ctx.meter.totals()}")
    errs, agrees = reference_check(engine, config, ctx.seed)
    log(f"[serve] reference check {errs} at {ctx.age():.1f}s; compiles {ctx.meter.totals()}")
    counts = spans.instrument(engine, clock)
    gateway = ServingGateway(engine, config=ServingConfig(
        max_queue_depth=config["engine"]["max_queue_depth"],
        default_max_new_tokens=16))
    try:
        warm_up(gateway, config)
        log(f"[serve] warm at {ctx.age():.1f}s; compiles {ctx.meter.totals()}")
        compiles_before = ctx.meter.totals()
        counts_before = counts.snapshot()
        syncs_before, emitted_before = engine.host_syncs, engine.tokens_emitted
        client = Client(gateway, clock)
        tracer = WindowTracer(ctx.trace, clock, ctx.keep_trace)
        loop = run_open if traffic["loop"] == "open" else run_closed
        t_open, measured = loop(client, traffic, seconds, tracer)
        setup_s = ctx.age_at(t_open)
        compiled_in_run = ctx.meter.totals()["compiles"] - compiles_before["compiles"]
        counts_after = counts.snapshot()
        syncs, emitted = engine.host_syncs - syncs_before, engine.tokens_emitted - emitted_before
        impls = dict(engine.attention_impls)
        device = ctx.describe_device()
        snapshot = gateway.snapshot()
    finally:
        gateway.shutdown()
    log(f"[serve] window closed; live high water {client.high_water}, queued mid/end "
        f"{client.queued_mid}/{client.queued_end}, engine {counts_after}")

    # ---- what the client saw
    is_open = traffic["loop"] == "open"
    attempted = len(measured)
    failed = sum(1 for f in measured if f.error is not None or (is_open and f.first is None))
    ttft = [(f.first - f.due) * 1e3 for f in measured if f.first is not None] if is_open else []
    ended_inside = [f for f in client.done
                    if f.ended is not None and client.in_window(f.ended) and f.error is None]
    timed = [f for f in ended_inside if f.tokens >= 2]
    tpot = [(f.last - f.first) * 1e3 / (f.tokens - 1) for f in timed]
    decode_s = sum(f.last - f.first for f in timed)
    decode_n = sum(f.tokens - 1 for f in timed)
    tpot_mean = decode_s * 1e3 / decode_n if decode_n else None
    late = [(f.sent - f.due) * 1e3 for f in measured] if is_open else []
    waits = [(counts.first_step_at[f.handle.uid] - f.due) * 1e3 for f in measured
             if f.handle.uid in counts.first_step_at]
    steps = counts_after["model_steps"] - counts_before["model_steps"]
    fed = counts_after["tokens_fed"] - counts_before["tokens_fed"]
    residence = [f.ended - f.sent for f in ended_inside]

    pinned_ok = ctx.rehearse or (bool(impls) and set(impls.values()) == {"pallas_paged"})
    correct = bool(agrees and pinned_ok and compiled_in_run == 0 and failed == 0
                   and attempted > 0)
    facts = {
        "reference_rel_err": errs, "attention_impls": {str(k): v for k, v in impls.items()},
        "compiled_after_warm_up": compiled_in_run, "compile_meter": compiles_before,
        "requests_ended_in_window": len(ended_inside), "high_water": client.high_water,
        "queued_mid": client.queued_mid, "queued_end": client.queued_end,
        "mean_residence_s": float(np.mean(residence)) if residence else None,
        "completed_per_s": len(ended_inside) / seconds,
        "ttft_p50_ms": percentile(ttft, 50), "ttft_p99_ms": percentile(ttft, 99),
        "tpot_mean_ms": tpot_mean,
        "tpot_p50_ms": percentile(tpot, 50),
        "bursts": counts_after["bursts"] - counts_before["bursts"],
        "gateway_counters": snapshot["counters"],
        # every measured request: when it was due (s after the window opened), its
        # time to first token in ms (null: none), prompt length
        "ttft_by_due": [[round(f.due - t_open, 3),
                         None if f.first is None else round((f.first - f.due) * 1e3, 1),
                         f.prompt_len] for f in measured] if is_open else [],
        # every request behind tpot_mean_ms and tpot_req_p90_ms (pre-roll arrivals that
        # ended in the window too, so a due before 0): due, prompt length, tokens received,
        # TPOT in ms as measured - tpot_req_p90_ms is percentile(column 3, 90), tpot_mean_ms
        # is sum(column 3 x (column 2 - 1)) / sum(column 2 - 1)
        "tpot_by_request": [[round(f.due - t_open, 3), f.prompt_len, f.tokens, t]
                            for f, t in zip(timed, tpot)],
    }
    observed = {
        "setup_s": setup_s,
        "ttft_p90_ms": percentile(ttft, 90),
        "ttft_p50_ms": percentile(ttft, 50),
        "tpot_mean_ms": tpot_mean,
        "tpot_p90_ms": percentile(tpot, 90),
        "serve_tok_s": window_tokens(client) / seconds,
        "gen_late_p99_ms": percentile(late, 99),
        "queue_wait_p90_ms": percentile(waits, 90),
        "itl_p99_ms": percentile(client.gaps_ms, 99),
        "tokens_per_step": fed / steps if steps else None,
        "host_syncs_per_tok": syncs / emitted if emitted else None,
        "compile_s": compiles_before["compile_s"] + compiles_before["trace_lower_s"],
    }
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "observed": observed, "device": device, "facts": facts,
            "trace": tracer.capture.trace if tracer.capture else None,
            "trace_window_s": tracer.capture.window_s if tracer.capture else None}
