"""Spans and counts around the calls into the engine, put there from the
benchmark's files (the program has no spans of its own yet).

:func:`instrument` rebinds ``engine.put`` and ``engine.decode_burst`` on
the one engine object the benchmark built. Each call runs inside a
``jax.profiler.TraceAnnotation`` named ``bench.engine.put`` /
``bench.engine.decode_burst`` (which the trace reduction uses to say
what the host was doing in an idle gap), and is counted: model steps,
tokens fed through them, and the first time each sequence was part of
a step (the end of its queue wait).
"""

import time


class EngineCounts:

    def __init__(self):
        self.calls = {"put": 0, "decode_burst": 0}
        self.model_steps = 0       # put = 1, a burst of k = k
        self.tokens_fed = 0        # prompt chunks + decode tokens, through the model
        self.burst_steps = []      # k of every burst
        self.first_step_at = {}    # uid -> clock at the start of the first step holding it

    def snapshot(self):
        return {"calls": dict(self.calls), "model_steps": self.model_steps,
                "tokens_fed": self.tokens_fed, "bursts": len(self.burst_steps)}


def instrument(engine, clock=time.perf_counter):
    import jax
    counts = EngineCounts()
    put, burst = engine.put, engine.decode_burst

    def seen(uids):
        now = clock()
        first = counts.first_step_at
        for uid in uids:
            if uid not in first:
                first[uid] = now

    def traced_put(batch_uids, batch_tokens, *args, **kwargs):
        seen(batch_uids)
        counts.calls["put"] += 1
        counts.model_steps += 1
        counts.tokens_fed += sum(len(t) if hasattr(t, "__len__") else 1 for t in batch_tokens)
        with jax.profiler.TraceAnnotation("bench.engine.put"):
            return put(batch_uids, batch_tokens, *args, **kwargs)

    def traced_burst(batch_uids, batch_tokens, k, *args, **kwargs):
        counts.calls["decode_burst"] += 1
        counts.model_steps += k
        counts.tokens_fed += k * len(batch_uids)
        counts.burst_steps.append(k)
        with jax.profiler.TraceAnnotation("bench.engine.decode_burst"):
            return burst(batch_uids, batch_tokens, k, *args, **kwargs)

    engine.put, engine.decode_burst = traced_put, traced_burst
    return counts
