"""Open loop: requests are due on a schedule whether or not earlier ones
have finished (independent users). Three phases at one rate — pre-roll
(served, not measured, part of set-up), the window, and a tail that keeps
the load on until every measured request has its first token — each
with its own stratified set dealt in blocks of ``block_s`` seconds, so
the window, and every stretch of it, holds the same work for every seed.

The pre-roll opens with ``warm_live`` requests at once, each with its
answer cut to a share ``(i + 0.5) / warm_live`` of its length: the
population a system in steady state would already hold, part-way through
their answers. Without them the window would open on a system that is
still filling (an answer can last most of a minute)."""

import numpy as np

from benchmark.harness.strata import block_sizes, deal, dealt_arrivals, stratified_lengths


def _phase(params, span_s, offset_s, vocab, rng):
    n = int(round(params["rate_rps"] * span_s))
    sizes = block_sizes(n, max(1, int(round(span_s / params["block_s"]))), rng)
    prompts = deal(stratified_lengths(params["prompt_tokens"], n), sizes, rng)
    outputs = deal(stratified_lengths(params["output_tokens"], n), sizes, rng)
    due = dealt_arrivals(params["gap_s"], sizes, span_s, rng)
    return [{"due_s": offset_s + due[b][i],
             "prompt": rng.integers(0, vocab, prompts[b][i], dtype=np.int32),
             "max_new": outputs[b][i]}
            for b in range(len(sizes)) for i in range(sizes[b])]


def _warm(params, offset_s, vocab, rng):
    n = int(params["warm_live"])
    prompts = deal(stratified_lengths(params["prompt_tokens"], n), [n], rng)[0]
    outputs = deal(stratified_lengths(params["output_tokens"], n), [n], rng)[0]
    shares = (rng.permutation(n) + 0.5) / max(n, 1)
    return [{"due_s": offset_s, "prompt": rng.integers(0, vocab, prompts[i], dtype=np.int32),
             "max_new": max(1, int(round(outputs[i] * shares[i])))} for i in range(n)]


def generate(params, seed, seconds, vocab):
    """→ requests sorted by ``due_s``, which counts from the opening of
    the window (pre-roll requests are due before 0)."""
    rng = np.random.default_rng(seed)
    preroll, tail = float(params["preroll_s"]), float(params["tail_s"])
    requests = (_warm(params, -preroll, vocab, rng)
                + _phase(params, preroll, -preroll, vocab, rng)
                + _phase(params, seconds, 0.0, vocab, rng)
                + _phase(params, tail, seconds, vocab, rng))
    requests.sort(key=lambda r: r["due_s"])
    return {"loop": "open", "requests": requests, "preroll_s": preroll, "tail_s": tail}
