"""Closed loop: ``clients`` callers, each sends its next request when its
last one completes (batch jobs, evaluation harnesses). The requests are
one stratified deck of ``cycle_requests``, arranged in blocks of
``block_requests`` that each hold one value from every stratum of
neighbouring quantiles (the seed decides which, and the order inside a
block). It is dealt to whichever client is free and dealt again when it
runs out, so over a cycle every seed has sent the same multiset, and
over any block nearly the same work.

The clients do not start in step: each client's first request has its
answer cut to a share ``(i + 0.5) / clients`` of its length, as if the
run had begun with every client somewhere inside a request. A closed
loop that starts all its clients at once decodes in lock-step waves for
several cycles, and the window would measure where the waves fall."""

import numpy as np

from benchmark.harness.strata import block_sizes, deal, stratified_lengths


def generate(params, seed, seconds, vocab):
    rng = np.random.default_rng(seed)
    n, clients = int(params["cycle_requests"]), int(params["clients"])
    sizes = block_sizes(n, max(1, n // int(params["block_requests"])), rng)
    prompts = sum(deal(stratified_lengths(params["prompt_tokens"], n), sizes, rng), [])
    outputs = sum(deal(stratified_lengths(params["output_tokens"], n), sizes, rng), [])
    deck = [{"prompt": rng.integers(0, vocab, prompts[i], dtype=np.int32),
             "max_new": outputs[i]} for i in range(n)]
    shares = (rng.permutation(clients) + 0.5) / clients
    first = [max(1, int(round(deck[i % n]["max_new"] * shares[i]))) for i in range(clients)]
    return {"loop": "closed", "clients": clients, "deck": deck, "first_max_new": first,
            "preroll_s": float(params["preroll_s"]), "tail_s": float(params["tail_s"])}
