"""Multi-turn sessions in a closed loop: ``clients`` conversations always in
flight, each a **system prompt** (one of ``system_prompts``, drawn by a Zipf
law) and ``turns`` turns. A turn's prompt is the system prompt, every earlier
message and answer of the session, and the turn's new message - the whole
history again, as a chat front end sends it - so its head is what the
session's last turn (or, for a first turn, any session with that system
prompt) already computed. After a turn's last token the client thinks for
``think_s``, then sends the next; a finished session is replaced by the deck's
next. The generator gives the messages; **the runner builds a turn's prompt
from the ids it received** (``runners/serve_granite.py``), so the history is a
true prefix.

The same multiset every seed (``harness/strata.py``): the system prompts'
lengths (which rank of the Zipf law has which is fixed too: from the median
length outwards down the ranks), the sessions' ``(system prompt, turns)`` pairs
(the Zipf law's inverse CDF at the mid-quantiles; within a system prompt's
sessions the turns ``lo..hi`` at the mid-quantiles of a uniform law), and over
all turns of the deck the messages' lengths, the answers' lengths and the
think times at the distributions' mid-quantiles; the seed decides the order,
which message meets which session, and the ids. ``serve_tok_s`` counts a
turn's whole prompt, history and all, so a session of 8 turns on a 4 k system
prompt weighs four times one of 4 turns on a 2 k one: the sessions are dealt
in blocks of ``block_sessions`` **by that weight** (each block one session
from every stratum of neighbouring weights), the turns' values in as many
blocks and handed to the sessions in deck order, so every stretch of the run
carries nearly the same work.

The clients do not start in step: client ``i`` waits a share ``(i + 0.5) /
clients`` of ``stagger_s`` and enters its first session **at a turn** that
share of the way through it, the turns before it given as ``history`` (their
messages, and answers of the dealt lengths made up of seeded ids): as if the
run had begun with every session somewhere inside its conversation, so that
the pre-roll holds sessions of every depth when the window opens.
"""

import numpy as np

from benchmark.harness.strata import block_sizes, deal, mid_quantiles, stratified, \
    stratified_lengths


def _dealt(values, n_blocks, rng):
    """``values`` (ascending strata) dealt into ``n_blocks`` blocks, flat."""
    return sum(deal(values, block_sizes(len(values), n_blocks, rng), rng), [])


def generate(params, seed, seconds, vocab):
    rng = np.random.default_rng(seed)
    n, clients = int(params["cycle_sessions"]), int(params["clients"])
    n_blocks = max(1, n // int(params["block_sessions"]))
    lengths = stratified_lengths(params["system_tokens"], int(params["system_prompts"]))
    # which rank of the Zipf law has which length is the same for every seed (from the median
    # length outwards down the ranks): were it seeded, the most asked-for prompt would be 2 k
    # in one run and 4 k in the next, and a run's prompt tokens with it
    order = sorted(range(len(lengths)), key=lambda i: abs(i - (len(lengths) - 1) / 2))
    systems = [rng.integers(0, vocab, lengths[i], dtype=np.int32) for i in order]
    weights = 1.0 / np.arange(1, len(systems) + 1) ** float(params["system_zipf_s"])
    cdf = np.cumsum(weights) / weights.sum()
    ranks = [int(np.searchsorted(cdf, u)) for u in mid_quantiles(n)]
    lo, hi = int(params["turns"]["lo"]), int(params["turns"]["hi"])
    # the sessions of one system prompt have every number of turns alike, so the multiset of
    # (system prompt, turns) is every seed's; a session's weight is the prompt tokens it sends
    pairs = [(rank, lo + int(u * (hi - lo + 1))) for rank in range(len(systems))
             for u in mid_quantiles(ranks.count(rank))]
    mean_turn = (params["message_tokens"]["lo"] * params["message_tokens"]["hi"]) ** 0.5 \
        + (params["output_tokens"]["lo"] * params["output_tokens"]["hi"]) ** 0.5
    weighed = [(t * len(systems[r]) + mean_turn * t * (t + 1) / 2, i)
               for i, (r, t) in enumerate(pairs)]
    sizes = block_sizes(n, n_blocks, rng)
    dealt = [pairs[i] for block in deal(weighed, sizes, rng) for _, i in block]
    ranks, turns = [r for r, _ in dealt], [t for _, t in dealt]
    total = sum(turns)
    messages = _dealt(stratified_lengths(params["message_tokens"], total), n_blocks, rng)
    answers = _dealt(stratified_lengths(params["output_tokens"], total), n_blocks, rng)
    thinks = _dealt(stratified(params["think_s"], total), n_blocks, rng)
    deck, at = [], 0
    for rank, count in zip(ranks, turns):
        deck.append({"system": rank, "turns": [
            {"message": rng.integers(0, vocab, messages[at + t], dtype=np.int32),
             "max_new": answers[at + t], "think_s": float(thinks[at + t])}
            for t in range(count)]})
        at += count
    limit = int(params["max_prompt_tokens"])
    for session in deck:
        longest = len(systems[session["system"]]) + sum(
            len(t["message"]) + t["max_new"] for t in session["turns"][:-1]) \
            + len(session["turns"][-1]["message"])
        assert longest <= limit, (longest, limit)
    shares = (rng.permutation(clients) + 0.5) / clients
    start = []
    for c in range(clients):
        session = deck[c % n]
        first = min(int(shares[c] * len(session["turns"])), len(session["turns"]) - 1)
        history = [np.concatenate([t["message"], rng.integers(0, vocab, t["max_new"],
                                                              dtype=np.int32)])
                   for t in session["turns"][:first]]
        start.append({"turn": first, "delay_s": float(shares[c] * params["stagger_s"]),
                      "history": np.concatenate(history) if history
                      else np.zeros(0, np.int32)})
    return {"loop": "sessions", "clients": clients, "systems": systems, "deck": deck,
            "start": start, "preroll_s": float(params["preroll_s"]),
            "tail_s": float(params["tail_s"])}
