"""``closed_loop`` with the token statistics of text: the same stratified
deck of prompt and answer lengths dealt in blocks to clients that start
out of step (``generators/closed_loop.py`` says why), but a prompt's ids
are not uniform over the vocabulary.

Each request has a **topic**. A topic is a seeded permutation of the
vocabulary's ids, and a prompt's ids are drawn with probability ~ 1 /
rank ** ``token_zipf`` by rank in its topic's permutation: a few ids make
most of a prompt and which ones depends on the topic, as the words of a
subject do. Topics are drawn with popularity ~ 1 / rank ** ``topic_zipf``;
like the lengths they are not sampled but dealt - every block of the deck
holds the topics in those shares (to a request, the remainders carried
from block to block), in seeded order - so every seed sends the same
multiset of (topic, length) and every stretch of a run the same mix.
Answers are whatever the model decodes.

With a router in front of experts this skews what the experts see from
step to step: which columns a token picks follows its id, and the ids
follow the few topics live at the time.
"""

import numpy as np

from benchmark.harness.strata import block_sizes, deal, stratified_lengths


def topic_counts(sizes, shares):
    """How many requests of each topic every block holds: the cumulative
    count of a topic after block ``b`` is its share of the requests so
    far, rounded so that the blocks' sizes are kept (largest remainder)."""
    shares = np.asarray(shares, float) / np.sum(shares)
    given, total, out = np.zeros(len(shares), int), 0, []
    for size in sizes:
        total += size
        due = shares * total - given
        take = np.floor(due).astype(int).clip(min=0)
        for t in np.argsort(-(due - take), kind="stable")[:size - take.sum()]:
            take[t] += 1
        given += take
        out.append(take)
    return out


def generate(params, seed, seconds, vocab):
    rng = np.random.default_rng(seed)
    n, clients = int(params["cycle_requests"]), int(params["clients"])
    sizes = block_sizes(n, max(1, n // int(params["block_requests"])), rng)
    prompts = sum(deal(stratified_lengths(params["prompt_tokens"], n), sizes, rng), [])
    outputs = sum(deal(stratified_lengths(params["output_tokens"], n), sizes, rng), [])

    n_topics = int(params["topics"])
    popularity = 1.0 / np.arange(1, n_topics + 1) ** float(params["topic_zipf"])
    topics = []
    for counts in topic_counts(sizes, popularity):
        block = np.repeat(np.arange(n_topics), counts)
        topics += [int(t) for t in block[rng.permutation(len(block))]]
    by_rank = 1.0 / np.arange(1, vocab + 1) ** float(params["token_zipf"])
    cdf = np.cumsum(by_rank / by_rank.sum())
    ids_by_rank = [rng.permutation(vocab).astype(np.int32) for _ in range(n_topics)]

    def prompt(i):
        ranks = np.minimum(np.searchsorted(cdf, rng.random(prompts[i])), vocab - 1)
        return ids_by_rank[topics[i]][ranks]

    deck = [{"prompt": prompt(i), "max_new": outputs[i], "topic": topics[i]} for i in range(n)]
    shares = (rng.permutation(clients) + 0.5) / clients
    first = [max(1, int(round(deck[i % n]["max_new"] * shares[i]))) for i in range(clients)]
    return {"loop": "closed", "clients": clients, "deck": deck, "first_max_new": first,
            "preroll_s": float(params["preroll_s"]), "tail_s": float(params["tail_s"])}
