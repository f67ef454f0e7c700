"""Stratified draws: the same multiset for every seed.

A run's lengths and gaps are not sampled: they are the distribution's
inverse CDF at the mid-quantiles ``(i + 0.5) / n``. ``--seed`` then
decides only the order, the pairing and the token ids, so every seed
sends the same amount of work and two seeds differ as two shuffles of
one deck do. (Independent draws from a heavy-tailed length law differ
by whole long prompts from seed to seed, which is noise in the traffic
and not in the system.)

Order matters as well. A request lives for seconds and a window lasts
tens of seconds, so where the long requests fall decides how full the
system is while it is measured: a free shuffle that puts three long
answers side by side makes another run than one that spreads them. So
the deck is dealt in **blocks** (a few seconds of arrivals, or a few
requests per client): each block gets one value from every stratum of
neighbouring quantiles, and the seed decides which, and the order inside
the block. Every stretch of the run then carries nearly the same work.
"""

import math
from statistics import NormalDist


def mid_quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def _ppf(dist, u):
    kind = dist["dist"]
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    if kind == "loguniform":
        return dist["lo"] * (dist["hi"] / dist["lo"]) ** u
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    if kind == "constant":
        return dist["value"]
    raise ValueError(f"unknown distribution {kind!r}: lognormal | loguniform | exponential | constant")


def stratified(dist, n):
    """``n`` values of ``dist`` at the mid-quantiles, ascending, clipped
    to ``[min, max]`` where the distribution states them."""
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    return [min(max(_ppf(dist, u), lo), hi) for u in mid_quantiles(n)]


def stratified_lengths(dist, n):
    return [int(round(v)) for v in stratified(dist, n)]


def block_sizes(n, blocks, rng):
    """``n`` items over ``blocks`` blocks, sizes differing by at most one;
    the seed decides which blocks hold one more."""
    base, extra = divmod(n, blocks)
    sizes = [base] * blocks
    for b in rng.permutation(blocks)[:extra]:
        sizes[b] += 1
    return sizes


def deal(values, sizes, rng):
    """Ascending ``values`` dealt into blocks of the given sizes: from the
    top, each run of ``len(sizes)`` neighbouring values goes one to each
    block in seeded order; what is left (the smallest) fills the blocks
    that hold one more. Each block comes back in seeded order."""
    blocks, base = len(sizes), min(sizes)
    out = [[] for _ in sizes]
    ordered = sorted(values, reverse=True)
    for j in range(base):
        for v, b in zip(ordered[j * blocks:(j + 1) * blocks], rng.permutation(blocks)):
            out[b].append(v)
    rest = iter(ordered[base * blocks:])
    for b in rng.permutation(blocks):
        if sizes[b] > base:
            out[b].append(next(rest))
    return [[block[i] for i in rng.permutation(len(block))] for block in out]


def dealt_arrivals(dist, sizes, span_s, rng):
    """Arrival times inside ``(0, span_s)``, a block after the other: the
    stratified gaps dealt to the blocks, each block's gaps scaled to fill
    its share of the span. Every seed has the same gaps, so the same
    burstiness. → one list of times per block."""
    gaps = deal(stratified(dist, sum(sizes)), sizes, rng)
    each = span_s / len(sizes)
    out = []
    for b, block in enumerate(gaps):
        scale, t, times = each / sum(block) if block else 0.0, b * each, []
        for g in block:
            times.append(t + g * scale / 2)
            t += g * scale
        out.append(times)
    return out
