"""What the paged latent (MLA) decode kernel fetches of what its rows name.

The program counts on the device, once a step of a model kind with a
latent state (``model_runner.LATENT_FETCH_COUNTS``), over all the rows of
the step's program, padding rows included: the blocks their contexts name
(``n_blocks_named``: ``pos // block_size + 1`` a row) and those of them
one call of ``paged_mla_decode_attention`` starts a copy for
(``n_blocks_fetched``: ``paged_mla_attention.fetch_counts``, the kernel's
fetch rule as a pure function of the step's tables and positions; the
same for every state layer, so the counts are one call's). A row whose
first tile the slot already holds — a run of padding rows on the null
block, a prompt chunk's consecutive tokens — fetches nothing or only the
blocks its predecessor lacked. They ride out with the step's result into
its step record (``counts``).

``mla_fetch_share`` = 100 x fetched / named over the step records that
started inside the traced window. A kernel that fetches every named block
for every row (the parent of PR 35) would read 100; the parent's records
carry no such counts, and the reader then returns ``None`` (the metric is
left out), as it does without a traced run.
"""

from benchmark.readers.program_spans import _serving

COUNTS = ("n_blocks_named", "n_blocks_fetched")


def _counted(records):
    return [r for r in records if r.get("counts") and all(c in r["counts"] for c in COUNTS)]


def _share(records):
    """→ (named, fetched, 100 x fetched / named or None) of counted records."""
    named = sum(r["counts"]["n_blocks_named"] for r in records)
    fetched = sum(r["counts"]["n_blocks_fetched"] for r in records)
    return named, fetched, 100.0 * fetched / named if named else None


def fetch_share(run, spec):
    found = _serving(run)
    if found is None:
        return None
    groups = {"burst": _counted(found["bursts"]), "mixed": _counted(found["mixed"])}
    named, fetched, share = _share(groups["burst"] + groups["mixed"])
    if share is None:
        return None
    by_kind = {kind: _share(group)[2] for kind, group in groups.items() if group}
    run["facts"]["mla_fetch"] = {"records": sum(len(g) for g in groups.values()),
                                 "blocks_named": named, "blocks_fetched": fetched,
                                 "share_by_kind": by_kind}
    return share
