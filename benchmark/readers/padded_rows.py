"""The rows a step's program ran beside the rows that held a token.

A serving program's width is static (a ``put``'s bucket, a burst's
``max_seqs`` a step, a verify program's ``(d + 1)`` a sequence) and its
batch is not, so the engine pads. Its step records carry both numbers:
``n_rows``, the program's rows over its ``k`` steps, and ``n_tokens``, the
rows of them that held a token (``deepspeed_tpu/utils/tracing.py``;
``docs/OBSERVABILITY.md``). ``padded_row_share`` = 100 x (1 - tokens /
rows) over the engine records that **started in the ``lookback_s`` before
the traced run's window closed** - the whole window, not the traced
seconds. It says how often the paged kernels' live-row bound engages
(they do no work for a padding row: ``ops/pallas/paged_attention``), and
what every other op of the program still pays for the width.

A program whose records have no ``n_rows`` (the parent of PR 37) gives
``None`` and the metric is left out, as without a traced run.
"""

from benchmark.readers.host_time import _window

ENGINE_KINDS = ("put", "burst", "burst_async", "verify")


def _share(records):
    rows = sum(r["n_rows"] for r in records)
    return 100.0 * (1.0 - sum(r["n_tokens"] for r in records) / rows) if rows else None


def padded_row_share(run, spec):
    window = _window(run, spec)
    if window is None:
        return None
    _, recorded, lo, hi = window
    counted = [r for r in recorded["steps"] if r["kind"] in ENGINE_KINDS and r.get("n_rows")
               and lo <= r["start_ns"] <= hi]
    share = _share(counted)
    if share is None:
        return None
    by_kind = {kind: _share([r for r in counted if r["kind"] == kind])
               for kind in sorted({r["kind"] for r in counted})}
    run["facts"]["padded_rows"] = {"records": len(counted),
                                   "rows": sum(r["n_rows"] for r in counted),
                                   "tokens": sum(r["n_tokens"] for r in counted),
                                   "share_by_kind": by_kind}
    return share
