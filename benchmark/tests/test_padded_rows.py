"""The reader of ``padded_row_share`` (``benchmark/readers/padded_rows.py``)
on a synthetic ring of step records around the small recorded trace. CPU,
seconds; like ``test_host_time.py`` not part of the repo's tier-1 tree.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import program_spans as ps  # noqa: E402
from benchmark.harness import spec, trace  # noqa: E402
from benchmark.tests.test_benchmark import DATA  # noqa: E402
from benchmark.tests.test_host_time import ring_with_fields  # noqa: E402

SUFFIXES = ("tpot", "topics", "serve")
# (n_tokens, n_rows) by the ring's engine records' kind: a 128-row program of 8 steps with 40
# rows live, a 512-row mixed step that holds 300 tokens
WIDTHS = {"burst": (8 * 40, 8 * 128), "put": (300, 512)}


@pytest.fixture(scope="module")
def recorded():
    return trace.load(os.path.join(DATA, "trace_small.json.gz"))


def read(recorded_trace, monkeypatch, steps):
    monkeypatch.setattr(ps, "records", lambda: {"steps": steps, "requests": [], "events": []})
    bench = spec.Benchmark(ROOT)
    run = {"trace": recorded_trace, "trace_window_s": 0.25, "observed": {}, "facts": {}}
    return {s: bench.reader(f"padded_row_share.{s}")(run, bench.layer_metric(f"padded_row_share.{s}"))
            for s in SUFFIXES}, run["facts"]


def test_the_share_is_read_from_the_records_rows(recorded, monkeypatch):
    steps = ring_with_fields(recorded)
    for r in steps:
        if r["kind"] in WIDTHS:
            r["n_tokens"], r["n_rows"] = WIDTHS[r["kind"]]
    # two bursts and two puts began in the 45 s before the trace ended; 12 and 13 after it
    engine = [r for r in steps if r["kind"] in WIDTHS and r["seq"] <= 11]
    assert [r["kind"] for r in engine] == ["burst", "put", "burst", "put"]
    tokens, rows = (sum(r[key] for r in engine) for key in ("n_tokens", "n_rows"))
    got, facts = read(recorded, monkeypatch, steps)
    assert got == dict.fromkeys(SUFFIXES, pytest.approx(100.0 * (1 - tokens / rows)))
    mine = facts["padded_rows"]
    assert (mine["records"], mine["rows"], mine["tokens"]) == (len(engine), rows, tokens)
    assert mine["share_by_kind"]["burst"] == pytest.approx(100.0 * (1 - 40 / 128))
    assert mine["share_by_kind"]["put"] == pytest.approx(100.0 * (1 - 300 / 512))


def test_records_without_rows_give_none(recorded, monkeypatch):
    """The parent of PR 37: its records say the tokens and not the rows."""
    got, facts = read(recorded, monkeypatch, ring_with_fields(recorded))
    assert got == dict.fromkeys(SUFFIXES) and "padded_rows" not in facts
    bench = spec.Benchmark(ROOT)
    run = {"trace": None, "trace_window_s": None, "observed": {}, "facts": {}}
    assert bench.reader("padded_row_share.tpot")(
        run, bench.layer_metric("padded_row_share.tpot")) is None
