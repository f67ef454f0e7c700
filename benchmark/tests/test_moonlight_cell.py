"""Tests of what the ``moonlight-16b-a3b`` configuration and its cell add
to the benchmark: the cell rehearsed on the CPU through the unchanged
``run.py``, the reference's copy against the program's own reference, the
control's recipe, and the roofline reader's arithmetic. Like
``test_benchmark.py`` they are the benchmark's, not tier-1's
(``python -m pytest benchmark/tests -q``).
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_moonlight, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "moonlight16b-longgen", "moonlight-16b-a3b"


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_moonlight"
    assert config["reduced"] == ["num_hidden_layers"]
    assert bench.traffic(cell["traffic"])["kind"] == "closed_loop"
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    layer = bench.metrics_of(CELL, "per_layer")
    assert {"mla_decode_roofline.longgen", "ctx_tokens_per_step.longgen",
            "state_pool_copy_share.longgen", "expert_matmul_share.longgen",
            "compile_s"} <= set(layer)
    assert all(m["moves"] in ("serve_tok_s", "setup_s") for m in layer.values())
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1


def test_the_published_keys_are_unchanged_but_the_depth():
    """Every number of the catalog's ``config`` under the same key; only
    ``num_hidden_layers`` differs, and it is listed."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Moonlight-16B-A3B")
    model = spec.Benchmark(ROOT).config(CONFIG)["model"]
    differ = [k for k, v in entry["config"].items() if model.get(k, "missing") != v]
    assert differ == ["num_hidden_layers"]
    assert model["num_hidden_layers"] >= 1 + 8 and model["first_k_dense_replace"] == 1


def test_rehearsal_at_debug_size_on_the_cpu(tmp_path):
    out = run_cell(rehearsal_root(tmp_path), CELL, "--rehearse", "--seconds", "3")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert {"setup_s", "serve_tok_s"} <= set(line["rehearsal"]["metrics"])
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["state_kind"] == "latent"
    assert set(facts["attention_impls"].values()) == {"xla_gather_mla"}
    assert facts["latent_shapes"] == {"layers": 3, "heads": 4, "rank": 32, "lanes": 128,
                                      "itemsize": 2}
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == (3 + 5) * 21
    assert check["largest_under_tolerance"] < 0.02


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("moonlight")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_moonlight", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's
    file, the program's reads its dataclass): the same logits on the
    same seeded weights, to float32 rounding."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.moonlight import reference_logits
    _, config, runner, engine = debug_engine
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    mine = np.asarray(reference_moonlight.logits(engine.params, jnp.asarray(ids),
                                                 config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids),
                                         runner["moonlight_config"](config["model"])))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    at, margins = reference_moonlight.logits_at(engine.params, jnp.asarray(ids),
                                                np.asarray([[3, 69], [0, 41]]), config["model"])
    at = np.asarray(at)
    # a margin per expert layer and compared position: the 3rd over the 4th of score + bias
    assert margins.shape == (2, 2, 2) and (np.asarray(margins) > 0).all()
    assert np.allclose(at[0, 1], mine[0, 69], atol=1e-5) and np.allclose(at[1, 1], mine[1, 41],
                                                                          atol=1e-5)


def test_the_check_feeds_the_long_sequence_over_several_chunks(debug_engine):
    _, config, runner, engine = debug_engine
    steps = config["reference"]["decode_steps"]
    lengths = runner["sample_lengths"](config["reference"])
    assert lengths == [12, 40, 100] + [12] * 5
    seqs, positions, batches = runner["reference_sample"](config, 7)
    assert [len(s) for s in seqs] == [n + steps for n in lengths]
    assert positions.shape == (8, 1 + steps) and positions[2, 0] == 99
    # the reference runs the three lengths padded to the longest, the short ones apart
    assert [(first, ids.shape) for first, ids in batches] == [(0, (3, 100 + steps)),
                                                               (3, (5, 12 + steps))]
    puts = []
    put = engine.put
    engine.put = lambda uids, toks, *a, **k: puts.append([len(t) for t in toks]) or put(
        uids, toks, *a, **k)
    try:
        errs, ok = runner["reference_check"](engine, config, 7)
    finally:
        del engine.put
    budget = config["engine"]["token_budget"]
    prefill = [p for p in puts if max(p) > 1]
    assert len(prefill) >= 3 and all(sum(p) <= budget for p in prefill)
    assert puts[-steps:] == [[1] * 8] * steps
    assert ok and errs["largest_under_tolerance"] < 0.02 and errs["flipped_share"] < 0.05


def test_every_position_is_judged_and_the_margin_says_how_many_may_differ():
    """A position over the tolerance is a routing flip or a fault: the
    share of them is bounded over all positions, more tightly among those
    the reference's margin calls firm, and in every sequence alone."""
    summarize = spec.Benchmark(ROOT).load("runners", "serve_moonlight", "summarize")
    reference = {"tolerance": 0.06, "flipped_share_max": [[0.0, 0.5], [0.004, 0.18],
                                                           [0.0085, 0.08]],
                 "flipped_share_max_a_sequence": 0.75}
    rng = np.random.default_rng(0)
    margins = np.tile(np.linspace(0.0001, 0.0199, 100), (4, 1))     # 40 % over 0.004 ...
    clean = np.full((4, 100), 0.017)
    assert summarize(clean, margins, reference)["agrees"]
    flipped = clean.copy()
    flipped[:, :20:2] = 0.4                  # a tenth, all of them of small margin
    got = summarize(flipped, margins, reference)
    assert got["agrees"] and got["flipped_share"] == 0.1
    assert [t["over"] for t in got["tiers"]] == [40, 0, 0]
    assert got["largest_under_tolerance"] == 0.017 and got["max"] == 0.4
    # the same number of positions moved, but firm ones: not what a flip does
    firm = clean.copy()
    firm[:, -10:] = 0.4
    assert not summarize(firm, margins, reference)["agrees"]
    # every position moved (a lower precision, a fault in the mathematics)
    assert not summarize(np.full((4, 100), 0.13), margins, reference)["agrees"]
    # too many positions moved, wherever they lie
    many = clean.copy()
    many[rng.random((4, 100)) < 0.6] = 0.3
    assert not summarize(many, margins, reference)["agrees"]
    # one sequence moved whole (say, only the one prefilled over several chunks)
    one = clean.copy()
    one[2, :80] = 0.3
    got = summarize(one, margins, reference)
    assert not got["agrees"] and got["flipped_share_by_sequence_max"] == 0.8
    # a tier with no position in it cannot be judged
    assert not summarize(clean, margins * 0.1, reference)["agrees"]


def test_the_float8_control_comes_out_as_not_correct(debug_engine):
    from benchmark.tests import control_moonlight
    bench, config, _, _ = debug_engine
    got = control_moonlight.measure(bench, config, 3000001201, rehearse=True)
    assert got["program"]["agrees"] and got["program"]["largest_under_tolerance"] < 0.02
    assert not got["float8"]["agrees"] and got["float8"]["flipped_share"] > 0.7
    assert got["float8"]["median"] > 5 * got["program"]["median"]
    # the router alone in bfloat16 moves only the positions it flips: the others agree to
    # float32 rounding, and at this size the reading cannot tell it from the program
    assert got["bf16_router"]["median"] < 1e-3 < got["bf16_router"]["max"]


def test_the_rooflines_bytes_and_operations_against_a_hand_count():
    load = spec.Benchmark(ROOT).load
    kernel_bytes = load("readers", "mla_roofline", "kernel_bytes")
    kernel_flops = load("readers", "mla_roofline", "kernel_flops")
    # one sequence with 1000 tokens of context decodes one token through 9 layers of
    # Moonlight's shapes: 1000 rows of 640 bf16 values, 16 query rows of 640 in, 16 output
    # rows of 512 out, per layer
    rows, query, out = 1000 * 640 * 2, 16 * 640 * 2, 16 * 512 * 2
    assert rows == 1_280_000 and query == 20_480 and out == 16_384
    assert kernel_bytes(1000, 1, 9, 16, 512, 128, 2) == 9 * (rows + query + out) == 11_851_776
    # per context row and head: 640 multiply-adds of scores and 512 of values
    assert kernel_flops(1000, 9, 16, 512, 128) == 9 * 1000 * 16 * 2 * (640 + 512) == 331_776_000
    # 28 operations a byte: under the chip's 197e12 / 819e9 = 240, so the bound is HBM
    assert kernel_flops(1000, 9, 16, 512, 128) / kernel_bytes(1000, 1, 9, 16, 512, 128, 2) < 30


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """On a run without a trace, or of a program whose records carry no
    ``n_ctx_tokens`` (the parent's), the metric is left out: no raise."""
    load = spec.Benchmark(ROOT).load
    for reader in ("decode_roofline", "ctx_tokens_per_step"):
        fn = load("readers", "mla_roofline", reader)
        assert fn({"trace": None, "facts": {}, "observed": {}}, {}) is None
        assert fn({"trace": None, "facts": {"latent_shapes": {}}, "trace_window_s": 6.0}, {}) is None
