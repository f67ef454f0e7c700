"""Tests of what the ``longcat-flash-omni-ep32`` configuration and its cell
add to the benchmark: the cell rehearsed on the CPU through the unchanged
``run.py``, the reference's copy against the program's own reference, the
share-aware margin, the topics generator, the control's recipe, and the
three readers of the step records' counts on a recorded record. Like
``test_benchmark.py`` they are the benchmark's, not tier-1's
(``python -m pytest benchmark/tests -q``).
"""

import collections
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_longcat, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "longcat-flash-topics", "longcat-flash-omni-ep32"
TOPICS = ("zero_pick_share.topics", "held_rows_per_expert.topics", "held_groups_empty.topics")


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_longcat" and len(cell["why"]) <= 200
    assert sorted(config["reduced"]) == ["n_routed_experts", "num_layers", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    traffic = bench.traffic(cell["traffic"])
    assert traffic["kind"] == "closed_loop_topics" and traffic["clients"] == 256
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    layer = bench.metrics_of(CELL, "per_layer")
    assert {"mla_decode_roofline.topics", "expert_matmul_call_ms.topics", "hbm_peak.topics",
            "compile_s", *TOPICS} <= set(layer)
    assert all(name.endswith(".topics") or name == "compile_s" for name in layer)
    assert all(m["moves"] in ("serve_tok_s", "setup_s") for m in layer.values())
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    engine = config["engine"]
    assert engine["max_ragged_sequence_count"] == traffic["clients"]
    assert engine["max_context"] >= traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"]


def test_the_published_keys_are_unchanged_but_the_three_cuts():
    """Every number of the catalog's ``config`` under the same key; only
    the keys listed in ``reduced`` differ, none of them a width, and the
    file states the published counts beside them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "LongCat-Flash-Omni")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    differ = sorted(k for k, v in entry["config"].items() if model.get(k, "missing") != v)
    assert differ == sorted(config["reduced"]) == ["n_routed_experts", "num_layers", "vocab_size"]
    assert model["published"] == {k: entry["config"][k] for k in differ}
    assert model["num_layers"] >= 4 and model["n_routed_experts"] >= 8
    assert model["vocab_size"] * 8 >= entry["config"]["vocab_size"]
    assert model["share"]["expert_parallel_ranks"] * model["n_routed_experts"] == 512


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
    # the state layers, two a double layer: what the roofline reader multiplies by
    assert facts["latent_shapes"] == {"layers": 4, "heads": 4, "rank": 32, "lanes": 128,
                                      "itemsize": 2}
    assert facts["expert_share"] == {"moe_topk": 3, "expert_layers": 2, "experts_held": 4,
                                     "routed": 8, "zero": 4}
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == (3 + 5) * 21
    assert check["largest_under_tolerance"] < 0.02
    # the expert layer alone, every double layer at every compared position
    alone = check["expert_layer"]
    assert alone["agrees"] and alone["positions"] == 2 * (3 + 5) * 21
    assert alone["held_positions"] > 50 and alone["max"] < 0.01


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("longcat")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_longcat", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's
    file, the program's reads its dataclass), given the same share - the
    second half of the routed experts here: the same logits on the same
    seeded weights, to float32 rounding."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.longcat import reference_logits
    _, config, runner, engine = debug_engine
    cfg = runner["longcat_config"](config["model"])
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert_held) == (8, 4, 4)
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    mine = np.asarray(reference_longcat.logits(engine.params, jnp.asarray(ids), config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), cfg))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    at, margins = reference_longcat.logits_at(engine.params, jnp.asarray(ids),
                                              np.asarray([[3, 69], [0, 41]]), config["model"])
    at = np.asarray(at)
    assert margins.shape == (2, 2, 2) and (np.asarray(margins) > 0).all()
    assert np.allclose(at[0, 1], mine[0, 69], atol=1e-5) and np.allclose(at[1, 1], mine[1, 41],
                                                                          atol=1e-5)


def test_the_margin_counts_only_the_picks_this_share_computes():
    """A swap between two absent experts changes nothing here, so it does
    not narrow the margin; one that moves a held or a zero-compute column
    in or out does."""
    import jax.numpy as jnp
    D, routed, zero = 16, 8, 4
    kw = dict(top_k=3, scaling=6.0, routed=routed, first=2, held=2)      # held: columns 2, 3
    x = jnp.ones((1, 1, D), jnp.float32)

    def margin(logits):
        # a router whose scores are softmax(logits) for this x, no bias
        weight = jnp.zeros((D, routed + zero)).at[0].set(jnp.asarray(logits, jnp.float32))
        router = {"classifier": {"weight": weight},
                  "e_score_correction_bias": jnp.zeros(routed + zero)}
        weights, m = reference_longcat._router(x, router, **kw)
        return np.asarray(weights)[0, 0], float(m[0, 0])

    def scores(logits):
        e = np.exp(np.asarray(logits, np.float64))
        return e / e.sum()

    # chosen 0, 1, 5 (all absent); the runner-up 6 is absent too and a hair behind: the
    # margin is the lead over the best column computed here that was left out (held 2)
    logits = [5.0, 4.0, -9, -9, -9, 3.0, 2.99, -9, -9, -9, -9, -9]
    logits[2] = 1.0
    w, m = margin(logits)
    s = scores(logits)
    assert (w > 0).nonzero()[0].tolist() == [0, 1, 5]
    assert abs(m - (s[5] - s[2])) < 1e-6 and m > 50 * (s[5] - s[6])
    assert np.allclose(w[[0, 1, 5]], 6.0 * s[[0, 1, 5]], rtol=1e-5)       # unbiased, x 6, not normalised
    # the runner-up is a zero-compute column a hair behind: that is the margin
    logits = [5.0, 4.0, -9, -9, -9, 3.0, -9, -9, -9, 2.99, -9, -9]
    w, m = margin(logits)
    s = scores(logits)
    assert abs(m - (s[5] - s[9])) < 1e-6 and m < 0.01
    # a held column is the last chosen, an absent one a hair behind: that swap matters too
    logits = [5.0, 4.0, -9, 3.0, -9, -9, 2.99, -9, -9, -9, -9, -9]
    w, m = margin(logits)
    s = scores(logits)
    assert (w > 0).nonzero()[0].tolist() == [0, 1, 3] and abs(m - (s[3] - s[6])) < 1e-6


def test_the_topics_deck_is_the_same_multiset_for_every_seed_and_text_like():
    bench = spec.Benchmark(ROOT)
    params = bench.traffic("topics")
    make = bench.load("generators", params["kind"], "generate")
    vocab = bench.config(CONFIG)["model"]["vocab_size"]
    a, b = (make(params, seed, 45.0, vocab) for seed in (1, 3_000_000_019))
    assert a["loop"] == "closed" and a["clients"] == 256 and len(a["deck"]) == 512
    for key in ("topic", "max_new"):
        assert collections.Counter(r[key] for r in a["deck"]) == \
            collections.Counter(r[key] for r in b["deck"])
    assert collections.Counter(len(r["prompt"]) for r in a["deck"]) == \
        collections.Counter(len(r["prompt"]) for r in b["deck"])
    again = make(params, 1, 45.0, vocab)
    assert all((r["prompt"] == s["prompt"]).all() for r, s in zip(a["deck"], again["deck"]))
    # popularity ~ 1 / rank over the deck and in every block of 16
    by_topic = collections.Counter(r["topic"] for r in a["deck"])
    assert [by_topic[t] for t in range(8)] == [188, 94, 63, 47, 38, 31, 27, 24]
    for start in range(0, 512, 16):
        block = collections.Counter(r["topic"] for r in a["deck"][start:start + 16])
        assert 5 <= block[0] <= 6 and 2 <= block[1] <= 4 and len(block) >= 6
    # lengths: one SplitFuse chunk, about a third of the tokens prompt
    prompts = sum(len(r["prompt"]) for r in a["deck"])
    answers = sum(r["max_new"] for r in a["deck"])
    assert all(128 <= len(r["prompt"]) <= 512 and 256 <= r["max_new"] <= 1024 for r in a["deck"])
    assert 0.30 < prompts / (prompts + answers) < 0.37
    # ids: inside the slice; a topic's most frequent id makes ~ a tenth of its tokens, and
    # two topics favour different ids
    ids = {t: np.concatenate([r["prompt"] for r in a["deck"] if r["topic"] == t]) for t in (0, 1)}
    tops = {}
    for t, drawn in ids.items():
        assert drawn.min() >= 0 and drawn.max() < vocab
        (top, count), = collections.Counter(drawn.tolist()).most_common(1)
        assert 0.08 < count / len(drawn) < 0.2
        tops[t] = top
    assert tops[0] != tops[1]
    assert len(a["first_max_new"]) == 256 and min(a["first_max_new"]) >= 1


def _experts_that_weigh(engine):
    """A seeded expert 6144 wide returns about as much as it is given, one
    64 wide a thousandth: scale the debug model's down projections so that
    a held pick counts beside a zero-compute one, as it does in the cell."""
    import jax
    engine.params = jax.tree.map(lambda w: w, engine.params)
    experts = engine.params["model"]["layers"]["mlp"]["experts"]
    experts["down_proj"] = experts["down_proj"] * 1024


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_longcat
    bench, config, _, _ = debug_engine
    return control_longcat.measure(bench, config, 3000001201, rehearse=True,
                                   prepare=_experts_that_weigh)


def test_the_float8_control_comes_out_as_not_correct(controls):
    got = controls
    assert got["program"]["agrees"] and got["program"]["largest_under_tolerance"] < 0.02
    assert got["program"]["expert_layer"]["agrees"]
    assert not got["float8"]["agrees"]
    assert got["float8"]["median"] > 5 * got["program"]["median"]


@pytest.mark.parametrize("control", ["held_left_out", "held_permuted"])
def test_a_fault_in_the_held_experts_alone_comes_out_as_not_correct(controls, control):
    """The held picks' part left out, or given to the neighbouring held
    expert: the expert layer alone fails it at every position with a held
    pick and passes every other, whatever the logits make of it."""
    program, faulty = controls["program"]["expert_layer"], controls[control]["expert_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["held_positions"] == program["held_positions"] > 50
    assert faulty["held_over"] == faulty["held_positions"] == faulty["tiers"][0]["over"]
    assert faulty["held_min"] > 5 * program["max"]


# ------------------------------------------------- the readers of the step records' counts
def _record(kind, k, n_tokens, counts, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": n_tokens, "n_prompt_tokens": n_prompt,
            "counts": counts}


def _run(records, share={"moe_topk": 12, "expert_layers": 4, "experts_held": 16,
                         "routed": 512, "zero": 256}):
    """A run as the readers see it after ``readers.program_spans._serving``
    has laid the records on the trace: a burst of 8 steps of 256 rows and
    one mixed step, recorded."""
    bursts = [r for r in records if r["kind"].startswith("burst")]
    mixed = [r for r in records if r["kind"] == "put"]
    return {"trace": object(), "trace_window_s": 6.0, "facts": {"expert_share": share},
            "_program_spans": {"bursts": bursts, "mixed": mixed}}


def test_the_three_readers_on_a_recorded_record():
    load = spec.Benchmark(ROOT).load
    zero, rows, empty = (load("readers", "expert_share", fn) for fn in (
        "zero_pick_share", "held_rows_per_expert", "held_groups_empty"))
    records = [
        # 8 steps x 256 rows x 4 layers x 12 picks = 98304 picks: a third zero, 2048 held
        _record("burst", 8, 8 * 256, {"n_picks_held": 2048, "n_picks_zero": 32768,
                                      "n_groups_live": 448}),
        # one mixed step of 512 tokens: 24576 picks
        _record("put", 1, 512, {"n_picks_held": 512, "n_picks_zero": 8192, "n_groups_live": 60},
                n_prompt=256)]
    run = _run(records)
    assert zero(run, {}) == pytest.approx(100.0 * 40960 / 122880)
    # 9 model steps x 4 layers x 16 experts = 576 expert-steps: 2560 rows, 508 of them live
    assert rows(run, {}) == pytest.approx(2560 / 576)
    assert empty(run, {}) == pytest.approx(100.0 * (1 - 508 / 576))
    assert run["facts"]["expert_share_counts"]["records"] == 2
    assert run["facts"]["expert_share_counts"]["picks"] == 122880


def test_the_gate_reader_and_the_weight_copy_pattern():
    """``gate_queued.topics`` is the runner's ``facts.queued_mid``;
    ``weight_copy_share.topics`` matches the copies of stacked halves that
    the first traced run of PR 32 showed and none of the ten longest ops
    of a healthy run (op names as the chip's traces had them)."""
    import re
    bench = spec.Benchmark(ROOT)
    queued = bench.reader("gate_queued.topics")
    assert queued({"facts": {"queued_mid": 72, "high_water": {"active": 256}}}, {}) == 72
    assert queued({"facts": {}}, {}) is None and queued({}, {}) is None
    pattern = re.compile(bench.layer_metric("weight_copy_share.topics")["kernels"])
    copies = ["dynamic-slice_bitcast_fusion.47 fusion bf16[2,12288,6144]",
              "dynamic-slice_bitcast_fusion.45 fusion bf16[2,6144,12288]",
              "dynamic-slice_bitcast_fusion.43 fusion bf16[2,8192,6144]",
              "copy.3 copy bf16[16,6144,2048]", "fusion.9 fusion bf16[1,1536,12288]"]
    healthy = ["paged_mla_decode_attention.26 custom-call bf16[512,64,512]",
               "fusion.373 fusion bf16[6400,6144]", "gmm_ragged_dot.41 custom-call bf16[6400,6144]",
               "gmm_ragged_dot.39 custom-call bf16[6400,2048]", "fusion.374 fusion bf16[6144,6144]",
               "fusion.366 fusion bf16[512,12288]", "fusion.348 fusion (tuple)",
               "fusion.425 fusion bf16[512,16384]", "gmm_ragged_dot.35 custom-call bf16[3328,6144]"]
    assert all(pattern.search(name) for name in copies)
    assert not any(pattern.search(name) for name in healthy)
    pool = re.compile(bench.layer_metric("state_pool_copy_share.topics")["kernels"])
    blocks = bench.config(CONFIG)["engine"]["num_kv_blocks"]
    assert pool.search(f"scatter.1 scatter bf16[8,{blocks},256,512]")


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no counts
    (the parent's, or another model kind's) or a runner that states no
    share, the metric is left out: no raise."""
    load = spec.Benchmark(ROOT).load
    parents = [{"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0},
               {"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0, "counts": None}]
    for fn in ("zero_pick_share", "held_rows_per_expert", "held_groups_empty"):
        reader = load("readers", "expert_share", fn)
        assert reader({"trace": None, "facts": {}, "observed": {}}, {}) is None
        assert reader(_run(parents), {}) is None
        assert reader(_run([_record("burst", 8, 2048, {"n_picks_held": 1, "n_picks_zero": 1,
                                                       "n_groups_live": 1})], share=None),
                      {}) is None
