"""Tests of what the ``laguna-xs2-ep8-20l`` configuration and its cell add to
the benchmark: the cell rehearsed on the CPU through the unchanged
``run.py`` (traced, so that the cell's own metric files are read), the
reference's copy against the program's own reference, the controls' recipe,
and the readers of the step records' counts on a recorded record. Like
``test_benchmark.py`` they are the benchmark's, not tier-1's (``python -m
pytest benchmark/tests -q``).
"""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_laguna, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "laguna-xs2-repochat", "laguna-xs2-ep8-20l"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
           "num_experts"]
# the metrics a device trace alone can give: a CPU rehearsal's trace holds no device op
DEVICE_TRACE = {"window_attn_roofline.repochat", "paged_attn_roofline.repochat",
                "window_attn_share.repochat", "paged_attn_share.repochat",
                "expert_matmul_share.repochat", "device_idle.repochat"}


def _repochat_metrics():
    return spec.Benchmark(ROOT).load("runners", "serve_laguna", "run").__globals__[
        "REPOCHAT_METRICS"]


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_laguna" and len(cell["why"]) <= 200
    assert len(bench.configs[CONFIG]["why"]) <= 200 and len(bench.workloads[CELL]["why"]) <= 200
    assert config["reduced"] == bench.configs[CONFIG]["reduced"] == REDUCED
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "an eighth of a deployment's rows" in config["reduced_why"]["num_experts"]
    assert "3,159,284,480" in config["reduced_why"]["num_hidden_layers"]
    assert {"gate_a_head", "router", "no_qk_norm", "yarn", "not_read", "dtype",
            "seeded_parameters"} <= set(config["assumed"])
    assert "33.44 B" in config["assumed"]["gate_a_head"]
    assert "no code stands in for the seven absent ranks" in config["deployment"]
    assert "16 TPU v5e chips" in config["deployment"]
    assert config["model"]["published"]["num_hidden_layers"] == 40
    assert config["model"]["published"]["num_experts"] == 256
    assert config["model"]["share"] == {"expert_parallel_ranks": 8, "rank": 0,
                                        "first_expert_held": 0, "pipeline_stages": 2, "stage": 0,
                                        "published_layers": [0, 19]}
    traffic = bench.traffic(cell["traffic"])
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 48
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 512, "hi": 16384}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 128, "hi": 1024}
    assert (traffic["cycle_requests"], traffic["block_requests"], traffic["preroll_s"],
            traffic["tail_s"]) == (96, 16, 30.0, 8.0)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    # BENCHMARK.json's per_layer holds the 128 metrics it may hold: the cell's own are
    # files the runner reads into facts, and enters none
    assert len(bench.doc["per_layer"]) == 128
    assert set(bench.metrics_of(CELL, "per_layer")) == {"compile_s"}
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    # appended after the accepted entries (not "is the last": the next cell's PR appends too)
    assert [w["name"] for w in bench.doc["workloads"]].index(CELL) >= 10
    assert [c["name"] for c in bench.doc["configs"]].index(CONFIG) >= 10
    engine = config["engine"]
    assert engine["token_budget"] == 512 and engine["kv_block_size"] == 64
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] \
        == traffic["clients"]
    assert engine["max_context"] == (traffic["prompt_tokens"]["hi"]
                                     + traffic["output_tokens"]["hi"]) == 17408
    # the full pool is laid for the mean request, not for 48 worst cases: the gate holds the rest
    worst = traffic["clients"] * (engine["max_context"] // engine["kv_block_size"])
    assert worst // 4 < engine["num_kv_blocks"] - 1 < worst // 2
    # the window pool holds every sequence's bound and a step's rows, whatever the lengths
    from deepspeed_tpu.inference.v2.ragged.kv_cache import WindowPool
    pool = WindowPool(config["model"]["sliding_window"], engine["kv_block_size"],
                      engine["token_budget"], engine["num_window_blocks"])
    assert (pool.bound(1), pool.bound(512), pool.ring) == (9, 17, 17)
    assert engine["num_window_blocks"] - 1 >= traffic["clients"] * (pool.bound(1) + 1) + 8
    # both programs' full-layer tables fit the paged kernel's SMEM budget, with tiles; groups
    # of 6 and 8
    from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported, smem_table_fits
    model = config["model"]
    for rows in (engine["token_budget"], engine["max_ragged_sequence_count"]):
        assert smem_table_fits(rows, engine["max_context"] // engine["kv_block_size"], tiles=True)
    assert kernel_supported(model["head_dim"], engine["kv_block_size"],
                            model["num_key_value_heads"])
    assert {h // model["num_key_value_heads"]
            for h in model["num_attention_heads_per_layer"]} == {6, 8}


def test_the_cells_own_metric_files_are_whole_and_name_readers_that_load():
    bench = spec.Benchmark(ROOT)
    names = _repochat_metrics()
    assert len(names) == 13 and all(n.endswith(".repochat") and spec.NAME.match(n) for n in names)
    assert {"window_attn_roofline.repochat", "paged_attn_roofline.repochat",
            "window_blocks_per_seq.repochat", "gate_queued.repochat"} <= set(names)
    assert sorted(f[:-5] for f in os.listdir(bench.path("layer_metrics"))
                  if f.endswith(".repochat.json")) == sorted(names)
    layers = {m["layer"] for m in bench.doc["per_layer"]}
    for name in names:
        assert name not in bench.per_layer
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            metric = json.load(f)
        assert metric["cells"] == [CELL] and metric["moves"] == "serve_tok_s"
        assert metric["layer"] in layers and spec.UNIT.match(metric["unit"])
        assert metric["source"] in spec.SOURCES and metric["better"] in ("lower", "higher")
        assert (metric["source"] == "device_trace") == (name in DEVICE_TRACE)
        module, _, attr = metric["reader"].partition(":")
        assert callable(bench.load("readers", module.partition(".")[2], attr))
        if name.endswith("_roofline.repochat"):
            assert metric["unit"] == "%" and metric["attention"] in ("window", "full")


def test_every_published_key_is_unchanged_but_the_five_that_are_reduced():
    """Every number of the catalog's ``config`` under the same key, the
    nested group copied whole, except what ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Laguna-XS.2")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    differs = [k for k, v in entry["config"].items() if model.get(k, "missing") != v]
    assert sorted(differs) == sorted(config["reduced"])
    assert {k: entry["config"][k] for k in config["reduced"]} == model["published"]
    for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
        assert model[key] == entry["config"][key][:20]          # cut, no entry changed
    assert model["num_experts"] * 8 == 256 and model["vocab_size"] == 100352
    widths = [k for k in config["reduced"]
              if k.endswith(("_dim", "_rank", "_size")) or "per_tok" in k]
    assert widths == []


def test_the_programs_config_and_count_are_the_files():
    import jax
    from deepspeed_tpu.models.laguna import LAGUNA_CONFIGS, param_shapes
    bench = spec.Benchmark(ROOT)
    config = bench.config(CONFIG)
    cfg = bench.load("runners", "serve_laguna", "run").__globals__["laguna_config"](config["model"])
    assert cfg == LAGUNA_CONFIGS["laguna-xs2-ep8-20l"]
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 3_159_284_480


@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    out = run_cell(rehearsal_root(tmp_path_factory.mktemp("laguna-run")), CELL, "--rehearse",
                   "--seconds", "8", "--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_at_debug_size_on_the_cpu(traced_rehearsal):
    line = traced_rehearsal
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert set(line["rehearsal"]["metrics"]) == {"compile_s"}           # the traced line's
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["state_kind"] == "kv+window"
    assert set(facts["attention_impls"].values()) == {"xla_gather"}
    assert facts["laguna_shapes"] == {"full_layers": 3, "window_layers": 9, "kv_row_bytes": 128,
                                      "window": 8, "block_size": 4, "sequences": 8}
    assert facts["expert_share"] == {"moe_topk": 4, "expert_layers": 11, "experts_held": 8,
                                     "routed": 16, "zero": 0}
    assert set(facts["state_extra_bytes"]) == {"wk", "wv"}
    assert facts["window"]["first_tokens"] > 0 and facts["tpot_by_request"] == []
    pool = facts["window_pool"]
    # every block came back; the run's high water is a live sequence's bound, not its length
    assert pool["in_use"] == 0 and pool["released"] > 0 and pool["ring_columns"] == 19
    assert 0 < pool["high_water"] <= 8 * (3 + 1) + 64 // 4
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == (3 + 3) * 13
    held_short = check["window_pool"]
    assert held_short["blocks_given_again"] > 0 and held_short["in_use_after"] == 0
    assert held_short["blocks_free"] == 40 and held_short["high_water"] <= 40
    window, full = check["window_attention_layer"], check["full_attention_layer"]
    assert window["agrees"] and window["rows"] == 152 and window["positions"] == 9 * 152
    assert full["agrees"] and full["positions"] == 3 * 152
    experts = check["expert_layer"]
    assert experts["agrees"] and experts["positions"] == 11 * 78 and experts["held_positions"] > 0


def test_every_metric_file_the_host_can_read_gives_a_number_in_a_traced_rehearsal(
        traced_rehearsal):
    """The traced debug run reads every ``.repochat`` file whose source is
    the program's counters, spans or the host: a number each, under
    ``facts.layer_metrics_repochat``. The six that need device ops are read
    on the chip (a CPU trace holds none: their readers return nothing and do
    not raise), and on a recorded record below."""
    read = traced_rehearsal["facts"]["layer_metrics_repochat"]
    assert set(read) == set(_repochat_metrics()) - DEVICE_TRACE - {"hbm_peak.repochat"}
    assert all(np.isfinite(m["value"]) for m in read.values())
    assert 0 < read["held_rows_per_expert.repochat"]["value"] < 8 * 4
    assert 0 < read["window_blocks_per_seq.repochat"]["value"] <= 3 + 1 + 64 / 4 / 8


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("laguna")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_laguna", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's file,
    the program's reads its dataclass): the same logits on the same seeded
    weights, to float32 rounding, given the same share; and the same output
    of an attention layer of either kind and of a routed feed-forward."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.laguna import (FULL, WINDOW, reference_attention, reference_logits,
                                             reference_moe)
    _, config, runner, engine = debug_engine
    cfg = runner["laguna_config"](config["model"])
    assert (cfg.held, cfg.first_expert_held, cfg.num_experts) == (8, 8, 16)
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    taps = []
    at = np.asarray([[3, 69], [0, 10]])
    h, margins, inputs = reference_laguna.hidden(
        engine.params, jnp.asarray(ids), config["model"], positions=at,
        tap=lambda kind, i, x, y: taps.append((kind, i, np.asarray(x), np.asarray(y))))
    mine = np.asarray(reference_laguna.head_at(engine.params, h, config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), cfg))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    assert [t[:2] for t in taps] == [(FULL, 0), (WINDOW, 0), (WINDOW, 1), (WINDOW, 2), (FULL, 1),
                                     (WINDOW, 3), (WINDOW, 4), (WINDOW, 5), (FULL, 2),
                                     (WINDOW, 6), (WINDOW, 7), (WINDOW, 8)]
    assert margins.shape == (11, 2, 70) and inputs.shape == (11, 2, 2, 64)

    def rel(have, ref):
        return np.linalg.norm(have - np.asarray(ref)) / np.linalg.norm(np.asarray(ref))

    for tap, stack in ((taps[6], "window_layers"), (taps[4], "full_layers")):
        kind, i, x, y = tap
        lp = jax.tree.map(lambda w: w[i], engine.params["model"][stack])
        with jax.default_matmul_precision("highest"):
            assert rel(y, reference_attention(lp, jnp.asarray(x), cfg, kind)) < 1e-5
        assert rel(reference_laguna.attention_at(engine.params, kind, i, x[0], config["model"]),
                   y[0]) < 1e-5
    fp = jax.tree.map(lambda w: w[6], engine.params["model"]["moe"])
    got, held = reference_laguna.experts_at(engine.params, 6, inputs[6], config["model"])
    with jax.default_matmul_precision("highest"):
        assert rel(got, reference_moe(fp, inputs[6], cfg)) < 1e-5
    assert held.shape == (2, 2) and float(held.min()) >= 0.0
    rows, margins = reference_laguna.rows_at(engine.params, jnp.asarray(ids), at, config["model"])
    assert rows.shape == (2, 2, 64) and margins.shape == (11, 2, 2)
    # the two files compute YaRN's frequencies apart
    for kind in (FULL, WINDOW):
        inv, factor = reference_laguna.rope_of(config["model"], kind)
        theirs_inv, theirs_factor = cfg.rope(kind)
        assert np.allclose(inv, theirs_inv, rtol=1e-6) and factor == pytest.approx(theirs_factor)


def test_the_traffic_is_issue_52s_and_draws_from_the_whole_vocabulary():
    bench = spec.Benchmark(ROOT)
    params, vocab = bench.traffic("repochat"), bench.config(CONFIG)["model"]["vocab_size"]
    make = bench.load("generators", params["kind"], "generate")
    a, b = (make(params, seed, 45.0, vocab) for seed in (3000000019, 7))
    assert len(a["deck"]) == 96 and a["clients"] == 48 and a["preroll_s"] == 30.0
    assert all(512 <= len(r["prompt"]) <= 16384 and 128 <= r["max_new"] <= 1024
               for r in a["deck"])
    prompts = sum(len(r["prompt"]) for r in a["deck"]) / 96
    answers = sum(r["max_new"] for r in a["deck"]) / 96
    assert 4300 < prompts < 4900 and 400 < answers < 460       # ~91 % of the tokens are prompts
    assert 0.90 < prompts / (prompts + answers) < 0.93
    assert sorted(len(r["prompt"]) for r in a["deck"]) == sorted(len(r["prompt"])
                                                                 for r in b["deck"])
    top = max(int(r["prompt"].max()) for r in a["deck"])
    assert 100000 < top < vocab == 100352
    assert len(a["first_max_new"]) == 48                       # the starts staggered


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_laguna
    bench, config, _, _ = debug_engine
    return control_laguna.measure(bench, config, 3000001201, rehearse=True)


def test_the_program_comes_out_as_correct_and_the_float8_control_as_not(controls):
    got = controls
    program = got["program"]
    assert program["agrees"] and all(program[k]["agrees"] for k in (
        "window_attention_layer", "full_attention_layer", "expert_layer"))
    assert got["window_pool"]["blocks_given_again"] > 0
    assert not got["float8"]["agrees"]
    assert got["float8"]["median"] > 2 * program["max"]


def test_a_window_layer_that_reads_too_much_or_too_little_comes_out_as_not_correct(controls):
    program = controls["program"]["window_attention_layer"]
    for name in ("unwindowed", "window_short"):
        faulty = controls[name]["window_attention_layer"]
        assert not faulty["agrees"]
        # every row from the window on fails; the rows before it are the reference's own
        assert faulty["from_window_min"] > 2 * program["max"]
        assert faulty["before_window_max"] <= program["max"]


def test_a_gateless_attention_another_rotation_and_a_dropped_pick_come_out_as_not_correct(
        controls):
    program = controls["program"]
    for kind in ("window_attention_layer", "full_attention_layer"):
        gateless = controls["gateless"][kind]
        assert not gateless["agrees"] and gateless["min"] > 10 * program[kind]["max"]
    rotary = controls["full_rotary"]["full_attention_layer"]
    assert not rotary["agrees"] and rotary["max"] > 2 * program["full_attention_layer"]["max"]
    assert "window_attention_layer" not in controls["full_rotary"]
    # (the debug preset's attention factor is 1.14: its control is read on the chip, at 1.42)
    assert controls["no_attention_factor"]["full_attention_layer"]["max"] \
        > program["full_attention_layer"]["max"]
    dropped = controls["held_left_out"]["expert_layer"]
    assert not dropped["agrees"] and dropped["held_min"] > 2 * program["expert_layer"]["held_max"]


# ------------------------------------------------- the readers of the step records' counts
def _record(kind, k, n_tokens, counts, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": n_tokens, "n_prompt_tokens": n_prompt,
            "counts": counts}


def _reader(name):
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", f"{name}.json")) as f:
        module, _, attr = json.load(f)["reader"].partition(":")
    return bench.load("readers", module.partition(".")[2], attr)


def _spec(name):
    with open(spec.Benchmark(ROOT).path("layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no such
    count (the parent's, or another model kind's) or a runner that states
    no shapes, the metric is left out: no raise."""
    for name in _repochat_metrics():
        if name != "hbm_peak.repochat":
            assert _reader(name)({"trace": None, "facts": {}, "observed": {}},
                                 {**_spec(name), "observed": "x", "kernels": "y"}) is None, name
    blocks = _reader("window_blocks_per_seq.repochat")
    assert blocks({"facts": {"window_pool": {"high_water": 0},
                             "laguna_shapes": {"sequences": 48}}}, {}) is None
    assert blocks({"facts": {"window_pool": {"high_water": 470},
                             "laguna_shapes": {"sequences": 48}}}, {}) == pytest.approx(470 / 48)
    roofline = _reader("window_attn_roofline.repochat")
    run = {"trace": object(), "trace_window_s": 6.0, "facts": {},
           "_program_spans": {"bursts": [], "mixed": [], "offset_ns": 0}}
    assert roofline(run, _spec("window_attn_roofline.repochat")) is None
    assert "attention_roofline" not in run["facts"]


def test_the_least_bytes_are_a_window_a_sequence_a_layer():
    from benchmark.readers import laguna
    # a decode step of 48 sequences past the window: 48 x 512 positions x 15 layers x 4 KB
    assert laguna.attention_bytes(48 * 512, 15, 4096) == 1_509_949_440
    # ISSUE 52's reckoning: ~1.7 GB of window reads and ~5.9 GB of full reads a mixed step
    assert round(laguna.attention_bytes(48 * 576, 15, 4096) / 1e9, 1) == 1.7
    assert round(laguna.attention_bytes(48 * 6000, 5, 4096) / 1e9, 1) == 5.9
    # the census' count (tools/kernel_census.py --window) is the same function
    from tools import kernel_census
    assert laguna.attention_bytes(512, 1, 4096) == kernel_census.window_bytes(512, 4096)
    assert set(laguna.KINDS) == {"window", "full"}
    assert laguna.KINDS["window"][0] == "n_win_seq_tokens"


def test_the_share_patterns_tell_the_two_kinds_of_call_apart():
    window = re.compile(_spec("window_attn_share.repochat")["kernels"])
    full = re.compile(_spec("paged_attn_share.repochat")["kernels"])
    assert window.search("paged_window_attention.7 custom-call bf16[512,64,128]")
    assert not window.search("paged_decode_attention.3 custom-call bf16[512,48,128]")
    assert full.search("paged_decode_attention.3 custom-call bf16[512,48,128]")
    assert not full.search("paged_window_attention.7 custom-call bf16[512,64,128]")
    from benchmark.readers import laguna
    assert laguna.KINDS["window"][2].match("paged_window_attention.12")
    assert not laguna.KINDS["full"][2].match("paged_window_attention.12")
