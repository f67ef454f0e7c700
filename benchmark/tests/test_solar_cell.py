"""Tests of what the ``solar-open2-ep8-4l`` configuration and its cell add to
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

from benchmark.harness import reference_solar, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "solar-open2-reason", "solar-open2-ep8-4l"
# the metrics a device trace alone can give: a CPU rehearsal's trace holds no device op
DEVICE_TRACE = {"kda_state_roofline.reason", "kda_share.reason", "expert_matmul_share.reason",
                "paged_attn_share.reason", "device_idle.reason"}


def _reason_metrics():
    return spec.Benchmark(ROOT).load("runners", "serve_solar", "run").__globals__[
        "REASON_METRICS"]


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_solar" and len(cell["why"]) <= 200
    assert len(bench.configs[CONFIG]["why"]) <= 200 and len(bench.workloads[CELL]["why"]) <= 200
    assert config["reduced"] == bench.configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert "an eighth of a deployment's rows" in config["reduced_why"]["n_routed_experts"]
    assert "larger than a deployment's twelve stages" in config["reduced_why"]["num_hidden_layers"]
    assert {"kda_inner_forms", "gqa_gate", "router", "parameter_count", "not_read",
            "state_dtype", "dtype", "seeded_parameters"} <= set(config["assumed"])
    assert "no code stands in for the seven absent ranks" in config["deployment"]
    assert config["model"]["published"] == {"num_hidden_layers": 48,
                                            "gqa_layers": list(range(0, 48, 4)),
                                            "n_routed_experts": 320, "vocab_size": 196608}
    assert config["model"]["share"]["expert_parallel_ranks"] == 8
    assert config["model"]["share"]["published_layers"] == [4, 7]
    traffic = bench.traffic(cell["traffic"])
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 192
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 256, "hi": 2048}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 1024, "hi": 3072}
    assert (traffic["cycle_requests"], traffic["block_requests"], traffic["preroll_s"],
            traffic["tail_s"]) == (384, 16, 20.0, 8.0)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    # BENCHMARK.json's per_layer holds the 128 metrics it may hold: the cell's own are
    # files the runner reads into facts, and enters none
    assert len(bench.doc["per_layer"]) == 128
    assert set(bench.metrics_of(CELL, "per_layer")) == {"compile_s"}
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    # appended after the accepted entries (not "is the last": the next cell's PR appends too)
    assert [w["name"] for w in bench.doc["workloads"]].index(CELL) >= 9
    assert [c["name"] for c in bench.doc["configs"]].index(CONFIG) >= 9
    engine = config["engine"]
    assert engine["token_budget"] == 512 and engine["kv_block_size"] == 64
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] \
        == traffic["clients"]
    assert engine["max_context"] == (traffic["prompt_tokens"]["hi"]
                                     + traffic["output_tokens"]["hi"]) == 5120
    # the pool is laid for the mean request, not for 192 worst cases: the gate holds the rest
    worst = traffic["clients"] * (engine["max_context"] // engine["kv_block_size"])
    assert worst // 2 < engine["num_kv_blocks"] - 1 < worst
    # both programs' tables fit the paged kernel's SMEM budget, with tiles; a group of 8; the
    # delta rule's kernel takes both programs' shapes
    from deepspeed_tpu.ops.pallas.kda import kernel_supported as rule_supported
    from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported, smem_table_fits
    model = config["model"]
    linear = model["linear_attn_config"]
    for rows in (engine["token_budget"], engine["max_ragged_sequence_count"]):
        assert smem_table_fits(rows, engine["max_context"] // engine["kv_block_size"], tiles=True)
        assert rule_supported((3, 193, linear["num_heads"], linear["head_dim"],
                               linear["head_dim"]), rows, 193)
    assert kernel_supported(model["head_dim"], engine["kv_block_size"],
                            model["num_key_value_heads"])
    assert model["num_attention_heads"] // model["num_key_value_heads"] == 8


def test_the_cells_own_metric_files_are_whole_and_name_readers_that_load():
    bench = spec.Benchmark(ROOT)
    names = _reason_metrics()
    assert len(names) == 15 and all(n.endswith(".reason") and spec.NAME.match(n) for n in names)
    assert {"kda_state_roofline.reason", "kda_share.reason", "state_slots_per_step.reason",
            "scan_runs_per_step.reason", "gate_queued.reason"} <= set(names)
    assert sorted(f[:-5] for f in os.listdir(bench.path("layer_metrics"))
                  if f.endswith(".reason.json")) == sorted(names)
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
        if name.endswith("_roofline.reason"):
            assert metric["unit"] == "%"


def test_every_published_key_is_unchanged_but_the_four_that_are_reduced():
    """Every number of the catalog's ``config`` under the same key, the
    nested group copied whole, except what ``reduced`` lists."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Solar-Open2-250B")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    differs = [k for k, v in entry["config"].items() if model.get(k, "missing") != v]
    assert sorted(differs) == sorted(config["reduced"])
    assert {k: entry["config"][k] for k in config["reduced"]} == model["published"]
    assert reference_solar.layer_kinds(model) == "gkkk"                  # one whole period
    assert model["n_routed_experts"] >= 8 and model["vocab_size"] * 8 == 196608
    widths = [k for k in config["reduced"]
              if k.endswith(("_dim", "_rank", "_size")) or "per_tok" in k]
    assert widths == ["vocab_size"]                     # rows of a table, no width of the model


def test_the_programs_config_and_count_are_the_files():
    import jax
    from deepspeed_tpu.models.solar_open2 import SOLAR_OPEN2_CONFIGS, param_shapes
    bench = spec.Benchmark(ROOT)
    config = bench.config(CONFIG)
    cfg = bench.load("runners", "serve_solar", "run").__globals__["solar_config"](config["model"])
    assert cfg == SOLAR_OPEN2_CONFIGS["solar-open2-ep8-4l"]
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert round(count / 1e9, 2) == 3.31 and "3.31 B" in config["reduced_why"]["num_hidden_layers"]


@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    out = run_cell(rehearsal_root(tmp_path_factory.mktemp("solar-run")), CELL, "--rehearse",
                   "--seconds", "8", "--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_at_debug_size_on_the_cpu(traced_rehearsal):
    line = traced_rehearsal
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert set(line["rehearsal"]["metrics"]) == {"compile_s"}           # the traced line's
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["state_kind"] == "kv+slots"
    assert set(facts["attention_impls"].values()) == {"xla_gather"}
    assert set(facts["state_step_impls"].values()) == {"xla"}
    assert facts["solar_shapes"] == {"kda_layers": 6, "attn_layers": 2, "heads": 4,
                                     "head_dim": 16, "state_itemsize": 4, "slots": 8}
    assert facts["expert_share"] == {"moe_topk": 4, "expert_layers": 8, "experts_held": 8,
                                     "routed": 16, "zero": 0}
    # a slot is both entries': 6 layers x (4 x 16 x 16 float32 + 3 rows of 192 bf16)
    assert facts["slot_bytes"] == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert set(facts["state_extra_bytes"]) == {"conv", "kda"}
    assert facts["window"]["first_tokens"] > 0 and facts["tpot_by_request"] == []
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == (3 + 3) * 13
    kda = check["kda_layer"]
    assert kda["agrees"] and kda["rows"] == 152 and kda["positions"] == 6 * 152
    assert kda["state_max"] < 0.0046 and kda["tail_max"] < 0.01
    assert check["attention_layer"]["agrees"] and check["attention_layer"]["positions"] == 2 * 152
    experts = check["expert_layer"]
    assert experts["agrees"] and experts["positions"] == 8 * 78 and experts["held_positions"] > 0


def test_every_metric_file_the_host_can_read_gives_a_number_in_a_traced_rehearsal(
        traced_rehearsal):
    """The traced debug run reads every ``.reason`` file whose source is the
    program's counters, spans or the host: a number each, under
    ``facts.layer_metrics_reason``. The five that need device ops are read on
    the chip (a CPU trace holds none: their readers return nothing and do
    not raise), and on a recorded record below."""
    read = traced_rehearsal["facts"]["layer_metrics_reason"]
    assert set(read) == set(_reason_metrics()) - DEVICE_TRACE - {"hbm_peak.reason"}
    assert all(np.isfinite(m["value"]) for m in read.values())
    assert 0 < read["state_slots_per_step.reason"]["value"] <= 8
    assert 0 <= read["scan_runs_per_step.reason"]["value"] < 4
    assert 0 < read["held_rows_per_expert.reason"]["value"] < 8 * 4
    assert 0 <= read["held_groups_empty.reason"]["value"] < 100
    assert traced_rehearsal["facts"]["kda"] is None


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("solar")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_solar", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's file,
    the program's reads its dataclass): the same logits on the same seeded
    weights, to float32 rounding, given the same share; and the same output,
    state and tail of a KDA mixer, the same output of an attention mixer
    and of a routed feed-forward."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.solar_open2 import (reference_attention, reference_kda,
                                                  reference_logits, reference_moe)
    _, config, runner, engine = debug_engine
    cfg = runner["solar_config"](config["model"])
    assert (cfg.held, cfg.first_expert_held, cfg.n_routed_experts) == (8, 8, 16)
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    taps = []
    at = np.asarray([[3, 69], [0, 10]])
    h, margins, inputs = reference_solar.hidden(
        engine.params, jnp.asarray(ids), config["model"], positions=at,
        tap=lambda kind, i, *kept: taps.append(
            (kind, i) + tuple(None if t is None else np.asarray(t) for t in kept)))
    mine = np.asarray(reference_solar.head_at(engine.params, h, config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), cfg))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    assert [t[:2] for t in taps] == [("g", 0), ("k", 0), ("k", 1), ("k", 2), ("g", 1), ("k", 3),
                                     ("k", 4), ("k", 5)]
    assert margins.shape == (8, 2, 70) and inputs.shape == (8, 2, 2, 64)

    def rel(have, ref):
        return np.linalg.norm(have - np.asarray(ref)) / np.linalg.norm(np.asarray(ref))

    _, _, x, y, state, tail = taps[5]
    lp = jax.tree.map(lambda w: w[3], engine.params["model"]["kda_layers"])
    with jax.default_matmul_precision("highest"):
        want_y, want_state, want_tail = reference_kda(lp, jnp.asarray(x), cfg)
    assert rel(y, want_y) < 1e-5 and rel(state, want_state) < 1e-5 and rel(tail, want_tail) < 1e-5
    alone = reference_solar.kda_at(engine.params, 3, x[1], config["model"])
    assert rel(alone[0], want_y[1]) < 1e-5 and rel(alone[1], want_state[1]) < 1e-5
    _, _, x, y, _, _ = taps[4]
    lp = jax.tree.map(lambda w: w[1], engine.params["model"]["gqa_layers"])
    with jax.default_matmul_precision("highest"):
        assert rel(y, reference_attention(lp, jnp.asarray(x), cfg)) < 1e-5
    assert rel(reference_solar.attention_at(engine.params, 1, x[0], config["model"]), y[0]) < 1e-5
    fp = jax.tree.map(lambda w: w[6], engine.params["model"]["moe"])
    got, held = reference_solar.experts_at(engine.params, 6, inputs[6], config["model"])
    with jax.default_matmul_precision("highest"):
        assert rel(got, reference_moe(fp, inputs[6], cfg)) < 1e-5
    assert held.shape == (2, 2) and float(held.min()) >= 0.0
    rows, margins = reference_solar.rows_at(engine.params, jnp.asarray(ids), at, config["model"])
    assert rows.shape == (2, 2, 64) and margins.shape == (8, 2, 2)


def test_the_traffic_is_issue_48s_and_draws_from_the_slice_of_the_vocabulary():
    bench = spec.Benchmark(ROOT)
    params, vocab = bench.traffic("reason"), bench.config(CONFIG)["model"]["vocab_size"]
    make = bench.load("generators", params["kind"], "generate")
    a, b = (make(params, seed, 45.0, vocab) for seed in (3000000019, 7))
    assert len(a["deck"]) == 384 and a["clients"] == 192 and a["preroll_s"] == 20.0
    assert all(256 <= len(r["prompt"]) <= 2048 and 1024 <= r["max_new"] <= 3072
               for r in a["deck"])
    prompts = sum(len(r["prompt"]) for r in a["deck"]) / 384
    answers = sum(r["max_new"] for r in a["deck"]) / 384
    assert 820 < prompts < 900 and 1800 < answers < 1930       # ~68 % of the tokens are answers
    assert 0.66 < answers / (prompts + answers) < 0.70
    assert sorted(len(r["prompt"]) for r in a["deck"]) == sorted(len(r["prompt"])
                                                                 for r in b["deck"])
    top = max(int(r["prompt"].max()) for r in a["deck"])
    assert 24000 < top < vocab == 24576
    assert len(a["first_max_new"]) == 192                      # the starts staggered


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_solar
    bench, config, _, _ = debug_engine
    return control_solar.measure(bench, config, 3000001201, rehearse=True)


def test_the_program_comes_out_as_correct_and_the_float8_control_as_not(controls):
    got = controls
    program = got["program"]
    assert program["agrees"] and all(program[k]["agrees"] for k in (
        "kda_layer", "attention_layer", "expert_layer"))
    assert not got["float8"]["agrees"]
    assert got["float8"]["min"] > 2 * program["max"]
    # and its KDA mixer alone fails by the state it leaves
    faulty = got["float8"]["kda_layer"]
    assert not faulty["agrees"]
    assert min(faulty["states"]) > 2 * max(program["kda_layer"]["states"])


def test_a_state_carried_in_bfloat16_comes_out_as_not_correct(controls):
    program, faulty = controls["program"]["kda_layer"], controls["state_bf16"]["kda_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert min(faulty["states"]) > 0.0046 > program["state_max"]
    assert faulty["tail_max"] == program["tail_max"]          # the tails are the program's


def test_a_clipped_beta_a_gateless_attention_and_a_dropped_pick_come_out_as_not_correct(controls):
    program = controls["program"]
    clipped = controls["beta_clipped"]["kda_layer"]
    assert not clipped["agrees"] and clipped["min"] > 2 * program["kda_layer"]["max"]
    assert clipped["flipped_share"] == 1.0                    # every row fails the rows' limit
    gateless = controls["gateless"]["attention_layer"]
    assert not gateless["agrees"] and gateless["min"] > 10 * program["attention_layer"]["max"]
    dropped = controls["held_left_out"]["expert_layer"]
    assert not dropped["agrees"] and dropped["held_min"] > 2 * program["expert_layer"]["held_max"]


# ------------------------------------------------- the readers of the step records' counts
def _record(kind, k, n_tokens, counts, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": n_tokens, "n_prompt_tokens": n_prompt,
            "counts": counts}


SHAPES = {"kda_layers": 3, "attn_layers": 1, "heads": 64, "head_dim": 128, "state_itemsize": 4,
          "slots": 192}


def _run(records, shapes=SHAPES):
    bursts = [r for r in records if r["kind"].startswith("burst")]
    mixed = [r for r in records if r["kind"] == "put"]
    return {"trace": object(), "trace_window_s": 6.0, "facts": {"solar_shapes": shapes},
            "_program_spans": {"bursts": bursts, "mixed": mixed}}


def _reader(name):
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", f"{name}.json")) as f:
        module, _, attr = json.load(f)["reader"].partition(":")
    return bench.load("readers", module.partition(".")[2], attr)


def _counts(rows, slots, runs):
    return {"n_picks_held": rows * 4, "n_picks_zero": 0, "n_groups_live": 120,
            "n_kda_rows": rows * 3, "n_state_slots": slots * 3, "n_scan_runs": runs * 3}


def test_the_readers_on_a_recorded_record():
    slots, runs = _reader("state_slots_per_step.reason"), _reader("scan_runs_per_step.reason")
    records = [
        # a burst of 2 steps of 190 sequences: no run of more than a row
        _record("burst", 2, 380, _counts(380, 380, 0)),
        # a mixed step: 188 decode rows and 290 rows of 2 prompts
        _record("put", 1, 478, _counts(478, 190, 2), n_prompt=290)]
    run = _run(records)
    assert slots(run, {}) == pytest.approx((380 + 190) / 3)
    assert runs(run, {}) == pytest.approx(2 / 3)
    assert run["facts"]["kda_steps"] == {"records": 2, "model_steps": 3,
                                         "n_state_slots": 570 * 3, "n_kda_rows": 858 * 3,
                                         "n_scan_runs": 6}


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no such
    count (the parent's, or another model kind's) or a runner that states
    no shapes, the metric is left out: no raise."""
    from benchmark.readers import solar
    slots, roofline = (_reader("state_slots_per_step.reason"),
                       _reader("kda_state_roofline.reason"))
    for name in _reason_metrics():
        if name != "hbm_peak.reason":
            assert _reader(name)({"trace": None, "facts": {}, "observed": {}},
                                 {"observed": "x", "kernels": "y"}) is None, name
    assert solar.trace_facts({"trace": None, "facts": {}}) is None
    others = [{"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0},
              _record("burst", 8, 2048, None),
              # Jamba's records: the same slots' name, another rows' name
              _record("burst", 8, 1024, {"n_ssm_rows": 5, "n_state_slots": 5, "n_scan_runs": 0})]
    assert slots(_run(others), {}) is None
    assert slots(_run([_record("burst", 8, 1024, _counts(8, 8, 0))], shapes=None), {}) is None
    run = _run(others, shapes=None)
    assert roofline(run, {}) is None and "kda_roofline" not in run["facts"]


def test_the_least_bytes_are_a_slot_in_and_out_and_a_rows_operands():
    from benchmark.readers import solar
    # a decode step of 192 sequences, one layer: 192 x 8.39 MB + 192 x 164 KB = 1.642 GB
    assert solar.kda_bytes(192, 192, 64, 128) == 192 * 2 * 64 * 128 * 128 * 4 + 192 * (
        5 * 8192 + 64) * 4 == 1642119168
    # the census' count (tools/kernel_census.py --kda) is the same function of the same shapes
    from tools import kernel_census
    assert solar.kda_bytes(3, 510, 64, 128) == kernel_census.kda_bytes(3, 510, 64, 128)
    # three layers of 192 live slots at 819 GB/s: ISSUE 48's 5.9 ms a decode step
    assert round(3 * solar.kda_bytes(192, 192, 64, 128) / 819e9 * 1e3, 1) == 6.0
    # a decode row: 7.3 M operations on 8.6 MB - under an operation a byte: the bound is HBM
    assert solar.kda_flops(1, 64, 128) / solar.kda_bytes(1, 1, 64, 128) < 1.0
    # a run's later row: the same operations on 164 KB - 45 a byte: the vector unit's, not HBM's
    assert solar.kda_flops(1, 64, 128) / solar.kda_bytes(0, 1, 64, 128) > 40.0


def test_the_share_pattern_names_the_kernel_and_the_pool_pattern_the_pools():
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", "kda_share.reason.json")) as f:
        pattern = re.compile(json.load(f)["kernels"])
    assert pattern.search("kda_delta_rule.7 custom-call f32[3,193,64,128,128]")
    assert not pattern.search("paged_decode_attention.3 custom-call bf16[512,64,128]")
    assert not pattern.search("fusion.12 fusion f32[512,64,128]")
    pool = re.compile(r"\[(3,)?193,64,128,128\]")              # readers/solar.trace_facts' first
    assert pool.search("fusion.3 fusion f32[193,64,128,128]")
    assert pool.search("copy.1 copy f32[3,193,64,128,128]")
    assert not pool.search("scatter.1 scatter bf16[3,193,3,24576]")
    assert not pool.search("fusion.9 fusion f32[512,64,128]")
    tensor = re.compile(r"\[\d+,64,128,128\]")                 # a [T, H, d, d]
    assert tensor.search("fusion.4 fusion f32[512,64,128,128]")
    assert not tensor.search("fusion.4 fusion f32[512,64,128]")
