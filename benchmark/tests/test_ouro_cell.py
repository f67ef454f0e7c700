"""Tests of what the ``ouro-2.6b`` configuration and its cell add to the
benchmark: the cell rehearsed on the CPU through the unchanged ``run.py``,
the reference's copy against the program's own reference, the controls'
recipe, and the readers of the step records on a recorded record. Like
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

from benchmark.harness import reference_ouro, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "ouro-2.6b-mathqa", "ouro-2.6b"


def _runner():
    return spec.Benchmark(ROOT).load("runners", "serve_ouro", "run").__globals__


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_ouro" and len(cell["why"]) <= 200
    assert len(bench.configs[CONFIG]["why"]) <= 200 and len(bench.workloads[CELL]["why"]) <= 200
    assert config["reduced"] == [] == bench.configs[CONFIG]["reduced"]      # nothing is cut
    assert {"block_norms", "loop_norm", "early_exit_gate", "cache", "dtype", "seeded_parameters",
            "not_read"} <= set(config["assumed"])
    assert "WHOLE on one TPU v5e chip" in config["deployment"]
    traffic = bench.traffic(cell["traffic"])
    # ISSUE 54's table, letter for letter
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 12
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 48, "hi": 192}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 96, "hi": 256}
    assert (traffic["cycle_requests"], traffic["block_requests"], traffic["preroll_s"],
            traffic["tail_s"]) == (96, 12, 20.0, 8.0)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    # BENCHMARK.json's per_layer holds the 128 metrics it may hold: the cell's own are files
    # the runner reads into facts, and enters none
    assert len(bench.doc["per_layer"]) == 128
    assert set(bench.metrics_of(CELL, "per_layer")) == {"compile_s"}
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert CELL in [w["name"] for w in bench.doc["workloads"]]
    engine, model = config["engine"], config["model"]
    assert engine["token_budget"] == 512 and engine["kv_block_size"] == 16
    assert engine["max_context"] == 512 >= (traffic["prompt_tokens"]["hi"]
                                            + traffic["output_tokens"]["hi"])
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] \
        >= traffic["clients"]
    # the gate commits every client's worst case: no client waits at it
    worst = -(-(traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"])
              // engine["kv_block_size"])
    assert worst == 28 and engine["num_kv_blocks"] - 1 >= traffic["clients"] * worst
    assert engine["num_kv_blocks"] >= 336
    # both programs' tables fit the paged kernel's SMEM budget, with tiles; a group of ONE
    from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported, smem_table_fits
    for rows in (engine["token_budget"], engine["max_ragged_sequence_count"]):
        assert smem_table_fits(rows, engine["max_context"] // engine["kv_block_size"], tiles=True)
    assert kernel_supported(model["head_dim"], engine["kv_block_size"],
                            model["num_key_value_heads"])
    assert model["num_attention_heads"] // model["num_key_value_heads"] == 1
    # the check's sequence lies inside the cell's context and its chunks inside the budget
    ref = config["reference"]
    assert ref["prompt_cut"] < ref["prompt_tokens"] <= engine["token_budget"]
    assert ref["decode_rows"] >= 16 and ref["burst"] >= 1
    assert ref["prompt_tokens"] + ref["decode_rows"] + 1 + ref["burst"] <= engine["max_context"]


def test_the_cells_own_metric_files_are_whole_and_name_readers_that_load():
    bench = spec.Benchmark(ROOT)
    names = _runner()["MATHQA_METRICS"]
    assert len(names) == 13 and all(n.endswith(".mathqa") and spec.NAME.match(n) for n in names)
    assert {"decode_hbm_roofline.mathqa", "paged_attn_roofline.mathqa", "paged_attn_share.mathqa",
            "weight_copy_share.mathqa", "loop_passes_per_step.mathqa"} <= set(names)
    layers = {m["layer"] for m in bench.doc["per_layer"]}
    for name in names:
        assert name not in bench.per_layer
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            metric = json.load(f)
        assert metric["cells"] == [CELL] and metric["moves"] == "serve_tok_s"
        assert metric["layer"] in layers and spec.UNIT.match(metric["unit"])
        assert metric["source"] in spec.SOURCES and metric["better"] in ("lower", "higher")
        module, _, attr = metric["reader"].partition(":")
        assert callable(bench.load("readers", module.partition(".")[2], attr))
        if name.endswith("_roofline.mathqa"):
            assert metric["unit"] == "%"


def test_every_published_key_is_unchanged():
    """Every number of the catalog's ``config`` under the same key, and
    nothing listed as reduced: 48 of 48 layers, all four passes, the whole
    vocabulary."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Ouro-2.6B")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    assert [k for k, v in entry["config"].items() if model.get(k, "missing") != v] == []
    assert model["num_hidden_layers"] == 48 and model["total_ut_steps"] == 4
    assert model["vocab_size"] == 49152 and model["early_exit_threshold"] == 1


def test_the_programs_config_and_count_are_the_files():
    import jax
    from deepspeed_tpu.models.ouro import OURO_CONFIGS, param_shapes
    bench = spec.Benchmark(ROOT)
    config = bench.config(CONFIG)
    cfg = _runner()["ouro_config"](config["model"])
    assert cfg == OURO_CONFIGS["ouro-2.6b"]
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 2667974657 and "2.668 B" in config["deployment"]
    # a pooled token: 192 layers x (keys + values of 2048) x 2 B
    assert cfg.state_layers * 2 * 2048 * 2 == 1572864


def test_rehearsal_at_debug_size_on_the_cpu(tmp_path):
    out = run_cell(rehearsal_root(tmp_path), CELL, "--rehearse", "--seconds", "3")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert {"setup_s", "serve_tok_s"} <= set(line["rehearsal"]["metrics"])
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["state_kind"] == "kv"
    assert set(facts["attention_impls"].values()) == {"xla_gather"}
    assert facts["ouro_shapes"]["state_layers"] == 12 and facts["ouro_shapes"]["param_layers"] == 3
    assert facts["ouro_shapes"]["passes"] == 4 and facts["ouro_shapes"]["query_group"] == 1
    assert facts["state_bytes_per_token"] == 12 * 2 * 128 * 2
    assert "layer_metrics_mathqa" not in facts                # no traced run: nothing is read
    assert facts["window"]["first_tokens"] > 0 and facts["tpot_by_request"] == []
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["failed"] == []
    assert len(check["logits"]["by_position"]) == 2 + 4 + 1
    assert check["logits"]["max"] < 0.03 and check["passes"]["max"] < 0.05
    assert len(check["passes"]["by_pass"]) == 4 and check["gate"]["max"] < 0.01
    assert check["burst_regret"]["max"] < 0.1 and check["exit_steps"] == [3]


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("ouro")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_ouro", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's file,
    the program's reads its dataclass): the same logits, passes, gates and
    exit steps on the same seeded weights, at threshold 1 and under it."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.ouro import reference_forward
    _, config, runner, engine = debug_engine
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 256, (2, 40), dtype=np.int32))

    def rel(have, ref):
        return np.linalg.norm(np.asarray(have) - np.asarray(ref)) / np.linalg.norm(np.asarray(ref))

    for threshold in (1, 0.45):
        model = dict(config["model"], early_exit_threshold=threshold)
        mine = reference_ouro.forward(engine.params, ids, model)
        theirs = reference_forward(engine.params, ids, runner["ouro_config"](model))
        assert rel(mine["logits"], theirs.logits) < 1e-5
        assert rel(mine["passes"], theirs.passes) < 1e-5 and mine["passes"].shape[0] == 4
        assert np.abs(np.asarray(mine["gates"]) - np.asarray(theirs.gates)).max() < 1e-5
        assert np.array_equal(np.asarray(mine["exit_step"]), np.asarray(theirs.exit_step))
    assert len(set(np.asarray(mine["exit_step"]).ravel().tolist())) >= 2
    assert np.array_equal(np.asarray(reference_ouro.logits(engine.params, ids, config["model"])),
                          np.asarray(reference_ouro.forward(engine.params, ids,
                                                            config["model"])["logits"]))


def test_the_traffic_is_issue_54s_and_draws_from_the_whole_vocabulary():
    bench = spec.Benchmark(ROOT)
    params, vocab = bench.traffic("mathqa"), bench.config(CONFIG)["model"]["vocab_size"]
    make = bench.load("generators", params["kind"], "generate")
    a, b = (make(params, seed, 45.0, vocab) for seed in (3000000019, 7))
    assert len(a["deck"]) == 96 and a["clients"] == 12 and a["preroll_s"] == 20.0
    assert all(48 <= len(r["prompt"]) <= 192 and 96 <= r["max_new"] <= 256 for r in a["deck"])
    prompts = sum(len(r["prompt"]) for r in a["deck"]) / 96
    answers = sum(r["max_new"] for r in a["deck"]) / 96
    assert 98 < prompts < 110 and 155 < answers < 172
    assert sorted(len(r["prompt"]) for r in a["deck"]) == sorted(len(r["prompt"])
                                                                 for r in b["deck"])
    top = max(int(r["prompt"].max()) for r in a["deck"])
    assert 48000 < top < vocab == 49152
    assert len(a["first_max_new"]) == 12                      # the starts staggered


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_ouro
    bench, config, _, _ = debug_engine
    return control_ouro.measure(bench, config, 3000001201, rehearse=True)


@pytest.mark.parametrize("name", ["one_loop_fewer", "loop_norm_left_out",
                                  "sandwich_norms_left_out", "float8"])
def test_a_faulty_reference_comes_out_as_not_correct(controls, name):
    assert controls["bfloat16_stream"]["logits"]["max"] < controls["program"]["logits"]["max"] * 2
    program, faulty = controls["program"], controls[name]
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["logits"]["min"] > 4 * program["logits"]["max"]      # at EVERY position
    if name == "one_loop_fewer":
        assert faulty["failed"] == ["logits"]
        assert faulty["passes"]["max"] == 0.0             # the passes it ran are the reference's
    elif name == "float8":
        # three layers a pass lose less than the cell's 48: at this size the logits stay under
        # the cell's limit (0.52: what four passes of the published depth leave bfloat16) and
        # the passes alone catch it
        assert "passes" in faulty["failed"]
    else:
        assert {"logits", "passes", "gate"} <= set(faulty["failed"])


def test_passes_that_share_one_cache_come_out_as_not_correct(controls):
    program, shared = controls["program"], controls["loops_share_cache"]
    assert not shared["agrees"] and {"logits", "passes"} <= set(shared["failed"])
    # a one-chunk prefill still agrees: a pass's rows are all its own there
    assert shared["logits"]["first_chunk"] < 2 * program["logits"]["max"]
    later = [e for p, e in shared["logits"]["by_position"].items()
             if int(p) != min(map(int, shared["logits"]["by_position"]))]
    assert min(later) > 4 * program["logits"]["max"]


# ------------------------------------------------- the readers of the step records
SHAPES = {"state_layers": 192, "param_layers": 48, "passes": 4, "stack_bytes": 4933025792,
          "head_bytes": 201326592, "kv_row_bytes": 8192, "query_group": 1}


def _record(kind, k, counts, ctx, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": 12 * k, "n_prompt_tokens": n_prompt,
            "counts": counts, "n_ctx_tokens": ctx}


def _run(records, shapes=SHAPES):
    bursts = [r for r in records if r["kind"].startswith("burst")]
    mixed = [r for r in records if r["kind"] == "put"]
    facts = {} if shapes is None else {"ouro_shapes": shapes, "state_bytes_per_token": 1572864}
    return {"trace": object(), "trace_window_s": 6.0, "facts": facts,
            "_program_spans": {"bursts": bursts, "mixed": mixed}}


def _reader(name):
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", f"{name}.json")) as f:
        module, _, attr = json.load(f)["reader"].partition(":")
    return bench.load("readers", module.partition(".")[2], attr)


def test_the_readers_on_a_recorded_record():
    passes = _reader("loop_passes_per_step.mathqa")
    records = [_record("burst", 8, {"n_stack_passes": 32, "n_loop_token_layers": 8 * 12 * 192,
                                    "n_exit_early_rows": 0}, 8 * 2400),
               _record("put", 1, {"n_stack_passes": 4, "n_loop_token_layers": 115 * 192,
                                  "n_exit_early_rows": 0}, 2500, n_prompt=104)]
    assert passes(_run(records), {}) == pytest.approx(4.0)
    ctx = _reader("ctx_tokens_per_step.mathqa")
    assert ctx(_run(records), {}) == pytest.approx(2400)


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no such count
    (the parent's, or another model kind's) or a runner that states no
    shapes, the metric is left out: no raise."""
    readers = [_reader(f"{n}.mathqa") for n in ("decode_hbm_roofline", "paged_attn_roofline",
                                                 "loop_passes_per_step")]
    for reader in readers:
        assert reader({"trace": None, "facts": {}, "observed": {}}, {}) is None
    others = [{"kind": "burst", "k": 8, "n_tokens": 96, "n_prompt_tokens": 0},
              _record("burst", 8, None, 100), _record("burst", 8, {"n_ssm_rows": 5}, 100)]
    assert readers[2](_run(others), {}) is None
    run = _run(others, shapes=None)
    assert readers[0](run, {}) is None and readers[1](run, {}) is None
    assert "decode_hbm" not in run["facts"] and "attention_roofline" not in run["facts"]


def test_the_least_bytes_are_the_stack_a_pass_the_head_a_step_and_the_cache():
    from benchmark.readers import ouro
    shapes = dict(SHAPES, state_bytes_per_token=1572864)
    # one decode step of 12 rows at ~190 positions each: 4 x 4.933 GB + 0.201 GB + 3.59 GB
    moved = ouro.step_bytes(4, 1, 12 * 190, shapes)
    assert moved == 4 * 4933025792 + 201326592 + 2280 * 1572864 == 23519559680
    assert 28.0 < moved / 819e9 * 1e3 < 29.5                  # ms at the chip's peak: ISSUE's floor
    assert ouro.attention_bytes(2280, shapes) == 2280 * 192 * 8192 == 2280 * 1572864
    # the census' count (tools/kernel_census.py --paged1) is the same function of the shapes
    from tools import kernel_census
    assert ouro.attention_bytes(2280, shapes) // 192 == kernel_census.paged_bytes(2280, 16, 128)


def test_a_records_device_time_is_the_union_of_its_ops_cut_at_its_end():
    from benchmark.readers import ouro
    ops = [("while.1 while", 100, 900),                    # a loop's parent over its body
           ("fusion.1 fusion bf16[16,2048]", 100, 300), ("paged_decode_attention.2 custom-call", 450, 50),
           ("fusion.2 fusion bf16[16,2048]", 1200, 100),   # the next record's
           ("while.9 while", 1900, 10 ** 9)]               # an end the profiler never saw
    run = {"trace": {"devices": {"/device:TPU:0": {"XLA Ops": ops}}}}
    from benchmark.harness import trace as tr
    assert tr.ops_of(run["trace"]), "the synthetic trace has the harness's own line name"
    chosen = [(90, 1010, {}), (1150, 1350, {}), (1890, 2000, {})]
    assert ouro._device_ns(run, chosen) == [900, 100, 100]
    assert ouro._device_ns(run, chosen, ouro.KERNEL) == [50, 0, 0]


def test_the_share_patterns_name_the_kernel_and_a_layers_matrices():
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", "paged_attn_share.mathqa.json")) as f:
        kernel = re.compile(json.load(f)["kernels"])
    assert kernel.search("paged_decode_attention.3 custom-call bf16[16,16,128]")
    assert not kernel.search("fusion.12 fusion bf16[16,2048]")
    with open(bench.path("layer_metrics", "weight_copy_share.mathqa.json")) as f:
        copied = re.compile(json.load(f)["kernels"])
    assert copied.search("copy.10 copy bf16[48,2048,2048]")
    assert copied.search("fusion.113 fusion bf16[2048,5632]")
    assert copied.search("fusion.7 fusion bf16[1,5632,2048]")
    assert not copied.search("fusion.9 fusion bf16[512,2048]")
    assert not copied.search("fusion.2 fusion bf16[192,368,16,2048]")         # the pool
    assert not copied.search("fusion.4 fusion f32[2048,2048]")
