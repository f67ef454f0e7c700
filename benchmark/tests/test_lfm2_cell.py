"""Tests of what the ``lfm2-24b-a2b-10l`` configuration and its cell add to
the benchmark: the cell rehearsed on the CPU through the unchanged
``run.py``, the reference's copy against the program's own reference, the
controls' recipe, and the readers of the step records' counts on a
recorded record. Like ``test_benchmark.py`` they are the benchmark's, not
tier-1's (``python -m pytest benchmark/tests -q``).
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

from benchmark.harness import reference_lfm2, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "lfm2-24b-rag", "lfm2-24b-a2b-10l"
CUTS = ["layer_types", "num_hidden_layers"]


def _rag_metrics():
    return spec.Benchmark(ROOT).load("runners", "serve_lfm2", "run").__globals__["RAG_METRICS"]


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_lfm2" and len(cell["why"]) <= 200
    assert len(bench.configs[CONFIG]["why"]) <= 200 and len(bench.workloads[CELL]["why"]) <= 200
    assert sorted(config["reduced"]) == CUTS
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert {"tie_word_embeddings", "head_dim", "qk_norm", "rope", "dense_width", "topk_epsilon",
            "dtype", "seeded_parameters"} <= set(config["assumed"])
    assert "stage 0 of 4" in config["deployment"]
    traffic = bench.traffic(cell["traffic"])
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 64
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 1024, "hi": 8192}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 64, "hi": 512}
    assert (traffic["cycle_requests"], traffic["block_requests"], traffic["preroll_s"],
            traffic["tail_s"]) == (128, 16, 30.0, 8.0)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    # BENCHMARK.json's per_layer holds the 128 metrics it may hold: the cell's own are
    # files the runner reads into facts, and enters none
    assert len(bench.doc["per_layer"]) == 128
    assert set(bench.metrics_of(CELL, "per_layer")) == {"compile_s"}
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    engine = config["engine"]
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] \
        == traffic["clients"]
    assert engine["max_context"] >= traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"]
    # every admitted request's worst case fits the pool: no client waits at the gate
    assert engine["num_kv_blocks"] - 1 >= traffic["clients"] * (
        engine["max_context"] // engine["kv_block_size"])
    # a 512-row program's table fits the paged kernel's SMEM budget
    from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported, smem_table_fits
    assert smem_table_fits(engine["token_budget"], engine["max_context"] // engine["kv_block_size"])
    model = config["model"]
    assert kernel_supported(model["hidden_size"] // model["num_attention_heads"],
                            engine["kv_block_size"], model["num_key_value_heads"])


def test_the_cells_own_metric_files_are_whole_and_name_readers_that_load():
    bench = spec.Benchmark(ROOT)
    names = _rag_metrics()
    assert len(names) == 9 and all(n.endswith(".rag") and spec.NAME.match(n) for n in names)
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


def test_the_published_keys_are_unchanged_but_the_two_cuts():
    """Every number of the catalog's ``config`` under the same key; only
    the keys listed in ``reduced`` differ, none of them a width, and the
    file states the published values and the deployment beside them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "LFM2-24B-A2B")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    differ = sorted(k for k, v in entry["config"].items() if model.get(k, "missing") != v)
    assert differ == sorted(config["reduced"]) == CUTS
    assert model["published"] == {k: entry["config"][k] for k in differ}
    first, last = model["share"]["published_layers"]
    types = entry["config"]["layer_types"]
    assert model["layer_types"] == types[first:last + 1] and (first, last) == (0, 9)
    assert model["num_hidden_layers"] == len(model["layer_types"]) == 10
    # the leading dense layers once, then whole periods at the published ratio, and the
    # guide's floors: 8 >= 4 layers after the leading ones, all 64 experts, the whole vocabulary
    after = model["layer_types"][model["num_dense_layers"]:]
    assert after == ["full_attention", "conv", "conv", "conv"] * 2
    assert types[2:38] == ["full_attention", "conv", "conv", "conv"] * 9
    assert model["num_experts"] == 64 and model["vocab_size"] == 65536
    assert model["share"]["pipeline_stages"] * model["num_hidden_layers"] == 40


def test_the_programs_count_of_the_cut_is_the_files():
    import jax
    from deepspeed_tpu.models.lfm2 import LFM2_CONFIGS, param_shapes
    bench = spec.Benchmark(ROOT)
    config = bench.config(CONFIG)
    cfg = bench.load("runners", "serve_lfm2", "run").__globals__["lfm2_config"](config["model"])
    assert cfg == LFM2_CONFIGS["lfm2-24b-a2b-10l"]
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert f"{count:,}" in config["reduced_why"]["num_hidden_layers"]


def test_rehearsal_at_debug_size_on_the_cpu(tmp_path):
    out = run_cell(rehearsal_root(tmp_path), CELL, "--rehearse", "--seconds", "3")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert {"setup_s", "serve_tok_s"} <= set(line["rehearsal"]["metrics"])
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["state_kind"] == "kv+slots"
    assert set(facts["attention_impls"].values()) == {"xla_gather"}
    assert facts["lfm2_shapes"] == {"conv_layers": 6, "attn_layers": 3, "expert_layers": 7,
                                    "kv_heads": 2, "head_dim": 16, "kv_itemsize": 2, "slots": 8}
    assert facts["expert_share"] == {"moe_topk": 3, "expert_layers": 7, "experts_held": 8,
                                     "routed": 8, "zero": 0}
    # a slot is the tail alone: 6 conv layers x 2 rows of 64 bf16
    assert facts["slot_bytes"] == 6 * 2 * 64 * 2 and set(facts["state_extra_bytes"]) == {"conv"}
    assert "layer_metrics_rag" not in facts                   # no traced run: nothing is read
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == (3 + 3) * 13
    assert check["largest_under_tolerance"] < 0.02
    conv = check["conv_layer"]
    assert conv["agrees"] and conv["rows"] == 152 and conv["positions"] == 6 * 152
    assert conv["tail_max"] < 0.01
    attn = check["attention_layer"]
    assert attn["agrees"] and attn["positions"] == 3 * 152
    alone = check["expert_layer"]
    assert alone["agrees"] and alone["positions"] == 7 * (3 + 3) * 13
    assert alone["held_positions"] == alone["positions"] and alone["max"] < 0.01


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("lfm2")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_lfm2", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's
    file, the program's reads its dataclass): the same logits on the same
    seeded weights, to float32 rounding; and the same output and tail of a
    ``conv`` operator, the same output of an attention operator."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.lfm2 import reference_attention, reference_conv, reference_logits
    _, config, runner, engine = debug_engine
    cfg = runner["lfm2_config"](config["model"])
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    taps = []
    h, margins, _ = reference_lfm2.hidden(
        engine.params, jnp.asarray(ids), config["model"],
        tap=lambda kind, i, *kept: taps.append(
            (kind, i) + tuple(None if t is None else np.asarray(t) for t in kept)))
    mine = np.asarray(reference_lfm2.head_at(engine.params, h, config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), cfg))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    assert margins.shape == (7, 2, 70) and (np.asarray(margins) > 0).all()
    assert [t[:2] for t in taps] == [("conv", 0), ("full_attention", 0), ("full_attention", 1),
                                     ("conv", 1), ("conv", 2), ("full_attention", 2),
                                     ("conv", 3), ("conv", 4), ("conv", 5)]

    def rel(have, ref):
        return np.linalg.norm(have - np.asarray(ref)) / np.linalg.norm(np.asarray(ref))

    _, _, x, y, tail = taps[4]
    lp = jax.tree.map(lambda w: w[2], engine.params["model"]["conv_layers"])
    with jax.default_matmul_precision("highest"):
        want_y, want_tail = reference_conv(lp, jnp.asarray(x), cfg)
    assert rel(y, want_y) < 1e-5 and rel(tail, want_tail) < 1e-5
    _, _, x, y, _ = taps[2]
    lp = jax.tree.map(lambda w: w[1], engine.params["model"]["attn_layers"])
    with jax.default_matmul_precision("highest"):
        assert rel(y, reference_attention(lp, jnp.asarray(x), cfg)) < 1e-5
        alone = reference_lfm2.attention_at(engine.params, 1, x[0], config["model"])
        carried = reference_lfm2.conv_at(engine.params, 2, taps[4][2][:, 40:], config["model"],
                                         tail=reference_lfm2.conv_at(
                                             engine.params, 2, taps[4][2][:, :40],
                                             config["model"])[1])
    assert rel(np.asarray(alone), y[0]) < 1e-5
    assert rel(np.asarray(carried[0]), taps[4][3][:, 40:]) < 1e-5      # a tail carried in


def test_the_traffic_is_issue_41s_and_draws_from_the_whole_vocabulary():
    bench = spec.Benchmark(ROOT)
    params, vocab = bench.traffic("rag"), bench.config(CONFIG)["model"]["vocab_size"]
    make = bench.load("generators", params["kind"], "generate")
    a = make(params, 3000000019, 45.0, vocab)
    assert len(a["deck"]) == 128 and a["clients"] == 64 and a["preroll_s"] == 30.0
    assert all(1024 <= len(r["prompt"]) <= 8192 and 64 <= r["max_new"] <= 512
               for r in a["deck"])
    prompts = sum(len(r["prompt"]) for r in a["deck"])
    answers = sum(r["max_new"] for r in a["deck"])
    assert 3300 < prompts / 128 < 3600 and 200 < answers / 128 < 230
    assert 0.93 < prompts / (prompts + answers) < 0.95        # ~94 % prompt tokens
    top = max(int(r["prompt"].max()) for r in a["deck"])
    assert 65000 < top < vocab == 65536
    # two to sixteen SplitFuse chunks
    assert min(len(r["prompt"]) for r in a["deck"]) > 512
    assert sum(len(r["prompt"]) > 7680 for r in a["deck"]) >= 2


@pytest.fixture(scope="module")
def controls(debug_engine):
    """No shared expert stands beside the picks here, so one left out is a
    third of the layer's output at the debug widths as it is."""
    from benchmark.tests import control_lfm2
    bench, config, _, _ = debug_engine
    return control_lfm2.measure(bench, config, 3000001201, rehearse=True)


def test_the_float8_control_comes_out_as_not_correct(controls):
    got = controls
    assert got["program"]["agrees"] and got["program"]["largest_under_tolerance"] < 0.02
    assert all(got["program"][k]["agrees"] for k in ("conv_layer", "attention_layer",
                                                     "expert_layer"))
    assert not got["float8"]["agrees"]
    assert got["float8"]["min"] > 2 * got["program"]["max"]


def test_tails_dropped_at_chunk_boundaries_come_out_as_not_correct(controls):
    program, faulty = controls["program"]["conv_layer"], controls["tails_dropped"]["conv_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert min(faulty["tails"]) > 0.5 and max(program["tails"]) < 0.01
    assert faulty["max"] > 10 * program["max"]


def test_keys_and_values_in_float8_come_out_as_not_correct(controls):
    program, faulty = (controls["program"]["attention_layer"],
                       controls["kv_float8"]["attention_layer"])
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["median"] > 2 * program["median"]


def test_a_pick_left_out_comes_out_as_not_correct(controls):
    program, faulty = controls["program"]["expert_layer"], controls["pick_left_out"]["expert_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["held_over"] > 0.9 * faulty["held_positions"]


# ------------------------------------------------- the readers of the step records' counts
def _record(kind, k, n_tokens, counts, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": n_tokens, "n_prompt_tokens": n_prompt,
            "counts": counts}


SHAPES = {"conv_layers": 8, "attn_layers": 2, "expert_layers": 8, "kv_heads": 8, "head_dim": 64,
          "kv_itemsize": 2, "slots": 64}
SHARE = {"moe_topk": 4, "expert_layers": 8, "experts_held": 64, "routed": 64, "zero": 0}


def _run(records, shapes=SHAPES, share=SHARE):
    bursts = [r for r in records if r["kind"].startswith("burst")]
    mixed = [r for r in records if r["kind"] == "put"]
    return {"trace": object(), "trace_window_s": 6.0,
            "facts": {"lfm2_shapes": shapes, "expert_share": share},
            "_program_spans": {"bursts": bursts, "mixed": mixed}}


def _reader(name):
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", f"{name}.json")) as f:
        module, _, attr = json.load(f)["reader"].partition(":")
    return bench.load("readers", module.partition(".")[2], attr)


def test_the_readers_on_a_recorded_record():
    slots, rows = _reader("tail_slots_per_step.rag"), _reader("rows_per_expert.rag")
    records = [
        # a burst of 4 steps of 30 sequences, 8 conv layers: 4 x 30 x 8 tails read and written;
        # 4 x 30 x 4 x 8 picks, all held
        _record("burst", 4, 120, {"n_picks_held": 3840, "n_picks_zero": 0, "n_groups_live": 1500,
                                  "n_conv_rows": 960, "n_tail_slots": 960,
                                  "n_ctx_seq_tokens": 4 * 30 * 2000}),
        # one mixed step: 30 decode rows and 482 rows of two prompts
        _record("put", 1, 512, {"n_picks_held": 16384, "n_picks_zero": 0, "n_groups_live": 512,
                                "n_conv_rows": 4096, "n_tail_slots": 256,
                                "n_ctx_seq_tokens": 32 * 2000}, n_prompt=482)]
    run = _run(records)
    assert slots(run, {}) == pytest.approx((960 + 256) / (8 * 5))
    assert run["facts"]["tail_slots"] == {"records": 2, "model_steps": 5, "n_tail_slots": 1216,
                                          "n_conv_rows": 5056}
    assert rows(run, {}) == pytest.approx(20224 / (8 * 5 * 64))


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no such
    count (the parent's, or another model kind's) or a runner that states
    no shapes, the metric is left out: no raise."""
    slots, roofline = _reader("tail_slots_per_step.rag"), _reader("paged_attn_roofline.rag")
    for reader in (slots, roofline):
        assert reader({"trace": None, "facts": {}, "observed": {}}, {}) is None
    others = [{"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0},
              _record("burst", 8, 2048, None),
              _record("burst", 8, 1024, {"n_picks_held": 1, "n_picks_zero": 1, "n_groups_live": 1,
                                         "n_ssm_rows": 5, "n_state_slots": 5})]
    assert slots(_run(others), {}) is None
    assert slots(_run([_record("burst", 8, 1024, {"n_tail_slots": 5120})], shapes=None),
                 {}) is None
    run = _run(others, shapes=None)
    assert roofline(run, {}) is None and "paged_attn" not in run["facts"]


def test_the_least_bytes_are_a_contexts_keys_and_values_once_a_sequence():
    from benchmark.readers import lfm2
    # 2 attention layers x 8 heads x 64 x K and V x 2 B = 4096 B a position
    assert lfm2.kernel_bytes(1000, 2, 8, 64, 2) == 1000 * 4096
    assert lfm2.kernel_flops(1000, 2, 32, 64) == 1000 * 2 * 32 * 4 * 64
    # 4 operations a byte: far under the chip's ~240 a byte, so the bound is HBM
    assert lfm2.kernel_flops(1, 1, 4, 64) / lfm2.kernel_bytes(1, 1, 1, 64, 2) == 4.0


def test_the_conv_share_pattern_names_the_convolutions_ops_and_no_others():
    with open(spec.Benchmark(ROOT).path("layer_metrics", "conv_op_share.rag.json")) as f:
        pattern = re.compile(json.load(f)["kernels"])
    conv = ["fusion.12 fusion bf16[512,6144]", "fusion.3 fusion bf16[64,6144]",
            "fusion.77 fusion bf16[65,2,2048]", "scatter.3 scatter bf16[8,65,2,2048]"]
    others = ["fusion.373 fusion bf16[512,2048]", "gmm_ragged_dot.41 custom-call bf16[2560,1536]",
              "paged_decode_attention.3 custom-call bf16[512,32,128]",
              "fusion.5 fusion bf16[64,65536]", "fusion.1 fusion bf16[512,11776]",
              "scatter.1 scatter bf16[2,8705,64,512]"]
    assert all(pattern.search(name) for name in conv)
    assert not any(pattern.search(name) for name in others)
