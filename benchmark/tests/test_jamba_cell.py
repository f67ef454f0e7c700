"""Tests of what the ``jamba2-3b`` configuration and its cell add to the
benchmark: the cell rehearsed on the CPU through the unchanged ``run.py``,
the reference's copy against the program's own reference, the controls'
recipe, and the readers of the step records' counts on a recorded record.
Like ``test_benchmark.py`` they are the benchmark's, not tier-1's (``python
-m pytest benchmark/tests -q``).
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

from benchmark.harness import reference_jamba, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "jamba2-3b-chatloop", "jamba2-3b"


def _chatloop_metrics():
    return spec.Benchmark(ROOT).load("runners", "serve_jamba", "run").__globals__[
        "CHATLOOP_METRICS"]


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_jamba" and len(cell["why"]) <= 200
    assert len(bench.configs[CONFIG]["why"]) <= 200 and len(bench.workloads[CELL]["why"]) <= 200
    assert config["reduced"] == [] == bench.configs[CONFIG]["reduced"]      # nothing is cut
    assert {"attention_positions", "state_dtype", "dtype", "seeded_state_space_parameters",
            "not_read"} <= set(config["assumed"])
    assert "one bfloat16 replica whole on one TPU v5e chip" in config["deployment"]
    traffic = bench.traffic(cell["traffic"])
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 256
    chat = bench.traffic("chat")                       # chat.json's lengths to the digit
    assert traffic["prompt_tokens"] == chat["prompt_tokens"] == {
        "dist": "lognormal", "median": 160, "sigma": 1.0, "min": 16, "max": 512}
    assert traffic["output_tokens"] == chat["output_tokens"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.8, "min": 8, "max": 512}
    assert (traffic["cycle_requests"], traffic["block_requests"], traffic["preroll_s"],
            traffic["tail_s"]) == (4096, 64, 20.0, 5.0)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    # BENCHMARK.json's per_layer holds the 128 metrics it may hold: the cell's own are
    # files the runner reads into facts, and enters none
    assert len(bench.doc["per_layer"]) == 128
    assert set(bench.metrics_of(CELL, "per_layer")) == {"compile_s"}
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert [w["name"] for w in bench.doc["workloads"]][-1] == CELL
    assert [c["name"] for c in bench.doc["configs"]][-1] == CONFIG
    engine = config["engine"]
    assert engine["token_budget"] == 512 and engine["kv_block_size"] == 64
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] \
        == traffic["clients"]
    assert engine["max_context"] == (traffic["prompt_tokens"]["max"]
                                     + traffic["output_tokens"]["max"])
    # every admitted request's worst case fits the pool: no client waits at the gate
    assert engine["num_kv_blocks"] - 1 >= traffic["clients"] * (
        engine["max_context"] // engine["kv_block_size"])
    # both programs' tables fit the paged kernel's SMEM budget, with tiles; a group of 20
    from deepspeed_tpu.ops.pallas.paged_attention import kernel_supported, smem_table_fits
    from deepspeed_tpu.ops.pallas.selective_scan import kernel_supported as scan_supported
    model = config["model"]
    for rows in (engine["token_budget"], engine["max_ragged_sequence_count"]):
        assert smem_table_fits(rows, engine["max_context"] // engine["kv_block_size"], tiles=True)
        assert scan_supported((26, 257, model["mamba_d_state"],
                               model["mamba_expand"] * model["hidden_size"]), rows, 257)
    assert kernel_supported(model["hidden_size"] // model["num_attention_heads"],
                            engine["kv_block_size"], model["num_key_value_heads"])
    assert model["num_attention_heads"] // model["num_key_value_heads"] == 20


def test_the_cells_own_metric_files_are_whole_and_name_readers_that_load():
    bench = spec.Benchmark(ROOT)
    names = _chatloop_metrics()
    assert len(names) == 9 and all(n.endswith(".chatloop") and spec.NAME.match(n) for n in names)
    assert {"selective_scan_roofline.chatloop", "selective_scan_share.chatloop",
            "state_slots_per_step.chatloop", "scan_runs_per_step.chatloop"} <= set(names)
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
        if name.endswith("_roofline.chatloop"):
            assert metric["unit"] == "%"


def test_every_published_key_is_unchanged():
    """Every number of the catalog's ``config`` under the same key, and
    nothing listed as reduced: 28 of 28 layers, the whole vocabulary."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "AI21-Jamba2-3B")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    assert [k for k, v in entry["config"].items() if model.get(k, "missing") != v] == []
    assert model["num_hidden_layers"] == 28 and model["vocab_size"] == 65536
    assert reference_jamba.layer_kinds(model).count("attention") == 2
    assert [i for i, k in enumerate(reference_jamba.layer_kinds(model)) if k == "attention"] \
        == [7, 21]


def test_the_programs_config_and_count_are_the_files():
    import jax
    from deepspeed_tpu.models.jamba import JAMBA_CONFIGS, param_shapes
    bench = spec.Benchmark(ROOT)
    config = bench.config(CONFIG)
    cfg = bench.load("runners", "serve_jamba", "run").__globals__["jamba_config"](config["model"])
    assert cfg == JAMBA_CONFIGS["jamba2-3b"]
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 3029337472 and "3.029 B" in config["deployment"]


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
    assert set(facts["state_step_impls"].values()) == {"xla"}
    assert facts["jamba_shapes"] == {"mamba_layers": 8, "attn_layers": 2, "channels": 384,
                                     "state_columns": 16, "state_itemsize": 4, "slots": 8}
    # a slot is both entries': 8 layers x (16 x 384 float32 + 3 rows of 384 bf16)
    assert facts["slot_bytes"] == 8 * (16 * 384 * 4 + 3 * 384 * 2)
    assert set(facts["state_extra_bytes"]) == {"conv", "ssm"}
    assert "layer_metrics_chatloop" not in facts              # no traced run: nothing is read
    assert facts["window"]["first_tokens"] > 0 and facts["window"]["ttft_p50_ms"] > 0
    assert facts["tpot_by_request"] == []
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == (3 + 3) * 13
    assert check["largest_under_tolerance"] < 0.03
    mamba = check["mamba_layer"]
    assert mamba["agrees"] and mamba["rows"] == 112 and mamba["positions"] == 8 * 112
    assert mamba["state_max"] < 0.004 and mamba["tail_max"] < 0.01
    attn = check["attention_layer"]
    assert attn["agrees"] and attn["positions"] == 2 * 112


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("jamba")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_jamba", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's
    file, the program's reads its dataclass): the same logits on the same
    seeded weights, to float32 rounding; and the same output, state and
    tail of a Mamba mixer, the same output of an attention mixer."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.jamba import reference_attention, reference_logits, reference_mamba
    _, config, runner, engine = debug_engine
    cfg = runner["jamba_config"](config["model"])
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    taps = []
    h = reference_jamba.hidden(
        engine.params, jnp.asarray(ids), config["model"],
        tap=lambda kind, i, *kept: taps.append(
            (kind, i) + tuple(None if t is None else np.asarray(t) for t in kept)))
    mine = np.asarray(reference_jamba.head_at(engine.params, h, config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), cfg))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    assert np.array_equal(mine, np.asarray(reference_jamba.logits(engine.params, jnp.asarray(ids),
                                                                  config["model"])))
    assert [t[:2] for t in taps] == [("mamba", 0), ("mamba", 1), ("attention", 0), ("mamba", 2),
                                     ("mamba", 3), ("mamba", 4), ("mamba", 5), ("attention", 1),
                                     ("mamba", 6), ("mamba", 7)]

    def rel(have, ref):
        return np.linalg.norm(have - np.asarray(ref)) / np.linalg.norm(np.asarray(ref))

    _, _, x, y, state, tail = taps[4]
    lp = jax.tree.map(lambda w: w[3], engine.params["model"]["mamba_layers"])
    with jax.default_matmul_precision("highest"):
        want_y, want_state, want_tail = reference_mamba(lp, jnp.asarray(x), cfg)
    assert rel(y, want_y) < 1e-5 and rel(state, want_state) < 1e-5 and rel(tail, want_tail) < 1e-5
    _, _, x, y, _, _ = taps[7]
    lp = jax.tree.map(lambda w: w[1], engine.params["model"]["attn_layers"])
    with jax.default_matmul_precision("highest"):
        assert rel(y, reference_attention(lp, jnp.asarray(x), cfg)) < 1e-5
    rows, margins = reference_jamba.rows_at(engine.params, jnp.asarray(ids),
                                            np.asarray([[3, 69], [0, 10]]), config["model"])
    assert rows.shape == (2, 2, 192) and margins.shape == (1, 2, 2)
    assert (np.asarray(margins) == 1).all()                  # no router: no position is fragile


def test_the_traffic_is_issue_45s_and_draws_from_the_whole_vocabulary():
    bench = spec.Benchmark(ROOT)
    params, vocab = bench.traffic("chatloop"), bench.config(CONFIG)["model"]["vocab_size"]
    make = bench.load("generators", params["kind"], "generate")
    a, b = (make(params, seed, 45.0, vocab) for seed in (3000000019, 7))
    assert len(a["deck"]) == 4096 and a["clients"] == 256 and a["preroll_s"] == 20.0
    assert all(16 <= len(r["prompt"]) <= 512 and 8 <= r["max_new"] <= 512 for r in a["deck"])
    prompts = sum(len(r["prompt"]) for r in a["deck"]) / 4096
    answers = sum(r["max_new"] for r in a["deck"]) / 4096
    assert 195 < prompts < 225 and 155 < answers < 175        # answers about as long as prompts
    assert sorted(len(r["prompt"]) for r in a["deck"]) == sorted(len(r["prompt"])
                                                                 for r in b["deck"])
    top = max(int(r["prompt"].max()) for r in a["deck"])
    assert 65000 < top < vocab == 65536
    assert len(a["first_max_new"]) == 256                     # the starts staggered


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_jamba
    bench, config, _, _ = debug_engine
    return control_jamba.measure(bench, config, 3000001201, rehearse=True)


def test_the_float8_control_comes_out_as_not_correct(controls):
    got = controls
    assert got["program"]["agrees"] and got["program"]["largest_under_tolerance"] < 0.03
    assert got["program"]["mamba_layer"]["agrees"] and got["program"]["attention_layer"]["agrees"]
    assert not got["float8"]["agrees"]
    assert got["float8"]["min"] > 2 * got["program"]["max"]
    # and its Mamba mixer alone fails by the state it leaves
    faulty = got["float8"]["mamba_layer"]
    assert not faulty["agrees"]
    assert min(faulty["states"]) > 2 * max(got["program"]["mamba_layer"]["states"])


def test_a_state_carried_in_bfloat16_comes_out_as_not_correct(controls):
    program, faulty = controls["program"]["mamba_layer"], controls["state_bf16"]["mamba_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["state_max"] > 0.004 > program["state_max"]
    assert min(faulty["states"]) > 2 * max(program["states"])
    assert faulty["tail_max"] == program["tail_max"]          # the tails are the program's


# ------------------------------------------------- the readers of the step records' counts
def _record(kind, k, n_tokens, counts, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": n_tokens, "n_prompt_tokens": n_prompt,
            "counts": counts}


SHAPES = {"mamba_layers": 26, "attn_layers": 2, "channels": 5120, "state_columns": 16,
          "state_itemsize": 4, "slots": 256}


def _run(records, shapes=SHAPES):
    bursts = [r for r in records if r["kind"].startswith("burst")]
    mixed = [r for r in records if r["kind"] == "put"]
    return {"trace": object(), "trace_window_s": 6.0, "facts": {"jamba_shapes": shapes},
            "_program_spans": {"bursts": bursts, "mixed": mixed}}


def _reader(name):
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", f"{name}.json")) as f:
        module, _, attr = json.load(f)["reader"].partition(":")
    return bench.load("readers", module.partition(".")[2], attr)


def test_the_readers_on_a_recorded_record():
    slots, runs = _reader("state_slots_per_step.chatloop"), _reader("scan_runs_per_step.chatloop")
    records = [
        # a burst of 2 steps of 250 sequences: 2 x 250 x 26 slots, no run of more than a row
        _record("burst", 2, 500, {"n_ssm_rows": 13000, "n_state_slots": 13000, "n_scan_runs": 0}),
        # a mixed step: 220 decode rows and 290 rows of 3 prompts
        _record("put", 1, 510, {"n_ssm_rows": 510 * 26, "n_state_slots": 223 * 26,
                                "n_scan_runs": 3 * 26}, n_prompt=290)]
    run = _run(records)
    assert slots(run, {}) == pytest.approx((500 + 223) / 3)
    assert runs(run, {}) == pytest.approx(3 / 3)
    assert run["facts"]["scan_steps"] == {"records": 2, "model_steps": 3,
                                          "n_state_slots": 18798, "n_ssm_rows": 26260,
                                          "n_scan_runs": 78}


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no such
    count (the parent's, or another model kind's) or a runner that states
    no shapes, the metric is left out: no raise."""
    from benchmark.readers import jamba
    slots, roofline = (_reader("state_slots_per_step.chatloop"),
                       _reader("selective_scan_roofline.chatloop"))
    for reader in (slots, roofline, _reader("scan_runs_per_step.chatloop")):
        assert reader({"trace": None, "facts": {}, "observed": {}}, {}) is None
    assert jamba.trace_facts({"trace": None, "facts": {}}) is None
    others = [{"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0},
              _record("burst", 8, 2048, None),
              _record("burst", 8, 1024, {"n_conv_rows": 5, "n_tail_slots": 5})]
    assert slots(_run(others), {}) is None
    assert slots(_run([_record("burst", 8, 1024, {"n_state_slots": 5120})], shapes=None),
                 {}) is None
    run = _run(others, shapes=None)
    assert roofline(run, {}) is None and "selective_scan" not in run["facts"]


def test_the_least_bytes_are_a_slot_in_and_out_and_a_rows_operands():
    from benchmark.readers import jamba
    # a decode step of 256 sequences, one layer: 256 x 640 KB + 256 x 60 KB = 183.5 MB
    assert jamba.scan_bytes(256, 256, 5120, 16) == 256 * 2 * 16 * 5120 * 4 + 256 * (
        3 * 5120 + 32) * 4 == 183533568
    # the census' count (tools/kernel_census.py --scan) is the same function of the same shapes
    from tools import kernel_census
    assert jamba.scan_bytes(3, 510, 5120, 16) == kernel_census.scan_bytes(3, 510, 16, 5120) \
        == 33365760
    # a decode row: 0.57 M operations on 0.7 MB - under an operation a byte: the bound is HBM
    assert jamba.scan_flops(1, 5120, 16) / jamba.scan_bytes(1, 1, 5120, 16) < 1.0
    # a run's later row: the same operations on 60 KB - 9 a byte, and 82 k exp: not HBM's
    assert jamba.scan_flops(1, 5120, 16) / jamba.scan_bytes(0, 1, 5120, 16) > 9.0


def test_the_share_pattern_names_the_kernel_and_the_pool_pattern_the_pools():
    bench = spec.Benchmark(ROOT)
    with open(bench.path("layer_metrics", "selective_scan_share.chatloop.json")) as f:
        pattern = re.compile(json.load(f)["kernels"])
    assert pattern.search("selective_scan.7 custom-call f32[26,257,16,5120]")
    assert not pattern.search("paged_decode_attention.3 custom-call bf16[512,20,128]")
    assert not pattern.search("fusion.12 fusion f32[512,5120]")
    pool = re.compile(r"\[(26,)?257,16,5120\]")                # readers/jamba.trace_facts' first
    assert pool.search("fusion.3 fusion f32[257,16,5120]")
    assert pool.search("copy.1 copy f32[26,257,16,5120]")
    assert not pool.search("scatter.1 scatter bf16[26,257,3,5120]")
    assert not pool.search("fusion.9 fusion f32[512,5120]")
