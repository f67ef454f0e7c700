"""Tests of what the ``minicpm-sala-16l`` configuration and its cell add to
the benchmark: the cell rehearsed on the CPU through the unchanged
``run.py``, the reference's copy against the program's own reference, the
controls' recipe, and the new readers on recorded records. Like
``test_benchmark.py`` they are the benchmark's, not tier-1's
(``python -m pytest benchmark/tests -q``).
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

from benchmark.harness import reference_sala, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "minicpm-sala-longdoc", "minicpm-sala-16l"
OWN = ("sparse_read_share.longdoc", "sparse_attn_roofline.longdoc", "linear_step_share.longdoc")


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_sala" and len(cell["why"]) <= 200
    assert len(bench.configs[CONFIG]["why"]) <= 200
    assert sorted(config["reduced"]) == ["mixer_types", "num_hidden_layers"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    traffic = bench.traffic(cell["traffic"])
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 24
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 10240, "hi": 24576}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 512, "hi": 2048}
    assert (traffic["cycle_requests"], traffic["block_requests"], traffic["preroll_s"],
            traffic["tail_s"]) == (48, 12, 60.0, 8.0)      # every one ISSUE 34's
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    layer = bench.metrics_of(CELL, "per_layer")
    assert {"hbm_peak.longdoc", "state_pool_copy_share.longdoc", "compile_s", *OWN} <= set(layer)
    assert all(name.endswith(".longdoc") or name == "compile_s" for name in layer)
    assert all(m["moves"] in ("serve_tok_s", "setup_s") for m in layer.values())
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    engine = config["engine"]
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] == 24
    assert engine["max_context"] == traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"]
    sparse = config["assumed"]["sparse_config"]
    assert engine["kv_block_size"] == sparse["block_size"] == 64
    assert traffic["prompt_tokens"]["lo"] > sparse["dense_len"]      # every prompt selects
    # the reference check's first sequence is prefilled sparsely, its second crosses
    # dense_len while it decodes
    first, second = config["reference"]["sample_lengths"]
    assert first >= sparse["dense_len"] > second > sparse["dense_len"] - config["reference"]["decode_steps"]


def test_the_published_keys_are_unchanged_but_the_cut_in_depth():
    """Every number of the catalog's ``config`` under the same key; only
    ``num_hidden_layers`` and ``mixer_types`` differ, neither a width, and
    the cut is the even-numbered entries of the published list."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "MiniCPM-SALA")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    differ = sorted(k for k, v in entry["config"].items() if model.get(k, "missing") != v)
    assert differ == sorted(config["reduced"]) == ["mixer_types", "num_hidden_layers"]
    assert model["published"] == {k: entry["config"][k] for k in differ}
    assert model["mixer_types"] == entry["config"]["mixer_types"][0::2]
    assert model["layer_ids"] == list(range(0, 32, 2)) and model["num_hidden_layers"] == 16
    assert model["mixer_types"].count("minicpm4") * 3 == model["mixer_types"].count("lightning-attn")
    runner = spec.Benchmark(ROOT).load("runners", "serve_sala", "run").__globals__
    from deepspeed_tpu.models.minicpm_sala import MINICPM_SALA_CONFIGS
    assert runner["sala_config"](config) == MINICPM_SALA_CONFIGS["minicpm-sala-16l"]


def test_rehearsal_at_debug_size_on_the_cpu(tmp_path):
    out = run_cell(rehearsal_root(tmp_path), CELL, "--rehearse", "--seconds", "3")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert {"setup_s", "serve_tok_s"} <= set(line["rehearsal"]["metrics"])
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["state_kind"] == "sparse_kv+slots"
    assert set(facts["attention_impls"].values()) == {"xla_gather"}
    assert facts["sala_shapes"] == {"sparse_layers": 2, "linear_layers": 4, "heads": 4,
                                    "kv_heads": 2, "head_dim": 16, "block_size": 16, "topk": 4,
                                    "itemsize": 2}
    assert set(facts["state_extra_bytes"]) == {"pooled_keys", "slots"}
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == 4 * 11
    assert check["max"] < 0.03
    alone = check["sparse_layer"]
    assert alone["agrees"] and alone["positions"] == 2 * 110 and alone["rows_selecting"] > 50
    assert alone["same_share_selecting"] > 0.9
    window = facts["window"]
    assert window["prompts"] and all(sent <= begun <= first for sent, begun, first, _ in window["prompts"])
    assert window["serve_tok_s_by_wait"] > 0 and window["generated_tok_s"] > 0


class _Flight:
    def __init__(self, sent, first, prompt_len):
        self.sent, self.first, self.prompt_len = sent, first, prompt_len


class _Client:
    def __init__(self, flights, open_at, close_at, generated=0):
        self.done, self.live = flights[:1], flights[1:]
        self.open_at, self.close_at, self.generated_in_window = open_at, close_at, generated

    def in_window(self, t):
        return self.open_at <= t < self.close_at


@pytest.mark.parametrize("flights, window, want, by_wait", [
    # no prompt waits for another's prefill: serve.py's rule, to the token
    ([(0, 4, 100), (6, 10, 200), (12, 20, 400)], (2, 16), 50 + 200 + 200, 50 + 200 + 200),
    # three sent at once, computed one after another: each counts where it was computed
    ([(0, 10, 100), (0, 20, 200), (0, 30, 300)], (15, 25), 100 + 150, 50 + 100),
    # a prompt sent while another is computed is begun at that one's first token
    ([(0, 10, 100), (4, 20, 200)], (10, 20), 200, 200 * 10 / 16),
    # none waiting any more: a later prompt is begun when it is sent
    ([(0, 10, 100), (14, 20, 600)], (10, 17), 300, 300),
    # two first tokens in one look of the client: the second counts whole, once, where it came
    ([(0, 10, 100), (0, 10, 200), (0, 20, 300)], (5, 30), 50 + 200 + 300, 50 + 100 + 225),
    # a window over everything counts every prompt once, by either rule
    ([(0, 10, 100), (1, 20, 200), (2, 30, 300), (40, 45, 50)], (-1, 50), 650, 650),
    # a request still waiting for its first token counts nothing
    ([(0, 10, 100), (5, None, 999)], (0, 20), 100, 100),
])
def test_a_prompt_counts_where_it_can_have_been_computed(flights, window, want, by_wait):
    bench = spec.Benchmark(ROOT)
    runner = bench.load("runners", "serve_sala", "run").__globals__
    serve = bench.load("runners", "serve", "run").__globals__
    client = _Client([_Flight(*f) for f in flights], *window, generated=7)
    assert runner["window_tokens"](client) == pytest.approx(7 + want)
    assert serve["window_tokens"](client) == pytest.approx(7 + by_wait)
    spans = runner["prompt_spans"](client)
    assert [f.first for f, _ in spans] == sorted(f.first for f, _ in spans)
    assert all(f.sent <= begun for f, begun in spans)


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("sala")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_sala", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


@pytest.mark.parametrize("prompt", [None, 40])
def test_the_references_copy_agrees_with_the_programs_reference(debug_engine, prompt):
    """Two plain references written apart (this one reads the config's
    file, the program's reads its dataclass): the same logits on the same
    seeded weights, to float32 rounding, through both branches of
    ``dense_len`` (a prompt of all 100 tokens: sparse from the first row;
    a prompt of 40: dense until the context is 64)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.minicpm_sala import reference_logits
    _, config, runner, engine = debug_engine
    model = runner["with_sparse"](config)["model"]
    ids = np.random.default_rng(5).integers(0, 256, (2, 100), dtype=np.int32)
    mine = np.asarray(reference_sala.logits(engine.params, jnp.asarray(ids), model, prompt))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), engine.model_config,
                                         prompt_len=prompt))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    tapped = []
    rows, margins = reference_sala.rows_at(engine.params, jnp.asarray(ids),
                                           np.asarray([[99, 60], [99, 3]]), model,
                                           tap=lambda *t: tapped.append(t))
    assert margins.shape == (2, 2, 2) and len(tapped) == 4
    at = np.asarray(reference_sala.head_at(engine.params, rows, model))
    assert np.allclose(at[0, 1], np.asarray(reference_sala.logits(
        engine.params, jnp.asarray(ids), model, 100))[0, 60], atol=1e-5)
    x, y, chosen, margin = tapped[0]
    assert x.shape == y.shape == (100, 64) and chosen.shape == (2, 100, 7) and margin.shape == (100,)
    assert np.isinf(np.asarray(margin)[:64]).all() and np.isfinite(np.asarray(margin)[64:]).all()


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_sala
    bench, config, _, _ = debug_engine
    return control_sala.measure(bench, config, 3000000019, True)


def test_the_float8_control_comes_out_as_not_correct(controls):
    assert controls["program"]["agrees"] and controls["program_sparse_layer"]["agrees"]
    assert not controls["float8"]["agrees"]
    assert controls["float8"]["min"] > 2 * controls["program"]["max"]


def test_reading_the_wrong_blocks_comes_out_as_not_correct(controls):
    """As many blocks, ascending, the forced ones kept: only *which*
    blocks differs, and both the selection's agreement and the mixer's
    output say so; the rows that read every block are untouched."""
    wrong, right = controls["wrong_blocks_sparse_layer"], controls["program_sparse_layer"]
    assert not wrong["agrees"]
    assert wrong["same_share_selecting"] < 0.5 < 0.9 < right["same_share_selecting"]
    assert wrong["median_selecting"] > 10 * right["median_selecting"]
    assert wrong["max_not_selecting"] == right["max_not_selecting"]


# ------------------------------------------------------------------ the readers
def _record(kind, k, n_tokens, counts, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": n_tokens, "n_prompt_tokens": n_prompt,
            "counts": counts}


def _run(records, shapes=True):
    bursts = [r for r in records if r["kind"].startswith("burst")]
    mixed = [r for r in records if r["kind"] == "put"]
    facts = {"sala_shapes": {"sparse_layers": 4, "linear_layers": 12, "heads": 32, "kv_heads": 2,
                             "head_dim": 128, "block_size": 64, "topk": 64, "itemsize": 2}}
    return {"trace": object(), "trace_window_s": 6.0, "facts": facts if shapes else {},
            "_program_spans": {"bursts": bursts, "mixed": mixed}}


def test_the_read_share_on_recorded_records():
    read = spec.Benchmark(ROOT).load("readers", "sala", "sparse_read_share")
    records = [
        # 16 decode steps of 24 rows at ~17k context: 64 of 270 blocks a (row, head, layer)
        _record("burst", 16, 16 * 24, {"n_blocks_selected": 16 * 24 * 8 * 64,
                                       "n_blocks_context": 16 * 24 * 8 * 270,
                                       "n_linear_rows": 16 * 24 * 12}),
        # a 512-row prompt chunk at positions 2048..2559: every block read (33-40 of them)
        _record("put", 1, 512, {"n_blocks_selected": 512 * 8 * 36, "n_blocks_context": 512 * 8 * 36,
                                "n_linear_rows": 512 * 12}, n_prompt=512)]
    run = _run(records)
    want = 100.0 * (16 * 24 * 64 + 512 * 36) / (16 * 24 * 270 + 512 * 36)
    assert read(run, {}) == pytest.approx(want)
    assert run["facts"]["sparse_read"]["share_by_kind"]["burst"] == pytest.approx(100 * 64 / 270)
    assert run["facts"]["sparse_read"]["share_by_kind"]["mixed"] == pytest.approx(100.0)
    assert run["facts"]["sparse_read"]["linear_rows"] == (16 * 24 + 512) * 12


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, or with a program whose records carry no such
    counts (the parent's, or another model kind's), the metric is left
    out: no raise."""
    load = spec.Benchmark(ROOT).load
    others = [{"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0},
              {"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0, "counts": None},
              _record("burst", 8, 2048, {"n_picks_held": 1, "n_picks_zero": 1, "n_groups_live": 1})]
    for fn in ("sparse_read_share", "sparse_attn_roofline"):
        reader = load("readers", "sala", fn)
        assert reader({"trace": None, "facts": {}, "observed": {}}, {}) is None
        assert reader({"trace": None, "trace_window_s": None, "facts": {}}, {}) is None
    assert load("readers", "sala", "sparse_read_share")(_run(others), {}) is None


def test_the_byte_count_and_the_shape_patterns():
    """The roofline's bytes are ISSUE 34's: selected blocks x 64 x 128 x K
    and V x 2 B + queries and outputs; 16 operations a byte, so HBM bounds
    it. The shape patterns match this kind's pools and linear-step ops and
    none of a layer's ordinary ops (names as the chip's traces have them)."""
    bench = spec.Benchmark(ROOT)
    sala = bench.load("readers", "sala", "kernel_bytes").__globals__
    blocks, rows = 512 * 2 * 64 * 4, 512
    moved = sala["kernel_bytes"](blocks, rows, 4, 32, 128, 64, 2)
    assert moved == blocks * 64 * 128 * 2 * 2 + rows * 4 * 32 * 128 * 2 * 2
    assert 8.5e9 < moved < 8.7e9                       # ISSUE 34: 8.6 GB a 512-token step
    flops = sala["kernel_flops"](blocks, 32, 2, 128, 64)
    assert 15 < flops / moved < 17
    pool = re.compile(bench.layer_metric("state_pool_copy_share.longdoc")["kernels"])
    linear = re.compile(bench.layer_metric("linear_step_share.longdoc")["kernels"])
    weights = re.compile(bench.layer_metric("weight_copy_share.longdoc")["kernels"])
    engine = bench.config(CONFIG)["engine"]
    assert engine["num_kv_blocks"] == 8192 and engine["max_tracked_sequences"] + 1 == 25
    for name in ("scatter.3 scatter bf16[8,8192,64,128]", "copy.1 copy bf16[8,8192,4,128]",
                 "bitcast.2 copy bf16[1,65536,64,128]", "scatter.9 scatter f32[12,25,32,128,128]"):
        assert pool.search(name), name
    for name in ("fusion.12 fusion f32[25,32,128,128]", "fusion.7 fusion f32[32,512,512]",
                 "fusion.8 fusion bf16[512,25,32,128]", "copy.4 copy f32[12,25,32,128,128]"):
        assert linear.search(name), name
    for name in ("copy.262 copy bf16[12,4096,4096]", "fusion.1 fusion bf16[1,4096,16384]"):
        assert weights.search(name), name
    ordinary = ["fusion.373 fusion bf16[512,4096]", "fusion.2 fusion bf16[512,16384]",
                "paged_decode_attention.3 custom-call bf16[1024,16,128]",
                "fusion.5 fusion bf16[512,73448]", "fusion.6 fusion f32[512,2,16,1663]"]
    for pattern in (pool, linear, weights):
        assert not any(pattern.search(name) for name in ordinary)
