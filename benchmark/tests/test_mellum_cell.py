"""Tests of what the ``mellum2-12b-ep4-4l`` configuration and its cell add to
the benchmark: the files found by name, the cell rehearsed on the CPU through
the unchanged ``run.py`` (a mesh ``expert=4`` of four virtual devices), the
reference's copy against the program's own reference, the controls' recipe,
and the readers' arithmetic. Like ``test_benchmark.py`` they are the
benchmark's, not tier-1's (``python -m pytest benchmark/tests -q``).
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_mellum, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "mellum2-12b-moe8k-x4", "mellum2-12b-ep4-4l"


def _runner():
    return spec.Benchmark(ROOT).load("runners", "train_mellum", "run").__globals__


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 4 and cell["runner"] == "train_mellum" and len(cell["why"]) <= 200
    assert len(bench.configs[CONFIG]["why"]) <= 200 and len(bench.workloads[CELL]["why"]) <= 200
    assert sorted(config["reduced"]) == ["layer_types", "mlp_layer_types", "num_hidden_layers"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert {"block", "qk_norm", "router", "mtp", "load_balancing", "dtype", "intermediate_size",
            "seeded_parameters"} <= set(config["assumed"])
    assert "four-chip v5e host" in config["deployment"]
    traffic = bench.traffic(cell["traffic"])
    assert (traffic["kind"], traffic["sequences_per_step"], traffic["seq_len"]) == \
        ("train_steps", 4, 8192)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"train_tok_s_chip", "setup_s"}
    # BENCHMARK.json's per_layer holds the 128 metrics it may hold: the cell's own are files
    # the runner reads into facts, and enters none
    assert len(bench.doc["per_layer"]) == 128
    assert set(bench.metrics_of(CELL, "per_layer")) == {"compile_s"}
    fours = sum(w["chips"] == 4 for w in bench.doc["workloads"])
    assert fours == 2 <= len(bench.doc["workloads"]) // 4
    trainer, ref = config["trainer"], config["reference"]
    assert (trainer["zero_stage"], trainer["expert_parallel"], trainer["moe_aux_loss_coef"]) == \
        (2, 4, 0.001)
    assert trainer["remat_policy"] in ("moe", "full")
    assert {"tolerance", "nll_tolerance", "grad_norm_tolerance", "half_tolerance", "route_margin",
            "picks_differ_max", "why"} <= set(ref)


def test_the_file_changes_only_depth_from_the_catalog_row():
    """Every key of the published ``config.json`` (the catalog's row) is in the
    file under its name with its value, but the three that ``reduced`` lists,
    which are the published ones cut to their first period."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f if "Mellum2-12B-A2.5B" in line)
    config = spec.Benchmark(ROOT).config(CONFIG)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            continue
        assert config["model"][key] == value, key
    assert config["model"]["num_hidden_layers"] == 4
    assert config["model"]["layer_types"] == row["config"]["layer_types"][:4]
    assert config["model"]["mlp_layer_types"] == row["config"]["mlp_layer_types"][:4]
    from deepspeed_tpu.models.mellum import MELLUM_CONFIGS
    published = _runner()["mellum_config"](dict(row["config"]))
    assert published == MELLUM_CONFIGS["mellum2-12b"]


def test_the_cells_own_metric_files_are_whole_and_name_readers_that_load():
    bench, names = spec.Benchmark(ROOT), _runner()["MOE8K_METRICS"]
    assert len(names) == 12 and len(set(names)) == 12
    layers = {m["layer"] for m in bench.doc["per_layer"]}
    for name in names:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            m = json.load(f)
        assert m["cells"] == [CELL] and m["moves"] == "train_tok_s_chip", name
        assert m["source"] in spec.SOURCES and spec.UNIT.match(m["unit"]), name
        assert m["better"] in ("lower", "higher") and spec.NAME.match(name), name
        module, _, attr = m["reader"].partition(":")
        assert callable(bench.load("readers", module.partition(".")[2], attr)), name
        if "roofline" in name or "mfu" in name:
            assert m["unit"] == "%" and m["better"] == "higher", name
        assert m["layer"] in layers or "expert exchange" in m["layer"], name


def test_rehearsal_at_debug_size_on_the_cpu(tmp_path):
    out = run_cell(rehearsal_root(tmp_path), CELL, "--rehearse", "--seconds", "2")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert {"setup_s", "train_tok_s_chip"} <= set(line["rehearsal"]["metrics"])
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["mesh"]["expert"] == 4
    assert facts["rows_beyond_passes"] == 0
    counts = facts["step_counts_last"]
    assert counts["n_expert_rows"] == 4 * 4 * 32 * 2 and counts["n_share_passes"] == 4
    assert all(value < limit for value, limit in facts["check"].values())
    assert {"nll_max_abs", "loss_abs", "grad_norm_rel", "attn_window_out", "attn_window_dx",
            "attn_full_out", "attn_full_dx", "experts_out", "experts_dx",
            "experts_picks_differ_share"} == set(facts["check"])


def test_the_controls_fail_the_check_and_the_program_passes_it(tmp_path):
    """``control_mellum.py``'s recipe at debug size on the CPU: every fault of
    the reference comes out as not correct by at least one of the limits."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    import subprocess
    out = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                                       "control_mellum.py"),
                          "--seed", "3000000019", "--root", rehearsal_root(tmp_path), "--rehearse"],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
    found = json.loads(next(l for l in out.stdout.splitlines() if l.startswith("{")))
    assert found["program"]["agrees"] and found["program"]["over"] == []
    assert set(found["controls"]) == set(reference_mellum.FAULTS)
    for fault, entry in found["controls"].items():
        assert not entry["agrees"] and entry["over"], fault
    by = {fault: set(entry["over"]) for fault, entry in found["controls"].items()}
    assert {"attn_window_out", "attn_window_dx"} <= by["window_as_full"]
    assert {"attn_full_out", "attn_full_dx"} <= by["full_as_window"] & by["yarn_left_out"]
    for fault in ("topk_not_normalised", "one_pick_fewer", "one_rank_left_out"):
        assert {"experts_out", "experts_dx"} <= by[fault], fault


def test_the_benchmarks_reference_is_the_programs():
    """``harness/reference_mellum.py`` (blocked, a layer at a time) against
    ``deepspeed_tpu.models.mellum.reference_loss`` and ``jax.grad`` of it (whole,
    plain): loss, per-position NLL and the gradient's norm at debug size,
    float32 weights: 1e-5."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import mellum
    cfg = mellum.MELLUM_CONFIGS["mellum2-debug"]
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "debug", "configs",
                           f"{CONFIG}.json")) as f:
        model = {k: v for k, v in json.load(f).items() if k not in spec.CONFIG_GROUPS}
    model.update(num_hidden_layers=8, layer_types=list(cfg.layer_types),
                 mlp_layer_types=list(cfg.mlp_layer_types))
    params = mellum.seeded_params(cfg, 5)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 32)), jnp.int32)
    want_loss, grads = jax.value_and_grad(lambda p: mellum.reference_loss(p, ids, cfg))(params)
    want_nll, _ = mellum.reference_nll(params, ids, cfg)
    want_norm = float(jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))))
    nll, loss, streams = reference_mellum.forward(params, ids, model, coef=0.001)
    norm, parts = reference_mellum.grad_norm(params, ids, model, streams, coef=0.001)
    np.testing.assert_allclose(nll, want_nll, atol=1e-5)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(norm - want_norm) < 1e-5 * want_norm
    assert len(parts) == 3 + 8 * 10


def test_the_readers_count_the_work_the_mathematics_needs():
    from benchmark.readers import mellum as work
    assert work.attention_pairs(8192) == 8192 * 8193 // 2
    assert work.attention_pairs(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024
    assert work.attention_pairs(64, 100) == work.attention_pairs(64)
    shapes = {"seq_len": 8192, "model": {
        "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "num_experts": 64, "num_experts_per_tok": 8, "moe_intermediate_size": 896,
        "vocab_size": 98304, "sliding_window": 1024,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}}
    flops = work.forward_flops_per_token(shapes)
    # 4 x 141.9 MF of matrices (projections 42.5, router 0.3, 8 picks x 12.4), 453 MF of head,
    # attention 4 x 128 x 32 x the mean keys a query sees: 3 x 15.7 MF (a band of 1024) + 67.1 MF
    # (the triangle's 4096.5): 1.135 GF, ISSUE 58's figure
    assert 1.13e9 < flops < 1.14e9
    assert work.expert_flops(1000, 2304, 896) == 1000 * 2 * 2304 * 896 * 9

    trace = {"devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_body", 0, 1000], ["jit_body", 1000, 1000], ["tiny", 2000, 10]],
        "XLA Ops": [["flash_window_fwd.1 custom-call (tuple)", 10, 100],
                    ["flash_window_dq.1 custom-call bf16[32,8192,128]", 200, 50],
                    ["flash_attention_fwd.2 custom-call (tuple)", 300, 70],
                    ["gmm_ragged_dot.3 custom-call bf16[86016,896]", 1100, 200],
                    ["flash_window_fwd.1 custom-call (tuple)", 1400, 100]]}}, "host": []}
    seconds, steps = work.step_seconds(trace, work.WINDOW_KERNELS.match)
    assert steps == 2 and seconds == pytest.approx((150 + 100) / 2 / 1e9)
    assert work.step_seconds(trace, work.EXPERT_KERNELS.search)[0] == pytest.approx(100 / 1e9)
    assert work.train_mfu({"facts": {}, "trace": trace}, {}) is None    # a program without the cell
    assert work.moe_exchange_share({"facts": {}, "trace": trace}, {}) is None
