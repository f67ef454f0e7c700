"""Tests of what the ``nemotron3-super-ep4-11l`` configuration and its cell
add to the benchmark: the cell rehearsed on the CPU through the unchanged
``run.py``, the reference's copy against the program's own reference, the
share-aware margin, the controls' recipe, and the readers of the step
records' counts on a recorded record. Like ``test_benchmark.py`` they are
the benchmark's, not tier-1's (``python -m pytest benchmark/tests -q``).
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

from benchmark.harness import reference_nemotron_h, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "nemotron3-super-agents", "nemotron3-super-ep4-11l"
AGENTS = ("ssm_state_share.agents", "state_slots_per_step.agents", "held_rows_per_expert.agents",
          "held_groups_empty.agents")
CUTS = ["hybrid_override_pattern", "n_routed_experts", "num_hidden_layers", "vocab_size"]


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_nemotron" and len(cell["why"]) <= 200
    assert sorted(config["reduced"]) == CUTS
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert {"attention_positions", "state_dtype"} <= set(config["assumed"])
    traffic = bench.traffic(cell["traffic"])
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 128
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 256, "hi": 2048}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 512, "hi": 2048}
    assert (traffic["cycle_requests"], traffic["block_requests"], traffic["preroll_s"],
            traffic["tail_s"]) == (256, 16, 20.0, 8.0)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    layer = bench.metrics_of(CELL, "per_layer")
    assert set(layer) == {*AGENTS, "compile_s"}
    assert all(m["moves"] in ("serve_tok_s", "setup_s") for m in layer.values())
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert len(bench.doc["per_layer"]) <= 128
    engine = config["engine"]
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] \
        == traffic["clients"]
    assert engine["max_context"] >= traffic["prompt_tokens"]["hi"] + traffic["output_tokens"]["hi"]
    # every admitted request's worst case fits the pool: no client waits at the gate
    assert engine["num_kv_blocks"] - 1 >= traffic["clients"] * (
        engine["max_context"] // engine["kv_block_size"])


def test_the_published_keys_are_unchanged_but_the_four_cuts():
    """Every number of the catalog's ``config`` under the same key; only
    the keys listed in ``reduced`` differ, none of them a width, and the
    file states the published values and the deployment beside them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f)
                     if e["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    differ = sorted(k for k, v in entry["config"].items() if model.get(k, "missing") != v)
    assert differ == sorted(config["reduced"]) == CUTS
    assert model["published"] == {k: entry["config"][k] for k in differ}
    first, last = model["share"]["published_layers"]
    pattern = entry["config"]["hybrid_override_pattern"]
    assert model["hybrid_override_pattern"] == pattern[first:last + 1] == "EMEMEMEMEM*"
    assert model["num_hidden_layers"] == len(model["hybrid_override_pattern"]) == 11
    # a whole period at the published ratio, and the guide's floors
    assert [pattern.count(t) // 8 for t in "ME*"] == [5, 5, 1]
    assert model["n_routed_experts"] >= 8 and model["vocab_size"] * 8 >= 131072
    assert model["share"]["expert_parallel_ranks"] * model["n_routed_experts"] == 512
    assert model["share"]["pipeline_stages"] * model["num_hidden_layers"] == 88


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
    assert facts["nemotron_shapes"] == {"mamba_layers": 4, "attn_layers": 1, "expert_layers": 3,
                                        "slots": 8}
    assert facts["expert_share"] == {"moe_topk": 3, "expert_layers": 3, "experts_held": 4,
                                     "routed": 8, "zero": 0}
    # a slot is a row of both entries: 4 layers x (4 x 32 x 16 float32 + 3 x 192 bf16)
    assert facts["slot_bytes"] == 4 * (4 * 32 * 16 * 4 + 3 * 192 * 2)
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["positions"] == (3 + 3) * 13
    assert check["largest_under_tolerance"] < 0.02
    mamba = check["mamba_layer"]
    assert mamba["agrees"] and mamba["rows"] == 152 and mamba["positions"] == 4 * 152
    assert mamba["state_max"] < 0.0015 and mamba["tail_max"] < 0.01
    alone = check["expert_layer"]
    assert alone["agrees"] and alone["positions"] == 3 * (3 + 3) * 13
    assert alone["held_positions"] > 50 and alone["max"] < 0.01


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("nemotron")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_nemotron", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's
    file, the program's reads its dataclass), given the same share - the
    second half of the routed experts here: the same logits on the same
    seeded weights, to float32 rounding; and the same state and tail of an
    ``M`` layer."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.nemotron_h import reference_logits, reference_mamba
    _, config, runner, engine = debug_engine
    cfg = runner["nemotron_config"](config["model"])
    assert (cfg.n_routed_experts, cfg.held, cfg.first_expert_held) == (8, 4, 4)
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    taps = []
    h, margins, _ = reference_nemotron_h.hidden(
        engine.params, jnp.asarray(ids), config["model"],
        tap=lambda *kept: taps.append(tuple(np.asarray(t) for t in kept)))
    mine = np.asarray(reference_nemotron_h.head_at(engine.params, h, config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), cfg))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    assert margins.shape == (3, 2, 70) and (np.asarray(margins) > 0).all()
    assert [t[0] for t in taps] == [0, 1, 2, 3]
    _, x, y, state, tail = taps[2]
    lp = jax.tree.map(lambda w: w[2], engine.params["model"]["mamba_layers"])
    with jax.default_matmul_precision("highest"):
        want = reference_mamba(lp, jnp.asarray(x), cfg)
    for have, ref in zip((y, state, tail), want):
        assert np.linalg.norm(have - np.asarray(ref)) / np.linalg.norm(np.asarray(ref)) < 1e-5


def test_the_margin_counts_only_the_picks_this_share_computes():
    """A swap between two absent experts does not change which held
    experts are computed, so it does not narrow the margin; one that moves
    a held column in or out does."""
    import jax.numpy as jnp
    D, columns = 16, 8
    kw = dict(top_k=3, scaling=5.0, first=2, held=2)                     # held: columns 2, 3
    x = jnp.ones((1, 1, D), jnp.float32)

    def margin(logits):
        weight = jnp.zeros((D, columns)).at[0].set(jnp.asarray(logits, jnp.float32))
        router = {"weight": weight, "e_score_correction_bias": jnp.zeros(columns)}
        weights, m = reference_nemotron_h._router(x, router, **kw)
        return np.asarray(weights)[0, 0], float(m[0, 0])

    sig = lambda v: 1.0 / (1.0 + np.exp(-np.asarray(v, np.float64)))  # noqa: E731
    # picks 0, 1, 4 (absent); the nearest held column (2) trails the last pick by a lot,
    # the nearest absent one (5) by a hair: the hair does not count
    logits = [3.0, 2.5, 0.0, -1.0, 2.0, 1.999, -2.0, -3.0]
    weights, m = margin(logits)
    assert (weights > 0).tolist() == [True, True, False, False, True, False, False, False]
    assert weights.sum() == pytest.approx(5.0, rel=1e-5)                 # normalised, times 5
    assert m == pytest.approx(sig(2.0) - sig(0.0), rel=1e-4)
    # a held pick (2) that leads the first column left out (5) by a hair: the hair counts
    logits = [3.0, 2.5, 2.0, -1.0, 0.0, 1.999, -2.0, -3.0]
    weights, m = margin(logits)
    assert weights[2] > 0 and m == pytest.approx(sig(2.0) - sig(1.999), rel=1e-2)


def test_the_traffic_is_issue_38s_and_draws_from_the_slice():
    bench = spec.Benchmark(ROOT)
    params, vocab = bench.traffic("agents"), bench.config(CONFIG)["model"]["vocab_size"]
    make = bench.load("generators", params["kind"], "generate")
    a = make(params, 3000000019, 45.0, vocab)
    assert len(a["deck"]) == 256 and a["clients"] == 128 and a["preroll_s"] == 20.0
    assert all(256 <= len(r["prompt"]) <= 2048 and 512 <= r["max_new"] <= 2048
               for r in a["deck"])
    prompts = sum(len(r["prompt"]) for r in a["deck"])
    answers = sum(r["max_new"] for r in a["deck"])
    assert 0.75 < prompts / answers < 0.85                   # ~0.8 prompt tokens a generated one
    assert max(int(r["prompt"].max()) for r in a["deck"]) < vocab == 32768
    # one to four SplitFuse chunks: some prompts cross three chunk boundaries
    assert sum(len(r["prompt"]) > 1536 for r in a["deck"]) > 20


def _experts_that_weigh(engine):
    """At the published widths a held pick is a few hundredths of the
    layer's output beside the shared expert; at the debug widths (a
    latent of 32, experts 48 wide) a thousandth: scale the debug model's
    expert down projections so that a held pick counts, as in the cell."""
    import jax
    engine.params = jax.tree.map(lambda w: w, engine.params)
    experts = engine.params["model"]["moe_layers"]["experts"]
    experts["down_proj"] = experts["down_proj"] * 64


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_nemotron
    bench, config, _, _ = debug_engine
    return control_nemotron.measure(bench, config, 3000001201, rehearse=True,
                                    prepare=_experts_that_weigh)


def test_the_float8_control_comes_out_as_not_correct(controls):
    got = controls
    assert got["program"]["agrees"] and got["program"]["largest_under_tolerance"] < 0.02
    assert got["program"]["mamba_layer"]["agrees"] and got["program"]["expert_layer"]["agrees"]
    assert not got["float8"]["agrees"]
    assert got["float8"]["min"] > 2 * got["program"]["max"]


def test_a_state_carried_in_bfloat16_comes_out_as_not_correct(controls):
    program, faulty = controls["program"]["mamba_layer"], controls["state_bf16"]["mamba_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert min(faulty["states"]) > 2 * max(program["states"])
    assert faulty["tail_max"] == pytest.approx(program["tail_max"])      # the tail is not at fault


def test_a_held_pick_left_out_comes_out_as_not_correct(controls):
    program, faulty = controls["program"]["expert_layer"], controls["held_left_out"]["expert_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["held_positions"] == program["held_positions"] > 50
    assert faulty["held_over"] > 0.9 * faulty["held_positions"]


# ------------------------------------------------- the readers of the step records' counts
def _record(kind, k, n_tokens, counts, n_prompt=0):
    return {"kind": kind, "k": k, "n_tokens": n_tokens, "n_prompt_tokens": n_prompt,
            "counts": counts}


def _run(records, shapes={"mamba_layers": 5, "attn_layers": 1, "expert_layers": 5, "slots": 128},
         share={"moe_topk": 22, "expert_layers": 5, "experts_held": 128, "routed": 512,
                "zero": 0}):
    bursts = [r for r in records if r["kind"].startswith("burst")]
    mixed = [r for r in records if r["kind"] == "put"]
    return {"trace": object(), "trace_window_s": 6.0,
            "facts": {"nemotron_shapes": shapes, "expert_share": share},
            "_program_spans": {"bursts": bursts, "mixed": mixed}}


def test_the_readers_on_a_recorded_record():
    bench = spec.Benchmark(ROOT)
    slots, rows, empty = (bench.reader(name) for name in AGENTS[1:])
    records = [
        # a burst of 4 steps of 128 sequences, 5 M layers: 4 x 128 x 5 slot reads and writes;
        # 4 x 128 x 22 x 5 picks of which a quarter held
        _record("burst", 4, 512, {"n_picks_held": 14080, "n_picks_zero": 0, "n_groups_live": 2500,
                                  "n_ssm_rows": 2560, "n_state_slots": 2560}),
        # one mixed step: 100 decode rows and 412 rows of two prompts
        _record("put", 1, 512, {"n_picks_held": 14100, "n_picks_zero": 0, "n_groups_live": 640,
                                "n_ssm_rows": 2560, "n_state_slots": 510}, n_prompt=412)]
    run = _run(records)
    assert slots(run, {}) == pytest.approx((2560 + 510) / (5 * 5))
    assert run["facts"]["state_slots"] == {"records": 2, "model_steps": 5,
                                           "n_state_slots": 3070, "n_ssm_rows": 5120}
    assert rows(run, {}) == pytest.approx(28180 / (5 * 5 * 128))
    assert empty(run, {}) == pytest.approx(100.0 * (1 - 3140 / (5 * 5 * 128)))


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no such
    count (the parent's, or another model kind's) or a runner that states
    no shapes, the metric is left out: no raise."""
    reader = spec.Benchmark(ROOT).reader("state_slots_per_step.agents")
    assert reader({"trace": None, "facts": {}, "observed": {}}, {}) is None
    others = [{"kind": "burst", "k": 8, "n_tokens": 2048, "n_prompt_tokens": 0},
              _record("burst", 8, 2048, None),
              _record("burst", 8, 2048, {"n_picks_held": 1, "n_picks_zero": 1,
                                         "n_groups_live": 1})]
    assert reader(_run(others), {}) is None
    assert reader(_run([_record("burst", 8, 1024, {"n_state_slots": 5120})], shapes=None),
                  {}) is None


def test_the_state_share_pattern_names_the_states_ops_and_no_others():
    """``ssm_state_share.agents``: the shapes only the slot pool and the
    packed recurrence have, as XLA's HLO for v5e names them (PR 38's
    compile of the 128- and 512-row programs), and none of a layer's
    matmuls or of the paged or grouped kernels."""
    pattern = re.compile(spec.Benchmark(ROOT).layer_metric(AGENTS[0])["kernels"])
    state = ["multiply_reduce_fusion.7 fusion f32[129,128,64]{2,1,0} f32[129,128,64,128]",
             "fusion.441 fusion f32[129,8,16,64,128]", "scatter.67 scatter f32[5,129,128,64,128]",
             "while.74 while f32[129,128,64,128]", "fusion.12 fusion bf16[129,3,10240]",
             "scatter.3 scatter bf16[5,129,3,10240]", "fusion.8 fusion f32[128,512,512]",
             "fusion.9 fusion f32[8,128,128]", "convolution.2 fusion bf16[128,128,128]"]
    others = ["fusion.373 fusion bf16[512,18560]", "gmm_ragged_dot.41 custom-call bf16[12288,2688]",
              "paged_decode_attention.3 custom-call bf16[512,32,128]",
              "fusion.1 fusion bf16[512,4096]",
              "fusion.5 fusion bf16[128,32768]", "fusion.6 fusion f32[512,512]",
              "scatter.1 scatter bf16[1,8193,64,256]", "fusion.2 fusion bf16[128,10240]"]
    assert all(pattern.search(name) for name in state)
    assert not any(pattern.search(name) for name in others)
