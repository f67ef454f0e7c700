"""Tests of what the ``granite4-h-small-ep4-10l`` configuration and its cell
``granite4-h-small-sessions`` add to the benchmark: the files found by name,
the published keys but the four cuts, the sessions generator (the same
multiset every seed; a turn's prompt extends the one before it), the cell
rehearsed on the CPU through the unchanged ``run.py``, the reference's copy
against the program's own reference, the controls' recipe, and the readers on
a recorded line. Like ``test_benchmark.py`` they are the benchmark's, not
tier-1's (``python -m pytest benchmark/tests -q``).
"""

import collections
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_granite, spec  # noqa: E402
from benchmark.tests.test_benchmark import rehearsal_root, run_cell  # noqa: E402

CELL, CONFIG = "granite4-h-small-sessions", "granite4-h-small-ep4-10l"
CUTS = ["layer_types", "num_hidden_layers", "num_local_experts", "vocab_size"]


def test_the_files_are_found_by_name_and_the_contract_holds():
    bench = spec.Benchmark(ROOT)
    assert bench.validate() > 0
    cell, config = bench.cell(CELL), bench.config(CONFIG)
    assert cell["chips"] == 1 and cell["runner"] == "serve_granite" and len(cell["why"]) <= 200
    assert sorted(config["reduced"]) == CUTS and set(config["reduced_why"]) == set(CUTS)
    assert {"seeded_state_space_parameters", "state_dtype", "expert_width"} <= set(config["assumed"])
    traffic = bench.traffic(cell["traffic"])
    # ISSUE 61's parameters
    assert traffic["kind"] == "sessions" and traffic["clients"] == 80
    assert traffic["system_prompts"] == 8 and traffic["system_zipf_s"] == 1.0
    assert traffic["system_tokens"] == {"dist": "loguniform", "lo": 2048, "hi": 4096}
    assert traffic["turns"] == {"lo": 4, "hi": 8}
    assert traffic["message_tokens"] == {"dist": "loguniform", "lo": 256, "hi": 1024}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 64, "hi": 256}
    assert traffic["think_s"] == {"dist": "exponential", "mean": 2.0, "min": 0.5, "max": 8.0}
    assert (traffic["max_prompt_tokens"], traffic["preroll_s"]) == (14336, 20.0)
    assert set(bench.metrics_of(CELL, "end_to_end")) == {"serve_tok_s", "setup_s"}
    assert set(bench.metrics_of(CELL, "per_layer")) == {"compile_s"}
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 2
    assert len(bench.doc["per_layer"]) <= 128
    engine = config["engine"]
    assert engine["max_context"] >= traffic["max_prompt_tokens"] + traffic["output_tokens"]["hi"]
    # a live slot a tracked sequence, and a snapshot a session and a system prompt beside them
    assert engine["max_ragged_sequence_count"] == engine["max_tracked_sequences"] == 48
    assert engine["snapshot_slots"] == traffic["clients"] + traffic["system_prompts"]
    # the cell's own metrics: a file each, a reader each, no entry
    runner = bench.load("runners", "serve_granite", "run").__globals__
    for name in runner["SESSIONS_METRICS"]:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            metric = json.load(f)
        assert metric["cells"] == [CELL] and metric["moves"] == "serve_tok_s"
        assert metric["layer"] in {m["layer"] for m in bench.doc["per_layer"]}
        module, _, attr = metric["reader"].partition(":")
        assert callable(bench.load("readers", module.partition(".")[2], attr))
        assert name not in bench.per_layer


def test_the_published_keys_are_unchanged_but_the_four_cuts():
    """Every number of the catalog's ``config`` under the same key; only the
    keys listed in ``reduced`` differ, none of them a width, and the file
    states the published values and the deployment beside them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "granite-4.0-h-small")
    config = spec.Benchmark(ROOT).config(CONFIG)
    model = config["model"]
    assert config["source"] == entry["source_url"]
    differ = sorted(k for k, v in entry["config"].items() if model.get(k, "missing") != v)
    assert differ == sorted(config["reduced"]) == CUTS
    assert model["published"] == {k: entry["config"][k] for k in differ}
    first, last = model["share"]["published_layers"]
    types = entry["config"]["layer_types"]
    assert model["layer_types"] == types[first:last + 1] and model["num_hidden_layers"] == 10
    assert model["layer_types"].count("attention") == 1 and types.count("attention") == 4
    assert model["share"]["expert_parallel_ranks"] * model["num_local_experts"] == 72
    assert model["share"]["pipeline_stages"] * model["num_hidden_layers"] == 40
    assert model["num_local_experts"] >= 8 and model["vocab_size"] * 8 >= 100352
    assert model["vocab_size"] * model["share"]["vocabulary_ranks"] == 100352


# ------------------------------------------------------------------- the generator
@pytest.fixture(scope="module")
def decks():
    bench = spec.Benchmark(ROOT)
    params = bench.traffic("sessions")
    make = bench.load("generators", params["kind"], "generate")
    return params, [make(params, seed, 45.0, 25088) for seed in (1, 3_000_000_019)]


def test_the_generator_deals_the_same_multiset_every_seed(decks):
    params, (a, b) = decks

    def multiset(t, of):
        return collections.Counter(of(turn) for s in t["deck"] for turn in s["turns"])

    for of in (lambda t: len(t["message"]), lambda t: t["max_new"],
               lambda t: round(t["think_s"], 6)):
        assert multiset(a, of) == multiset(b, of)
    for of in (lambda s: s["system"], lambda s: len(s["turns"])):
        assert collections.Counter(map(of, a["deck"])) == collections.Counter(map(of, b["deck"]))
    assert [len(s) for s in a["systems"]] == [len(s) for s in b["systems"]]     # rank by rank
    pairs = lambda t: collections.Counter((s["system"], len(s["turns"])) for s in t["deck"])  # noqa: E731
    assert pairs(a) == pairs(b)
    # a block of 16 sessions sends nearly the same prompt tokens, whichever block and seed
    def sent(t):
        out = []
        for s in t["deck"]:
            history, total = len(t["systems"][s["system"]]), 0
            for turn in s["turns"]:
                history += len(turn["message"])
                total += history
                history += turn["max_new"]
            out.append(total)
        return np.asarray(out).reshape(10, 16).sum(axis=1)
    blocks = np.concatenate([sent(a), sent(b)])
    assert blocks.std() / blocks.mean() < 0.03
    assert [s["system"] for s in a["deck"]] != [s["system"] for s in b["deck"]]
    # ISSUE 61's ranges
    assert len(a["systems"]) == 8 and all(2048 <= len(s) <= 4096 for s in a["systems"])
    assert all(4 <= len(s["turns"]) <= 8 for s in a["deck"])
    turns = [t for s in a["deck"] for t in s["turns"]]
    assert all(256 <= len(t["message"]) <= 1024 and 64 <= t["max_new"] <= 256
               and 0.5 <= t["think_s"] <= 8.0 for t in turns)
    assert 1.6 < np.mean([t["think_s"] for t in turns]) < 2.4
    by_system = collections.Counter(s["system"] for s in a["deck"])
    assert by_system[0] > 2.5 * by_system[3] > 0 and len(by_system) == 8      # Zipf s = 1
    assert max(int(t["message"].max()) for t in turns) < 25088
    assert a["clients"] == 80 and a["preroll_s"] == 20.0 and len(a["start"]) == 80
    # the clients enter at every depth of their conversation
    assert {s["turn"] for s in a["start"]} >= {0, 1, 2, 3}
    assert all((s["turn"] == 0) == (len(s["history"]) == 0) for s in a["start"])


def test_a_turns_prompt_extends_the_previous_one(decks):
    """The client's sessions: each turn's prompt is the one before it, the
    answer received and the new message - a true prefix - and never longer
    than the traffic's limit."""
    params, (a, _) = decks
    bench = spec.Benchmark(ROOT)
    runner = bench.load("runners", "serve_granite", "run").__globals__
    entry = a["deck"][0]
    session = runner["Session"](a["systems"][entry["system"]], entry)
    prompts = []
    for turn in entry["turns"]:
        session.history.extend(int(t) for t in turn["message"])
        prompts.append(list(session.history))
        session.history.extend([7] * turn["max_new"])          # the ids "received"
    for before, after, turn in zip(prompts, prompts[1:], entry["turns"]):
        assert after[:len(before)] == before
        assert len(after) == len(before) + turn["max_new"] + (len(after) - len(before)
                                                               - turn["max_new"])
    assert prompts[0][:session.system_len] == [int(t) for t in a["systems"][entry["system"]]]
    assert max(len(p) for p in prompts) <= params["max_prompt_tokens"]


# ------------------------------------------------------------------- the rehearsal
def test_rehearsal_at_debug_size_on_the_cpu(tmp_path):
    out = run_cell(rehearsal_root(tmp_path), CELL, "--rehearse", "--seconds", "3")
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run reports no metric"
    assert {"setup_s", "serve_tok_s"} <= set(line["rehearsal"]["metrics"])
    facts = line["facts"]
    assert facts["compiled_after_warm_up"] == 0 and facts["state_kind"] == "kv+slots"
    assert facts["granite_shapes"]["mamba_layers"] == 4 and facts["granite_shapes"]["slots"] == 14
    check = facts["reference_rel_err"]
    assert check["agrees"] and check["mamba_layer"]["agrees"] and check["expert_layer"]["agrees"]
    assert check["attention_layer"]["agrees"] and check["attention_layer"]["positions"] == 2 * 87
    resume = check["resume"]
    assert resume["agrees"] and resume["cached_tokens"] == resume["boundary"] == 64
    assert resume["snapshots_restored"] == 1 and resume["twin_drift"] == 0.0   # bit for bit
    sessions, cache = facts["sessions"], facts["prefix_cache"]
    assert sessions["turns_ended_in_window"] == line["attempted"] and not sessions["errors"]
    assert sessions["prompt_cached_tokens"] > 0.4 * sessions["prompt_tokens_by_record"]
    assert cache["tokens_saved_by_kind"]["trailing"] > 0
    assert cache["tokens_saved_by_kind"]["breakpoint"] > 0 and cache["snapshots_restored"] > 0


@pytest.fixture(scope="module")
def debug_engine(tmp_path_factory):
    bench = spec.Benchmark(rehearsal_root(tmp_path_factory.mktemp("granite")))
    config = bench.config(CONFIG)
    runner = bench.load("runners", "serve_granite", "run").__globals__
    return bench, config, runner, runner["build_engine"](config, 3000000019, True)


def test_the_references_copy_agrees_with_the_programs_reference(debug_engine):
    """Two plain references written apart (this one reads the config's file,
    the program's reads its dataclass), given the same share - the second half
    of the experts here: the same logits on the same seeded weights."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.granite_hybrid import reference_logits
    _, config, runner, engine = debug_engine
    cfg = runner["granite_config"](config["model"])
    assert (cfg.num_local_experts, cfg.held, cfg.first_expert_held) == (8, 4, 4)
    ids = np.random.default_rng(5).integers(0, 256, (2, 70), dtype=np.int32)
    taps = []
    h, margins, _ = reference_granite.hidden(
        engine.params, jnp.asarray(ids), config["model"],
        tap=lambda *kept: taps.append(kept[0]))
    mine = np.asarray(reference_granite.head_at(engine.params, h, config["model"]))
    theirs = np.asarray(reference_logits(engine.params, jnp.asarray(ids), cfg))
    assert np.linalg.norm(mine - theirs) / np.linalg.norm(theirs) < 1e-5
    assert margins.shape == (6, 2, 70) and taps == [0, 1, 2, 3]


def _experts_that_weigh(engine):
    """At the debug widths a held pick is a thousandth of the layer's output
    beside the shared expert: scale the debug model's expert down projections
    so that a held pick counts, as in the cell."""
    import jax
    engine.params = jax.tree.map(lambda w: w, engine.params)
    experts = engine.params["model"]["moe_layers"]["experts"]
    experts["down_proj"] = experts["down_proj"] * 64
    # likewise the attention scores: 64 wide, queries and keys are too short for the scale of
    # their product to move a logit; at the published 4096 they are not
    attn = engine.params["model"]["attn_layers"]
    attn["q_proj"]["kernel"] = attn["q_proj"]["kernel"] * 4
    attn["k_proj"]["kernel"] = attn["k_proj"]["kernel"] * 4


@pytest.fixture(scope="module")
def controls(debug_engine):
    from benchmark.tests import control_granite
    bench, config, _, _ = debug_engine
    return control_granite.measure(bench, config, 3000001201, rehearse=True,
                                   prepare=_experts_that_weigh)


def test_the_float8_control_comes_out_as_not_correct(controls):
    assert controls["program"]["agrees"] and not controls["float8"]["agrees"]
    assert controls["float8"]["median"] > 2 * controls["program"]["max"]


def test_the_root_of_the_head_size_for_the_multiplier_comes_out_as_not_correct(controls):
    program = controls["program"]["attention_layer"]
    faulty = controls["attention_root"]["attention_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["median"] > 2 * program["max"]


@pytest.mark.parametrize("control", ["resume_zero", "resume_older"])
def test_a_resume_from_the_wrong_state_comes_out_as_not_correct(controls, control):
    program, faulty = controls["program"]["resume"], controls[control]["resume"]
    assert program["agrees"] and program["cached_tokens"] == program["boundary"] > 0
    assert faulty["cached_tokens"] == program["cached_tokens"] and not faulty["agrees"]
    assert program["twin_drift"] == 0.0 and faulty["twin_drift"] > 0.01
    assert min(faulty["states"]) > 2 * program["state_max"]


def test_a_state_carried_in_bfloat16_comes_out_as_not_correct(controls):
    program, faulty = controls["program"]["mamba_layer"], controls["state_bf16"]["mamba_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert min(faulty["states"]) > 2 * max(program["states"])


@pytest.mark.parametrize("control", ["held_left_out", "softmax_all"])
def test_a_control_of_the_feed_forward_comes_out_as_not_correct(controls, control):
    program, faulty = controls["program"]["expert_layer"], controls[control]["expert_layer"]
    assert program["agrees"] and not faulty["agrees"]
    assert faulty["held_over"] > 0.9 * faulty["held_positions"] > 0


# ------------------------------------------------------------------- the readers
def _line(**facts):
    sessions = {"prompt_tokens_by_record": 400000, "prompt_cached_tokens": 352000,
                "resume_ttft_p50_ms": 81.5}
    shapes = {"mamba_layers": 9, "heads": 128, "head_dim": 64, "state_size": 128, "slots": 136}
    share = {"moe_topk": 10, "expert_layers": 10, "experts_held": 18, "routed": 72, "zero": 0,
             "hidden": 4096, "expert_width": 768}
    return {"trace": None, "facts": {"sessions": sessions, "granite_shapes": shapes,
                                     "expert_share": share, **facts},
            "device": {"kind": "TPU v5 lite"}}


def test_the_readers_on_a_recorded_line():
    from benchmark.readers import granite
    bench = spec.Benchmark(ROOT)
    cached = bench.load("readers", "granite", "prompt_cached_share")
    ttft = bench.load("readers", "granite", "resume_ttft_p50_ms")
    run = _line()
    assert cached(run, {}) == pytest.approx(88.0) and ttft(run, {}) == 81.5
    # the kernels' bytes and operations, from the shapes: a live slot in and out, a fresh one out
    assert granite.ssm_state_bytes(9 * 48, 9 * 2, 128, 64, 128) == (2 * 432 - 18) * 4194304
    assert granite.expert_flops(1000, 4096, 768) == 1000 * 2 * 4096 * 768 * 3
    assert granite.expert_bytes(1000, 18, 4096, 768) == (18 * 3 * 4096 * 768
                                                         + 1000 * (2 * 4096 + 2 * 768)) * 2
    # the records whole in the window, given outright: the kernel's time against the roofline
    records = [(0, 100, {"kind": "put", "k": 1, "counts": {
        "n_state_slots": 432, "n_fresh_slots": 18, "n_ssm_rows": 900, "n_picks_held": 1250,
        "n_groups_live": 170, "n_snapshots_taken": 2, "n_snapshots_restored": 1}})]
    run = dict(_line(), trace=object(), trace_window_s=6.0, _program_spans={"offset_ns": 0},
               _granite_records=records)
    assert bench.load("readers", "granite", "state_slots_per_step")(run, {}) == 48.0
    assert run["facts"]["state_slots"]["n_snapshots_taken"] == 2


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a traced run, with a program whose records carry no such count
    (the parent's, or another model kind's) or a runner that states no shapes,
    the metric is left out: no raise."""
    bench = spec.Benchmark(ROOT)
    bare = {"trace": None, "facts": {}, "observed": {}, "device": {"kind": "TPU v5 lite"}}
    for name in ("prompt_cached_share", "snapshot_copy_share", "ssm_state_roofline",
                 "expert_matmul_roofline", "state_slots_per_step", "resume_ttft_p50_ms"):
        assert bench.load("readers", "granite", name)(dict(bare), {}) is None, name


def test_the_state_share_pattern_names_the_states_ops_and_no_others():
    with open(spec.Benchmark(ROOT).path("layer_metrics", "ssm_state_share.sessions.json")) as f:
        pattern = re.compile(json.load(f)["kernels"])
    state = ["ssm_state_step.3 custom-call", "fusion.441 fusion f32[137,128,64,128]",
             "scatter.67 scatter f32[9,137,128,64,128]", "fusion.12 fusion bf16[137,3,8448]",
             "scatter.3 scatter bf16[9,137,3,8448]"]
    others = ["fusion.373 fusion bf16[512,16768]", "gmm_ragged_dot.41 custom-call bf16[1280,768]",
              "paged_decode_attention.3 custom-call bf16[512,32,128]",
              "fusion.1 fusion bf16[512,4096]", "fusion.5 fusion bf16[48,25088]",
              "scatter.1 scatter bf16[1,4609,64,1024]", "fusion.2 fusion bf16[512,8448]"]
    assert all(pattern.search(name) for name in state)
    assert not any(pattern.search(name) for name in others)
