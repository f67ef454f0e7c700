"""Runner ``serve_ouro``: the ``serve`` runner for Ouro (``ouro-2.6b``: one
stack of 48 layers run four times with the same weights, keys and values
192 layers deep under 48 layers of parameters, sandwich norms, the model's
norm after every pass, the exit gate; 16 query heads over 16 key-value
heads, so the paged kernel runs at a query group of one).

The client, the closed loop, the window's accounting and the result table
are ``runners/serve.py``'s, unedited: this file loads a private copy and
gives it what is this configuration's - the engine builder (the program's
``OuroConfig`` from the published keys, the Pallas paged kernel pinned), a
warm-up inside this cell's context of 512, and the judging below.

``correct`` compares what the timed path produced at the timed sizes with
the float32 reference (``harness/reference_ouro.py``) on the same weights,
over **one seeded sequence served as the cell serves**
(:func:`served_sequence`): its prompt in two chunks through the 512-row
program, ``reference.decode_rows`` single rows through the 16-row program
and the pools, one burst of ``reference.burst`` steps whose own tokens
extend the sequence, and one more row through what the burst wrote.

- the logits at every one of those positions, by relative L2 error
  (``reference.logits.tolerance``); the burst's tokens by their **regret**
  in the reference's logits - ``(max - logit of the token taken) / norm`` of
  the row they were drawn from (``reference.burst_regret.tolerance``): with
  random weights the largest logit changes on rounding, the regret does not;
- **every pass's** ``x_u`` at every row and the gate ``g_u``
  (:func:`served_passes`): ``OuroKind.run_pass``, the step programs' own
  pass with the engine's weights in place, over fresh pools in the same
  calls - rows of the token budget for the chunks, of the decode program
  for single rows - **each pass on the stream the reference has entering
  it**, against the reference's ``passes`` and ``gates``
  (``reference.passes.tolerance``, relative L2 a row and a pass;
  ``reference.gate.tolerance``, absolute). The seeded weights amplify a
  difference ~2.6 times a pass, so four passes in a row read what the
  logits' limit allows and one pass on the reference's input reads what one
  pass may lose: the tight comparison.

A closed loop has no arrival to count a first token from; what a client
waits between sending a request and its first token is in the line's
``facts.window`` (``ttft_p50_ms``, ``ttft_p90_ms``), under no bound.
"""

import importlib.util
import json
import os
import sys

import numpy as np

from benchmark.harness import reference_ouro
from benchmark.harness.device import log

PIN = "pallas_paged"

# The cell's own per-layer metrics: a file each under ``layer_metrics/`` with the reader
# it names, and **no entry in BENCHMARK.json**, whose ``per_layer`` holds the 128 metrics it
# may hold. A traced run reads them here into ``facts.layer_metrics_mathqa``; the
# ``benchmark`` PR that makes room enters them, and this table goes.
MATHQA_METRICS = ("decode_hbm_roofline.mathqa", "paged_attn_roofline.mathqa",
                  "paged_attn_share.mathqa", "weight_copy_share.mathqa",
                  "loop_passes_per_step.mathqa", "decode_step_ms_p50.mathqa",
                  "mixed_step_ms_p50.mathqa", "tokens_per_step.mathqa",
                  "ctx_tokens_per_step.mathqa", "device_idle.mathqa", "hbm_peak.mathqa",
                  "gap_engine_ms.mathqa", "gate_queued.mathqa")

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "hidden_act", "layer_types", "total_ut_steps",
    "early_exit_threshold", "max_position_embeddings", "max_window_layers", "rms_norm_eps",
    "rope_theta", "rope_scaling", "sliding_window", "use_sliding_window", "tie_word_embeddings")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_ouro", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ouro_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``) → the program's ``OuroConfig``; a key the program does
    not support is refused there."""
    from deepspeed_tpu.models.ouro import OuroConfig
    return OuroConfig(**{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.ouro import build_ouro
    e = config["engine"]
    return InferenceEngineV2(
        model=build_ouro(ouro_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            implementation_overrides={} if rehearse else {"attention": PIN},
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


# ------------------------------------------------------------------ the check
def check_tokens(config, seed):
    """The seeded tokens of the check: the prompt, the single decode rows and
    the token the burst starts from."""
    ref = config["reference"]
    rng = np.random.default_rng(seed)
    return rng.integers(0, config["model"]["vocab_size"],
                        ref["prompt_tokens"] + ref["decode_rows"] + 1, dtype=np.int32)


def compared_positions(ref):
    """The positions whose logits the served path gives: the two chunks'
    last rows, every single decode row, the row behind the burst."""
    prompt, rows = ref["prompt_tokens"], ref["decode_rows"]
    return ([ref["prompt_cut"] - 1, prompt - 1] + list(range(prompt, prompt + rows))
            + [prompt + rows + ref["burst"]])


def served_sequence(engine, config, ids, uid=-1):
    """``ids`` (:func:`check_tokens`) served as the cell serves →
    ({position: its logits row}, the whole sequence - ``ids`` and the
    burst's own tokens -, the burst's tokens)."""
    ref = config["reference"]
    prompt, cut, k = ref["prompt_tokens"], ref["prompt_cut"], ref["burst"]
    rows = {cut - 1: engine.put([uid], [ids[:cut]])[0],
            prompt - 1: engine.put([uid], [ids[cut:prompt]])[0]}
    for p in range(prompt, len(ids) - 1):
        rows[p] = engine.put([uid], [ids[p:p + 1]])[0]
    burst = np.asarray(engine.decode_burst([uid], [int(ids[-1])], k))[:, 0].astype(np.int32)
    full = np.concatenate([ids, burst])
    rows[len(full) - 1] = engine.put([uid], [full[-1:]])[0]
    engine.flush(uid)
    return {p: np.asarray(r, np.float32) for p, r in rows.items()}, full, burst


def _programs(engine, name, make):
    """One jitted function an engine, kept on it for every call."""
    kept = vars(engine).setdefault("_benchmark_programs", {})
    if name not in kept:
        kept[name] = make()
    return kept[name]


def served_passes(engine, config, full, enter):
    """``full`` [S] (one sequence), ``enter`` [R, S, D]: the stream the
    reference has **leaving** each pass → (x [R, S, D] float32: what the
    served pass ``u`` makes of the stream the reference has entering it - the
    embedded rows for pass 0, ``enter[u - 1]`` at bfloat16's values after -,
    g [R, S]: its gate, the attention implementation each program got).

    ``OuroKind.run_pass`` - the step programs' own pass, the engine's weights
    in place, the engine's pinned attention implementation - over fresh pools
    of the sequence's blocks, in the served sequence's calls: the two chunks
    in rows of the token budget, then a row a call in the decode program's
    rows (the rows past a call's are padding's), every pass on pool layers
    ``u L ..`` as the step programs have it. **A pass at a time on the
    reference's stream**, because the seeded weights amplify a difference
    ~2.6 times a pass (one pass of bfloat16 reads 0.03, four in a row 0.5:
    the configuration's ``reference.why``): held to the reference's input
    every pass is held to what one pass may lose; the four in a row are what
    the logits read."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import OuroKind
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    cfg, e, ref = engine.model_config, config["engine"], config["reference"]
    bs, S, R = e["kv_block_size"], len(full), enter.shape[0]
    blocks = -(-S // bs)
    shape = (OuroKind.state_layers(cfg), blocks + 1, bs, OuroKind.state_rows(cfg)[0])
    kc, vc = jnp.zeros(shape, engine.dtype), jnp.zeros(shape, engine.dtype)
    tables = jnp.asarray([list(range(1, blocks + 1)), [0] * blocks], jnp.int32)

    def make():
        impl = AttentionChoice(engine._attention.override)

        def step(params, u, ids, h, kc, vc, tables, seq, pos):
            batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables}
            embedded = params["model"]["embed_tokens"][ids].astype(engine.dtype)
            x, g, kc, vc = OuroKind.run_pass(params, cfg, u, jnp.where(u == 0, embedded, h), kc,
                                             vc, batch, impl)
            return x.astype(jnp.float32), g, kc, vc

        return jax.jit(step, donate_argnums=(4, 5)), impl

    step, impl = _programs(engine, "run_pass", make)
    prompt, cut = ref["prompt_tokens"], ref["prompt_cut"]
    calls = [(0, cut, e["token_budget"]), (cut, prompt - cut, e["token_budget"])] \
        + [(p, 1, e["max_ragged_sequence_count"]) for p in range(prompt, S)]
    xs, gs = [], []
    for first, n, rows in calls:
        ids, seq, pos = np.zeros(rows, np.int32), np.ones(rows, np.int32), np.zeros(rows, np.int32)
        ids[:n], seq[:n], pos[:n] = full[first:first + n], 0, np.arange(first, first + n)
        x_call, g_call = [], []
        for u in range(R):
            h = np.zeros((rows, enter.shape[-1]), np.float32)
            if u:
                h[:n] = enter[u - 1, first:first + n]
            x, g, kc, vc = step(engine.params, jnp.int32(u), jnp.asarray(ids),
                                jnp.asarray(h, engine.dtype), kc, vc, tables, jnp.asarray(seq),
                                jnp.asarray(pos))
            x_call.append(np.asarray(x[:n]))
            g_call.append(np.asarray(g[:n]))
        xs.append(np.stack(x_call))
        gs.append(np.stack(g_call))
    return np.concatenate(xs, axis=1), np.concatenate(gs, axis=1), dict(impl.selected)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(float(np.linalg.norm(want)), 1e-30))


def readings(want, ref, rows, x, g, burst=None):
    """What ``correct`` reads: ``want`` the reference's forward of the whole
    sequence (``reference_ouro.forward``, a batch of one); ``rows``
    {position: logits row}; ``x`` [R', S, D] / ``g`` [R', S] the passes'
    streams and gates (a control's may have fewer passes: the passes it has
    are compared); ``burst``: the served burst's tokens, or None."""
    logits, passes, gates = (np.asarray(want[k]) for k in ("logits", "passes", "gates"))
    by_position = {int(p): _rel(row, logits[0, p]) for p, row in sorted(rows.items())}
    n = min(x.shape[0], passes.shape[0])
    by_pass = [max(_rel(x[u, s], passes[u, 0, s]) for s in range(x.shape[1])) for u in range(n)]
    out = {"logits": {"max": max(by_position.values()), "min": min(by_position.values()),
                      "first_chunk": by_position[ref["prompt_cut"] - 1],
                      "by_position": {str(p): round(e, 5) for p, e in by_position.items()}},
           "passes": {"max": max(by_pass), "by_pass": [round(e, 5) for e in by_pass]},
           "gate": {"max": float(np.abs(g[:n] - gates[:n, 0]).max())}}
    if burst is not None:
        first = ref["prompt_tokens"] + ref["decode_rows"]
        regret = [float((logits[0, first + j].max() - logits[0, first + j, int(t)])
                        / np.linalg.norm(logits[0, first + j])) for j, t in enumerate(burst)]
        out["burst_regret"] = {"max": max(regret), "by_step": [round(r, 5) for r in regret]}
    return out


def summarize(read, ref):
    """→ ``read`` with ``agrees``: every reading finite and under its limit."""
    parts = {"logits": read["logits"]["max"], "passes": read["passes"]["max"],
             "gate": read["gate"]["max"]}
    if "burst_regret" in read:
        parts["burst_regret"] = read["burst_regret"]["max"]
    failed = [name for name, value in parts.items()
              if not np.isfinite(value) or value > ref[name]["tolerance"]]
    return dict(read, failed=failed, agrees=not failed)


def reference_check(engine, config, seed):
    """→ (what the check read, whether the program agrees with the reference)."""
    import jax.numpy as jnp
    ref = config["reference"]
    rows, full, burst = served_sequence(engine, config, check_tokens(config, seed))
    want = reference_ouro.forward(engine.params, jnp.asarray(full)[None], config["model"])
    x, g, impls = served_passes(engine, config, full, np.asarray(want["passes"])[:, 0])
    read = summarize(readings(want, ref, rows, x, g, burst), ref)
    read["passes"]["impls"] = {str(k): v for k, v in impls.items()}
    read["exit_steps"] = sorted(set(np.asarray(want["exit_step"]).ravel().tolist()))
    return read, read["agrees"]


# -------------------------------------------------------------------- the run
def warm_up(gateway, config):
    """Two requests alone that walk through every program the cell can run,
    inside its context: a prompt that takes the budget-sized program and an
    answer whose remaining length steps through every power-of-two burst (47
    = 16+16+8+4+2+1), then a prompt short enough for the decode-sized one."""
    e = config["engine"]
    vocab, rows = config["model"]["vocab_size"], e["max_ragged_sequence_count"]
    long = np.arange(e["max_context"] - 48 - rows, dtype=np.int32) % vocab
    for prompt, new in ((long, 48), (long[:max(1, rows - 4)], 3)):
        tokens = gateway.submit(prompt, max_new_tokens=new).result(timeout=900)
        if len(tokens) != new:
            raise RuntimeError(f"warm-up request returned {len(tokens)} tokens of {new}")


def state_facts(engine):
    """What the pools hold and what a step streams, as the engine states it,
    for the readers of the step records."""
    import jax
    cfg = engine.model_config
    layers = sum(int(w.nbytes) for w in jax.tree.leaves(engine.params["model"]["layers"]))
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "ouro_shapes": {"state_layers": int(engine.kv_cache.num_layers),
                            "param_layers": int(cfg.num_hidden_layers),
                            "passes": int(cfg.total_ut_steps),
                            "stack_bytes": layers,
                            "head_bytes": int(engine.params["lm_head"]["kernel"].nbytes),
                            "kv_row_bytes": int(engine.state_bytes_per_token
                                                // engine.kv_cache.num_layers),
                            "query_group": int(cfg.num_attention_heads
                                               // cfg.num_key_value_heads),
                            "pool_bytes": int(engine.kv_cache.bytes()),
                            "param_bytes": sum(int(w.nbytes)
                                               for w in jax.tree.leaves(engine.params))}}


def window_facts(client):
    """What a closed loop's clients waited for a first token: over the
    requests sent inside the window whose first token came, ms between
    sending and it."""
    from benchmark.harness.stats import percentile
    ttft = [(f.first - f.sent) * 1e3 for f in client.done + client.live
            if f.first is not None and client.in_window(f.sent)]
    return {"ttft_p50_ms": percentile(ttft, 50), "ttft_p90_ms": percentile(ttft, 90),
            "first_tokens": len(ttft)}


def mathqa_metrics(bench, run):
    """:data:`MATHQA_METRICS` read of a traced run as ``run.py`` reads an
    entered metric: the metric's own file, its reader given the run and the
    file. → {name: {"value", "unit"}}, a metric whose reader finds nothing
    left out."""
    out = {}
    for name in MATHQA_METRICS:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, _, attr = spec["reader"].partition(":")
        value = bench.load("readers", module.partition(".")[2], attr)(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def run(ctx):
    try:
        import deepspeed_tpu.models.ouro  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_ouro: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    serve = _private_copy("serve")
    verdict = {}

    def checked(engine, config, seed):
        errs, verdict["agrees"] = reference_check(engine, config, seed)
        verdict["state"] = state_facts(engine)
        return errs, verdict["agrees"]

    counted = serve.window_tokens

    def windowed(client):
        verdict["window"] = window_facts(client)
        return counted(client)

    serve.build_engine, serve.reference_check, serve.warm_up = build_engine, checked, warm_up
    serve.window_tokens = windowed
    result = serve.run(ctx)
    facts = result["facts"]
    impls = facts["attention_impls"]
    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN})
    result["correct"] = bool(verdict["agrees"] and pinned and result["failed"] == 0
                             and facts["compiled_after_warm_up"] == 0
                             and result["attempted"] > 0)
    facts.update(verdict["state"])
    facts["window"] = verdict["window"]
    facts["tpot_by_request"] = []       # TPOT is no metric of this cell: the line stays short
    if result.get("trace") is not None:
        facts["layer_metrics_mathqa"] = mathqa_metrics(ctx.bench, result)
    log(f"[serve_ouro] programs {impls}; state {verdict['state']}; correct {result['correct']}")
    return result
