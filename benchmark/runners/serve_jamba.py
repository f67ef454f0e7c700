"""Runner ``serve_jamba``: the ``serve`` runner for Jamba (``jamba2-3b``:
Mamba-1 mixers whose state - a decay of its own for every channel and
state column - and convolution tail are a slot, two position-free
attention layers of 20 query heads over one key-value head whose keys and
values are paged beside it, a dense SwiGLU in every layer).

The client, the two loops, the warm-up, the window's accounting and the
result table are ``runners/serve.py``'s, unedited; the judging of the
logits is ``runners/serve_moonlight.py``'s, unedited (seeded sequences, the
longest prefilled over two SplitFuse chunks, the short ones a dozen to a
chunk, then decode steps of all through the pools and the slots:
``summarize`` there says how; this model has no router, so every compared
position is held to the tolerance); the serving of those sequences is
``runners/serve_nemotron.py``'s, unedited (the engine is told each prompt
before its first chunk, and **two sequences take slots that others have
just released**): this file loads a private copy of each and gives them
what is this configuration's - the engine builder (the program's
``JambaConfig`` from the published keys, the Pallas paged kernel pinned),
the reference (``harness/reference_jamba.py``) and the served mixers alone.

The logits cannot see a fault of the size of bf16's own error confined to
one mixer, nor whether a state is carried in float32. So ``correct`` also
compares **each mixer alone**, at the published widths, on what the
reference's layers saw of the check's longest sequence:

- every Mamba layer (:func:`mamba_layer_readings`,
  :func:`summarize_mamba_layer`): the served mixer -
  ``JambaKind.mamba_layer``, the step programs' own convolution, scan and
  slot reads and writes, the engine's weights in place - its first rows in
  calls of the token budget as a prompt step has them, its last
  ``reference.mamba_layer.decode_rows`` rows one a call in the decode
  program's rows, in a slot that held another state: its output a row, and
  **the state and the tail it leaves**, against the reference's
  token-by-token recurrence;
- both attention layers (:func:`attention_layer_errors`): the served mixer
  - ``JambaKind.attention_layer``, the writes into fresh pools and the
  pinned paged kernel at a query group of 20 - in chunks of the token
  budget, its output a row against the reference's.

A closed loop has no arrival to count a first token from; what a client
waits between sending a request and its first token is in the line's
``facts.window`` (``ttft_p50_ms``, ``ttft_p90_ms``), under no bound.
"""

import functools
import importlib.util
import json
import os
import sys

import numpy as np

from benchmark.harness import reference_jamba
from benchmark.harness.device import log

PIN = "pallas_paged"
SCAN = "pallas_selective_scan"

# The cell's own per-layer metrics: a file each under ``layer_metrics/`` with the reader
# it names, and **no entry in BENCHMARK.json**, whose ``per_layer`` holds the 128 metrics it
# may hold. A traced run reads them here into ``facts.layer_metrics_chatloop``; the
# ``benchmark`` PR that makes room enters them, and this table goes.
CHATLOOP_METRICS = ("selective_scan_roofline.chatloop", "selective_scan_share.chatloop",
                    "state_slots_per_step.chatloop", "scan_runs_per_step.chatloop",
                    "tokens_per_step.chatloop", "mixed_step_ms_p50.chatloop",
                    "gap_engine_ms.chatloop", "device_idle.chatloop", "hbm_peak.chatloop")

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "hidden_act", "attn_layer_period",
    "attn_layer_offset", "expert_layer_period", "expert_layer_offset", "num_experts",
    "num_experts_per_tok", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
    "mamba_conv_bias", "mamba_proj_bias", "use_mamba_kernels", "num_logits_to_keep",
    "sliding_window", "rms_norm_eps", "tie_word_embeddings", "max_position_embeddings")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_jamba", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ``runners/serve_nemotron.py``'s serving of the check's sequences (two of them in slots that
# flushed sequences have just released) and three small helpers of its check, and
# ``runners/serve_lfm2.py``'s padding of a call's rows and errors a row, all unedited
_nemotron, _lfm2 = _private_copy("serve_nemotron"), _private_copy("serve_lfm2")
bf16_values, longest_sample, _rel = (_nemotron.bf16_values, _nemotron.longest_sample,
                                     _nemotron._rel)
_padded, _row_errors, attention_layer_errors = (_lfm2._padded, _lfm2._row_errors,
                                                _lfm2.attention_layer_errors)


@functools.lru_cache(maxsize=None)
def _check():
    """``runners/serve_moonlight.py``'s check (sample, errors by position,
    ``summarize``), reading this configuration's reference and serving
    through ``runners/serve_nemotron.py``'s ``served_logits``."""
    module = _private_copy("serve_moonlight")
    module.reference_moonlight = reference_jamba      # rows_at / head_at, the same signatures
    module.build_engine = build_engine
    module.served_logits = _nemotron.served_logits
    return module


def jamba_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``) → the program's ``JambaConfig``; a key the program
    does not support is refused there."""
    from deepspeed_tpu.models.jamba import JambaConfig
    return JambaConfig(**{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.jamba import build_jamba
    e = config["engine"]
    return InferenceEngineV2(
        model=build_jamba(jamba_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            implementation_overrides={} if rehearse else {"attention": PIN},
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


class Tapped:
    """``reference_jamba`` as the check reads it (``rows_at``, ``head_at``),
    keeping what the mixers saw of the **first** batch's longest sequence:
    ``mamba``: ``(x, y, state, tail)`` a Mamba layer, ``attn``: ``(x, y)`` an
    attention layer - the normalised input and the output a row, the state
    and the tail the sequence left - on the host."""
    head_at = staticmethod(reference_jamba.head_at)

    def __init__(self, longest):
        self.mamba, self.attn, self.longest, self.batches = [], [], longest, 0

    def rows_at(self, params, ids, positions, model):
        first = not self.batches
        self.batches += 1

        def keep(kind, layer, x, y, state, tail):
            if not first:
                return
            if kind == reference_jamba.MAMBA:
                self.mamba.append(tuple(np.asarray(t[self.longest]) for t in (x, y, state, tail)))
            else:
                self.attn.append(tuple(np.asarray(t[self.longest]) for t in (x, y)))

        return reference_jamba.rows_at(params, ids, positions, model, tap=keep)


def _calls(S, budget, decode_rows, decode_program):
    """The calls a sequence of ``S`` rows is served in: its first rows in
    chunks of ``budget`` as prompt steps have them, its last
    ``decode_rows`` one a call → [(first row, rows, the program's rows)]."""
    prompt = max(S - decode_rows, 0)
    cuts = list(range(0, prompt, budget)) + list(range(prompt, S))
    return [(r0, r1 - r0, budget if r1 - r0 > 1 else decode_program)
            for r0, r1 in zip(cuts, cuts[1:] + [S])]


def _programs(engine, name, make):
    """One jitted function an engine and a mixer, kept on the engine for
    every layer's calls: a layer is an argument, and 26 layers share two
    compilations (jitted anew a layer they were 19 minutes of tracing)."""
    kept = vars(engine).setdefault("_benchmark_programs", {})
    if name not in kept:
        kept[name] = make()
    return kept[name]


def served_mamba_layer(engine, config, layer, x, state_dtype=None):
    """x [S, D] (one sequence's normalised stream into Mamba layer
    ``layer``) → (y [S, D] float32, the state [N, I] and the tail [K - 1, I]
    its slot holds afterwards, the state step each program got):
    ``JambaKind.mamba_layer`` - the step programs' own function, the
    engine's weights in place - over a fresh slot pool whose slots are
    **not empty** (ones: position 0 has to ignore them), in :func:`_calls`'
    calls: the token budget's rows a prompt call, the decode program's
    (``max_ragged_sequence_count``) a single row, the rows past the
    sequence's padding's. Hundreds of single rows, because that is where a
    state held in too few bits shows: every step rounds all of it again,
    and an increment of a thousandth of a slow channel's state is under
    bfloat16's last place. ``state_dtype``: None, or a control's - the state
    rounded to it between calls, as a pool of that type would hold it."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import JambaKind
    cfg, e = engine.model_config, config["engine"]
    Lm = cfg.count("m")
    ssm = jnp.ones((Lm, 3, cfg.mamba_d_state, cfg.mamba_inner), jnp.float32)
    conv = jnp.ones((Lm, 3, cfg.mamba_d_conv - 1, cfg.mamba_inner), engine.dtype)
    tables = jnp.zeros((2, 1), jnp.int32)
    slots = jnp.asarray([[2], [0]], jnp.int32)

    def make():
        impls = {}

        def step(params, layer, x, ssm, conv, seq, pos):
            from deepspeed_tpu.ops.pallas import selective_scan
            batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables,
                     "seq_state": slots}
            impls[x.shape[0]] = selective_scan.scan_impl(ssm.shape, x.shape[0], tables.shape[0])
            return JambaKind.mamba_layer(params, cfg, layer, x, ssm, conv, batch)

        return jax.jit(step, donate_argnums=(3, 4)), impls

    step, impls = _programs(engine, "mamba", make)
    y = []
    for r0, n, rows in _calls(x.shape[0], e["token_budget"],
                              config["reference"]["mamba_layer"]["decode_rows"],
                              e["max_ragged_sequence_count"]):
        part, seq, pos = _padded(x, r0, n, rows)
        out, ssm, conv = step(engine.params, jnp.int32(layer), jnp.asarray(part, engine.dtype),
                              ssm, conv, seq, pos)
        if state_dtype is not None:
            # programs of their own: inside one, XLA drops a round trip through a narrower
            # type (it may keep excess precision), and the control would be the program
            ssm = jax.block_until_ready(ssm.astype(state_dtype)).astype(jnp.float32)
        y.append(out[:n])
    y = np.asarray(jnp.concatenate(y).astype(jnp.float32))
    return (y, np.asarray(ssm[layer, 2]), np.asarray(conv[layer, 2].astype(jnp.float32)),
            dict(impls))


def served_attention_layer(engine, config, layer, x):
    """x [S, D] (one sequence's normalised stream into attention layer
    ``layer``) → (y [S, D] float32, the attention implementation each
    program got): ``JambaKind.attention_layer`` - the step programs' own
    function, the engine's weights in place, the engine's pinned attention
    implementation - over fresh pools of the sequence's blocks,
    ``token_budget`` rows a call as a prompt step has them (the last call's
    rows past the sequence are padding's)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import JambaKind
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    cfg, budget = engine.model_config, config["engine"]["token_budget"]
    bs = config["engine"]["kv_block_size"]
    S = x.shape[0]
    blocks = -(-S // bs)
    shape = (cfg.count("a"), blocks + 1, bs, cfg.num_key_value_heads * cfg.head_dim)
    kc, vc = jnp.zeros(shape, engine.dtype), jnp.zeros(shape, engine.dtype)
    tables = jnp.asarray([list(range(1, blocks + 1)), [0] * blocks], jnp.int32)
    slots = jnp.zeros((2, 1), jnp.int32)

    def make():
        impl = AttentionChoice(engine._attention.override)

        def step(params, layer, x, kc, vc, tables, seq, pos):
            batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables,
                     "seq_state": slots}
            return JambaKind.attention_layer(params, cfg, layer, x, kc, vc, batch, impl)

        return jax.jit(step, donate_argnums=(3, 4)), impl

    step, impl = _programs(engine, "attention", make)
    y = []
    for r0 in range(0, S, budget):
        n = min(budget, S - r0)
        part, seq, pos = _padded(x, r0, n, budget)
        out, kc, vc = step(engine.params, jnp.int32(layer), jnp.asarray(part, engine.dtype), kc, vc,
                           tables, seq, pos)
        y.append(out[:n])
    return np.asarray(jnp.concatenate(y).astype(jnp.float32)), dict(impl.selected)


def mamba_layer_readings(taps, read):
    """``taps``: :class:`Tapped`'s of the check's longest sequence, one a
    Mamba layer; ``read(layer, x)`` → the served (y, state, tail) or a
    control's. → (errors [layers, S]: the relative L2 error of the mixer's
    output a row; states [layers], tails [layers]: the relative L2 error of
    the state and of the convolution's tail the sequence leaves)."""
    errors, states, tails = [], [], []
    for layer, (x, y, state, tail) in enumerate(taps):
        have, have_state, have_tail = read(layer, np.asarray(x))[:3]
        errors.append(_row_errors(have, y))
        states.append(_rel(have_state, state))
        tails.append(_rel(have_tail, tail))
    return np.asarray(errors), np.asarray(states), np.asarray(tails)


def summarize_mamba_layer(errors, states, tails, reference):
    """What is reported of the Mamba layers alone, and ``agrees``: every
    row's output by ``summarize`` with ``reference.mamba_layer``'s limits (a
    layer is what a sequence is to the logits), and every layer's state and
    tail under ``state_tolerance`` and ``tail_tolerance``."""
    limits = reference["mamba_layer"]
    out = _check().summarize(errors, np.ones(errors.shape), limits)
    out.update(state_max=float(states.max()), state_min=float(states.min()),
               tail_max=float(tails.max()), rows=int(errors.shape[1]),
               by_layer_max=[float(e) for e in errors.max(axis=1)])
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all()
                         and np.isfinite(states).all() and np.isfinite(tails).all()
                         and states.max() <= limits["state_tolerance"]
                         and tails.max() <= limits["tail_tolerance"])
    return out


def summarize_attention_layer(errors, reference):
    """``summarize`` over every (layer, row) with
    ``reference.attention_layer``'s limits."""
    out = _check().summarize(errors, np.ones(errors.shape), reference["attention_layer"])
    out.update(rows=int(errors.shape[1]), by_layer_max=[float(e) for e in errors.max(axis=1)])
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all())
    return out


def reference_check(engine, config, seed):
    """The logits against the reference, then each mixer alone on what the
    reference's layers saw → (what all three read, whether all agree)."""
    check = _check()
    check.reference_moonlight = tapped = Tapped(longest_sample(config["reference"]))
    try:
        errs, agrees = check.reference_check(engine, config, seed)
    finally:
        check.reference_moonlight = reference_jamba
    reference = config["reference"]
    # the served stream is bf16: a mixer reads the reference's input at bf16's values
    scans = {}

    def mamba(layer, x):
        y, state, tail, impls = served_mamba_layer(engine, config, layer, x)
        scans.update(impls)
        return y, state, tail

    errors, states, tails = mamba_layer_readings(
        [(bf16_values(x), y, state, tail) for x, y, state, tail in tapped.mamba], mamba)
    errs["mamba_layer"] = dict(summarize_mamba_layer(errors, states, tails, reference),
                               impls={str(k): v for k, v in scans.items()})
    impls = {}

    def attention(layer, x):
        y, selected = served_attention_layer(engine, config, layer, x)
        impls.update(selected)
        return y

    errors = attention_layer_errors([(bf16_values(x), y) for x, y in tapped.attn], attention)
    errs["attention_layer"] = dict(summarize_attention_layer(errors, reference),
                                   impls={str(k): v for k, v in impls.items()})
    return errs, bool(agrees and errs["mamba_layer"]["agrees"]
                      and errs["attention_layer"]["agrees"])


def state_facts(engine, config):
    """What the pools and the slots hold, as the engine states it, for the
    readers of the step records' counts."""
    cfg = engine.model_config
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "state_extra_bytes": {name: int(x.nbytes)
                                  for name, x in sorted(engine.state_extra.items())},
            "slot_bytes": engine.slot_pool.bytes_per_slot,
            "jamba_shapes": {"mamba_layers": cfg.count("m"), "attn_layers": cfg.count("a"),
                             "channels": cfg.mamba_inner, "state_columns": cfg.mamba_d_state,
                             "state_itemsize": 4, "slots": engine.slot_pool.slots}}


def window_facts(client):
    """What a closed loop's clients waited for a first token: over the
    requests sent inside the window whose first token came, ms between
    sending and it."""
    from benchmark.harness.stats import percentile
    ttft = [(f.first - f.sent) * 1e3 for f in client.done + client.live
            if f.first is not None and client.in_window(f.sent)]
    return {"ttft_p50_ms": percentile(ttft, 50), "ttft_p90_ms": percentile(ttft, 90),
            "first_tokens": len(ttft)}


def chatloop_metrics(bench, run):
    """:data:`CHATLOOP_METRICS` read of a traced run as ``run.py`` reads an
    entered metric: the metric's own file, its reader given the run and the
    file. → {name: {"value", "unit"}}, a metric whose reader finds nothing
    left out."""
    out = {}
    for name in CHATLOOP_METRICS:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, _, attr = spec["reader"].partition(":")
        value = bench.load("readers", module.partition(".")[2], attr)(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def run(ctx):
    try:
        import deepspeed_tpu.models.jamba  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_jamba: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    serve = _private_copy("serve")
    verdict = {}

    def checked(engine, config, seed):
        errs, verdict["agrees"] = reference_check(engine, config, seed)
        verdict["state"] = state_facts(engine, config)
        verdict["engine"] = engine
        return errs, verdict["agrees"]

    counted = serve.window_tokens

    def windowed(client):
        verdict["window"] = window_facts(client)
        return counted(client)

    serve.build_engine, serve.reference_check = build_engine, checked
    serve.window_tokens = windowed
    result = serve.run(ctx)
    facts = result["facts"]
    impls = facts["attention_impls"]
    scans = {str(k): v for k, v in verdict.pop("engine").state_step_impls.items()}
    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN}
                              and bool(scans) and set(scans.values()) == {SCAN})
    result["correct"] = bool(verdict["agrees"] and pinned and result["failed"] == 0
                             and facts["compiled_after_warm_up"] == 0
                             and result["attempted"] > 0)
    facts.update(verdict["state"])
    facts["state_step_impls"] = scans
    facts["window"] = verdict["window"]
    # TPOT is no metric of this cell, and ~1,300 requests end in a window: the line stays short
    facts["tpot_by_request"] = []
    if result.get("trace") is not None:
        facts["layer_metrics_chatloop"] = chatloop_metrics(ctx.bench, result)
        facts["trace_facts"] = ctx.bench.load("readers", "jamba", "trace_facts")(result)
    log(f"[serve_jamba] programs {impls}; state step {scans}; state {verdict['state']}; "
        f"correct {result['correct']}")
    return result
