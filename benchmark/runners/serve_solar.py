"""Runner ``serve_solar``: the ``serve`` runner for Solar Open 2
(``solar-open2-ep8-4l``: Kimi-delta-attention layers whose state - a 128 x
128 float32 matrix a head, rotated as well as decayed by every token - and
three convolution tails are a slot, a gated position-free attention of 64
query heads over 8 key-value heads whose keys and values are paged beside
it, and in every layer a routed feed-forward that is one chip's share of an
8-way expert-parallel deployment).

The client, the two loops, the warm-up, the window's accounting and the
result table are ``runners/serve.py``'s, unedited; the judging of the logits
is ``runners/serve_moonlight.py``'s, unedited (seeded sequences, the longest
prefilled over three SplitFuse chunks, the short ones sharing chunks, then
decode steps of all through the pools and the slots, every compared
position judged by the reference's margin: ``summarize`` there says how);
the serving of those sequences is ``runners/serve_nemotron.py``'s, unedited
(the engine is told each prompt before its first chunk, and **two sequences
take slots that others have just released**); the comparison of the expert
layer alone is ``runners/serve_longcat.py``'s and the padding of a call's
rows and the errors a row ``runners/serve_lfm2.py``'s, both unedited: this
file loads a private copy of each and gives them what is this
configuration's - the engine builder (the program's ``SolarOpen2Config`` from
the published keys and the share, the Pallas paged kernel pinned), the
reference (``harness/reference_solar.py``, given the same share) and the
served layers alone.

The logits alone cannot hold the new mechanisms: a state that drifts by a
part in a thousand a step, a ``beta`` held to (0, 1), a gate left out of one
layer in four or a dropped held pick (one of a token's eight, in the mean)
each move a logit row by about what bf16 rounding over four layers does. So
``correct`` also compares **the layers alone**, at the published widths, on
what the reference's layers saw:

- every KDA layer (:func:`kda_layer_readings`, :func:`summarize_kda_layer`):
  the served mixer - ``SolarOpen2Kind.kda_layer``, the step programs' own
  convolutions, delta rule and slot reads and writes, the engine's weights in
  place - over the check's longest sequence, its first rows in calls of the
  token budget as a prompt step has them, its last
  ``reference.kda_layer.decode_rows`` rows one a call in the decode program's
  rows, in a slot that held another state: its output a row, and **the
  state and the tails it leaves**, against the reference's token-by-token
  recurrence;
- the attention layer (``serve_lfm2.attention_layer_errors``): the served
  mixer - ``SolarOpen2Kind.attention_layer``, the writes into fresh pools,
  the pinned paged kernel at a query group of 8 and the gate - in chunks of
  the token budget, its output a row against the reference's;
- every routed feed-forward (``serve_longcat.expert_layer_errors``): the
  served layer on its rows against the reference's.

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

from benchmark.harness import reference_solar
from benchmark.harness.device import log

PIN = "pallas_paged"
RULE = "pallas_kda"

# The cell's own per-layer metrics: a file each under ``layer_metrics/`` with the reader
# it names, and **no entry in BENCHMARK.json**, whose ``per_layer`` holds the 128 metrics it
# may hold. A traced run reads them here into ``facts.layer_metrics_reason``; the
# ``benchmark`` PR that makes room enters them, and this table goes.
REASON_METRICS = ("kda_state_roofline.reason", "kda_share.reason", "state_slots_per_step.reason",
                  "scan_runs_per_step.reason", "held_rows_per_expert.reason",
                  "held_groups_empty.reason", "expert_matmul_share.reason",
                  "paged_attn_share.reason", "tokens_per_step.reason",
                  "mixed_step_ms_p50.reason", "decode_step_ms_p50.reason",
                  "gap_engine_ms.reason", "device_idle.reason", "hbm_peak.reason",
                  "gate_queued.reason")

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "linear_attn_config",
    "gqa_interval", "gqa_layers", "use_rope", "use_gqa_gate", "kda_use_full_proj",
    "kda_allow_neg_eigval", "rope_theta", "partial_rotary_factor", "first_k_dense_replace",
    "n_shared_experts", "num_experts_per_tok", "moe_intermediate_size", "norm_topk_prob",
    "routed_scaling_factor", "rms_norm_eps", "tie_word_embeddings", "max_position_embeddings")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_solar", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ``runners/serve_nemotron.py``'s serving of the check's sequences (two of them in slots that
# flushed sequences have just released) and three small helpers of its check,
# ``runners/serve_lfm2.py``'s padding of a call's rows and errors a row, all unedited,
_nemotron, _lfm2 = _private_copy("serve_nemotron"), _private_copy("serve_lfm2")
bf16_values, longest_sample, _rel = (_nemotron.bf16_values, _nemotron.longest_sample,
                                     _nemotron._rel)
_padded, _row_errors, attention_layer_errors = (_lfm2._padded, _lfm2._row_errors,
                                                _lfm2.attention_layer_errors)
# and ``runners/serve_jamba.py``'s cut of a sequence into a check's calls, its one jitted
# program a layer kind kept on the engine, and a closed loop's time to first token
_jamba = _private_copy("serve_jamba")
_calls, _programs, window_facts = _jamba._calls, _jamba._programs, _jamba.window_facts


@functools.lru_cache(maxsize=None)
def _check():
    """``runners/serve_moonlight.py``'s check (sample, errors by position,
    ``summarize``), reading this configuration's reference and serving
    through ``runners/serve_nemotron.py``'s ``served_logits``."""
    module = _private_copy("serve_moonlight")
    module.reference_moonlight = reference_solar      # rows_at / head_at, the same signatures
    module.build_engine = build_engine
    module.served_logits = _nemotron.served_logits
    return module


@functools.lru_cache(maxsize=None)
def _expert_check():
    """``runners/serve_longcat.py``'s comparison of an expert layer alone
    (``expert_layer_errors``, ``summarize_expert_layer``), reading this
    configuration's reference (``experts_at``, the same signature)."""
    module = _private_copy("serve_longcat")
    module.reference_longcat = reference_solar
    module._check = _check
    return module


def solar_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``, ``published`` and ``share``) → the program's
    ``SolarOpen2Config``: the router keeps the published number of columns,
    of which the file's ``n_routed_experts`` are held; a key the program
    does not support is refused there."""
    from deepspeed_tpu.models.solar_open2 import SolarOpen2Config
    return SolarOpen2Config(
        n_routed_experts=model["published"]["n_routed_experts"],
        experts_held=model["n_routed_experts"],
        first_expert_held=model["share"]["first_expert_held"],
        **{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.solar_open2 import build_solar_open2
    e = config["engine"]
    return InferenceEngineV2(
        model=build_solar_open2(solar_config(config["model"])),
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
    """``reference_solar`` as the check reads it (``rows_at``, ``head_at``),
    keeping what the layers saw: ``inputs``, every routed feed-forward's
    input at the compared positions, [L, b, n, D] a batch of the reference;
    and, of the **first** batch's longest sequence, ``kda``: ``(x, y, state,
    tail)`` a KDA layer and ``attn``: ``(x, y)`` an attention layer - the
    normalised input and the output a row, the state and the tail the
    sequence left - on the host."""
    head_at = staticmethod(reference_solar.head_at)

    def __init__(self, longest):
        self.inputs, self.kda, self.attn, self.longest = [], [], [], longest

    def rows_at(self, params, ids, positions, model):
        first = not self.inputs

        def keep(kind, layer, x, y, state, tail):
            if not first:
                return
            if kind == reference_solar.KDA:
                self.kda.append(tuple(np.asarray(t[self.longest]) for t in (x, y, state, tail)))
            else:
                self.attn.append(tuple(np.asarray(t[self.longest]) for t in (x, y)))

        rows, margins, inputs = reference_solar.layers_at(params, ids, positions, model,
                                                          tap=keep)
        self.inputs.append(inputs)
        return rows, margins


def served_kda_layer(engine, config, layer, x, state_dtype=None):
    """x [S, D] (one sequence's normalised stream into KDA layer ``layer``) →
    (y [S, D] float32, the state [H, d, d] and the tail [K - 1, 3 I] its
    slot holds afterwards, the state step each program got):
    ``SolarOpen2Kind.kda_layer`` - the step programs' own function, the
    engine's weights in place - over a fresh slot pool whose slots are **not
    empty** (ones: position 0 has to ignore them), in :func:`_calls`' calls:
    the token budget's rows a prompt call, the decode program's
    (``max_ragged_sequence_count``) a single row, the rows past the
    sequence's padding's. Hundreds of single rows, because that is where a
    state held in too few bits shows: every step rounds all of it again.
    ``state_dtype``: None, or a control's - the state rounded to it between
    calls, as a pool of that type would hold it."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import SolarOpen2Kind
    cfg, e = engine.model_config, config["engine"]
    Lk, H, d = cfg.count("k"), cfg.kda_heads, cfg.kda_head_dim
    kda = jnp.ones((Lk, 3, H, d, d), jnp.float32)
    conv = jnp.ones((Lk, 3, cfg.kda_conv - 1, 3 * cfg.kda_inner), engine.dtype)
    tables = jnp.zeros((2, 1), jnp.int32)
    slots = jnp.asarray([[2], [0]], jnp.int32)

    def make():
        impls = {}

        def step(params, layer, x, kda, conv, seq, pos):
            from deepspeed_tpu.ops.pallas import kda as rule
            batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables,
                     "seq_state": slots}
            impls[x.shape[0]] = rule.delta_rule_impl(kda.shape, x.shape[0], tables.shape[0])
            return SolarOpen2Kind.kda_layer(params, cfg, layer, x, kda, conv, batch)

        return jax.jit(step, donate_argnums=(3, 4)), impls

    step, impls = _programs(engine, "kda", make)
    y = []
    for r0, n, rows in _calls(x.shape[0], e["token_budget"],
                              config["reference"]["kda_layer"]["decode_rows"],
                              e["max_ragged_sequence_count"]):
        part, seq, pos = _padded(x, r0, n, rows)
        out, kda, conv = step(engine.params, jnp.int32(layer), jnp.asarray(part, engine.dtype),
                              kda, conv, seq, pos)
        if state_dtype is not None:
            # programs of their own: inside one, XLA drops a round trip through a narrower
            # type (it may keep excess precision), and the control would be the program
            kda = jax.block_until_ready(kda.astype(state_dtype)).astype(jnp.float32)
        y.append(out[:n])
    y = np.asarray(jnp.concatenate(y).astype(jnp.float32))
    return (y, np.asarray(kda[layer, 2]), np.asarray(conv[layer, 2].astype(jnp.float32)),
            dict(impls))


def served_attention_layer(engine, config, layer, x):
    """x [S, D] (one sequence's normalised stream into attention layer
    ``layer``) → (y [S, D] float32, the attention implementation each
    program got): ``SolarOpen2Kind.attention_layer`` - the step programs' own
    function, the engine's weights in place, the engine's pinned attention
    implementation, the gate - over fresh pools of the sequence's blocks,
    ``token_budget`` rows a call as a prompt step has them (the last call's
    rows past the sequence are padding's)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import SolarOpen2Kind
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    cfg, budget = engine.model_config, config["engine"]["token_budget"]
    bs = config["engine"]["kv_block_size"]
    S = x.shape[0]
    blocks = -(-S // bs)
    shape = (cfg.count("g"), blocks + 1, bs, cfg.num_key_value_heads * cfg.head_dim)
    kc, vc = jnp.zeros(shape, engine.dtype), jnp.zeros(shape, engine.dtype)
    tables = jnp.asarray([list(range(1, blocks + 1)), [0] * blocks], jnp.int32)
    slots = jnp.zeros((2, 1), jnp.int32)

    def make():
        impl = AttentionChoice(engine._attention.override)

        def step(params, layer, x, kc, vc, tables, seq, pos):
            batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables,
                     "seq_state": slots}
            return SolarOpen2Kind.attention_layer(params, cfg, layer, x, kc, vc, batch, impl)

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


def served_expert_layers(engine, config, x):
    """x [L, N, D] → the served routed feed-forward of each layer on its
    rows, float32: ``SolarOpen2Kind.expert_layer`` (the step programs' own
    function, the engine's weights in place), ``token_budget`` rows a call
    as a prompt step has them (the last call's rows padded with zeros, which
    are tokens like the others here)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import SolarOpen2Kind
    cfg, rows = engine.model_config, config["engine"]["token_budget"]
    layer = _programs(engine, "experts", lambda: jax.jit(
        lambda params, l, x: SolarOpen2Kind.expert_layer(params, cfg, l, x)))
    out = np.zeros(x.shape, np.float32)
    for l in range(x.shape[0]):
        for start in range(0, x.shape[1], rows):
            part = np.zeros((rows, x.shape[2]), np.float32)
            n = min(rows, x.shape[1] - start)
            part[:n] = x[l, start:start + n]
            got = layer(engine.params, jnp.int32(l), jnp.asarray(part, engine.dtype))
            out[l, start:start + n] = np.asarray(got.astype(jnp.float32))[:n]
    return out


def kda_layer_readings(taps, read):
    """``taps``: :class:`Tapped`'s of the check's longest sequence, one a KDA
    layer; ``read(layer, x)`` → the served (y, state, tail) or a control's. →
    (errors [layers, S]: the relative L2 error of the mixer's output a row;
    states [layers], tails [layers]: the relative L2 error of the state and
    of the convolutions' tail the sequence leaves)."""
    errors, states, tails = [], [], []
    for layer, (x, y, state, tail) in enumerate(taps):
        have, have_state, have_tail = read(layer, np.asarray(x))[:3]
        errors.append(_row_errors(np.asarray(have), y))
        states.append(_rel(have_state, state))
        tails.append(_rel(have_tail, tail))
    return np.asarray(errors), np.asarray(states), np.asarray(tails)


def summarize_kda_layer(errors, states, tails, reference):
    """What is reported of the KDA layers alone, and ``agrees``: every row's
    output by ``summarize`` with ``reference.kda_layer``'s limits (a layer is
    what a sequence is to the logits; no margin: nothing here is a step
    function), and every layer's state and tail under ``state_tolerance`` and
    ``tail_tolerance``."""
    limits = reference["kda_layer"]
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
    """The logits against the reference, then each layer kind alone on what
    the reference's layers saw → (what all four read, whether all agree)."""
    check, experts = _check(), _expert_check()
    check.reference_moonlight = tapped = Tapped(longest_sample(config["reference"]))
    try:
        errs, agrees = check.reference_check(engine, config, seed)
    finally:
        check.reference_moonlight = reference_solar
    reference = config["reference"]
    # the served stream is bf16: a mixer reads the reference's input at bf16's values
    rules = {}

    def kda(layer, x):
        y, state, tail, impls = served_kda_layer(engine, config, layer, x)
        rules.update(impls)
        return y, state, tail

    errors, states, tails = kda_layer_readings(
        [(bf16_values(x), y, state, tail) for x, y, state, tail in tapped.kda], kda)
    errs["kda_layer"] = dict(summarize_kda_layer(errors, states, tails, reference),
                             impls={str(k): v for k, v in rules.items()})
    impls = {}

    def attention(layer, x):
        y, selected = served_attention_layer(engine, config, layer, x)
        impls.update(selected)
        return y

    errors = attention_layer_errors([(bf16_values(x), y) for x, y in tapped.attn], attention)
    errs["attention_layer"] = dict(summarize_attention_layer(errors, reference),
                                   impls={str(k): v for k, v in impls.items()})
    errors, held = experts.expert_layer_errors(
        engine.params, config, tapped.inputs, lambda x: served_expert_layers(engine, config, x))
    errs["expert_layer"] = experts.summarize_expert_layer(errors, held, reference)
    return errs, bool(agrees and errs["kda_layer"]["agrees"]
                      and errs["attention_layer"]["agrees"] and errs["expert_layer"]["agrees"])


def state_facts(engine, config):
    """What the pools and the slots hold, as the engine states it, and the
    share, for the readers of the step records' counts."""
    cfg, model = engine.model_config, config["model"]
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "state_extra_bytes": {name: int(x.nbytes)
                                  for name, x in sorted(engine.state_extra.items())},
            "slot_bytes": engine.slot_pool.bytes_per_slot,
            "solar_shapes": {"kda_layers": cfg.count("k"), "attn_layers": cfg.count("g"),
                             "heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
                             "state_itemsize": 4, "slots": engine.slot_pool.slots},
            "expert_share": {"moe_topk": model["num_experts_per_tok"],
                             "expert_layers": cfg.num_hidden_layers,
                             "experts_held": model["n_routed_experts"],
                             "routed": model["published"]["n_routed_experts"], "zero": 0}}


def reason_metrics(bench, run):
    """:data:`REASON_METRICS` read of a traced run as ``run.py`` reads an
    entered metric: the metric's own file, its reader given the run and the
    file. → {name: {"value", "unit"}}, a metric whose reader finds nothing
    left out."""
    out = {}
    for name in REASON_METRICS:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, _, attr = spec["reader"].partition(":")
        value = bench.load("readers", module.partition(".")[2], attr)(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def run(ctx):
    try:
        import deepspeed_tpu.models.solar_open2  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_solar: the program in this checkout cannot run this "
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
    rules = {str(k): v for k, v in verdict.pop("engine").state_step_impls.items()}
    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN}
                              and bool(rules) and set(rules.values()) == {RULE})
    result["correct"] = bool(verdict["agrees"] and pinned and result["failed"] == 0
                             and facts["compiled_after_warm_up"] == 0
                             and result["attempted"] > 0)
    facts.update(verdict["state"])
    facts["state_step_impls"] = rules
    facts["window"] = verdict["window"]
    # TPOT is no metric of this cell: the line stays short
    facts["tpot_by_request"] = []
    if result.get("trace") is not None:
        facts["layer_metrics_reason"] = reason_metrics(ctx.bench, result)
        facts["kda"] = ctx.bench.load("readers", "solar", "trace_facts")(result)
    log(f"[serve_solar] programs {impls}; state step {rules}; state {verdict['state']}; "
        f"correct {result['correct']}")
    return result
