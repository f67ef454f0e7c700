"""Runner ``serve_lfm2``: the ``serve`` runner for LFM2-MoE
(``lfm2-24b-a2b-10l``: gated short-convolution operators whose two-row
tail is a slot, grouped-query attention at a head of 64 whose keys and
values are paged beside it, and 64 whole experts behind a biased sigmoid
router).

The client, the two loops, the warm-up, the window's accounting and the
result table are ``runners/serve.py``'s, unedited; the judging of the
logits is ``runners/serve_moonlight.py``'s, unedited (seeded sequences,
the longest prefilled over six SplitFuse chunks, then decode steps of all
through the pools and the slots, every compared position judged by the
reference's margin: ``summarize`` there says how); the serving of those
sequences is ``runners/serve_nemotron.py``'s, unedited (the engine is told
each prompt before its first chunk, and **two sequences take slots that
others have just released**), and the comparison of the expert layer
alone ``runners/serve_longcat.py``'s: this file loads a private copy of
each and gives them what is this configuration's - the engine builder (the
program's ``Lfm2MoeConfig`` from the published keys, the Pallas paged
kernel pinned), the reference (``harness/reference_lfm2.py``) and the
served operators alone.

The logits cannot see a fault of the size of bf16's own error confined to
one operator. So ``correct`` also compares **each new part alone**, at the
published widths, on what the reference's layers saw:

- every ``conv`` operator (:func:`conv_layer_readings`,
  :func:`summarize_conv_layer`): the served operator -
  ``Lfm2Kind.conv_layer``, the step programs' own gates, convolution and
  slot reads and writes, the engine's weights in place - over the check's
  longest sequence - its first rows in chunks of the token budget, its
  last ``reference.conv_layer.decode_rows`` rows one a call as decode
  steps have them - in a slot that held another tail: its output a row,
  and **the tail it leaves**, against the reference's shifted products;
- every ``full_attention`` operator (:func:`attention_layer_errors`): the
  served operator - ``Lfm2Kind.attention_layer``, the step programs' own
  norms, rotation, writes into fresh pools and the pinned paged kernel at a
  head of 64 - over the same sequence in chunks of the token budget, its
  output a row against the reference's;
- every expert feed-forward (``serve_longcat.expert_layer_errors``): the
  served layer on its rows against the reference's.
"""

import functools
import importlib.util
import json
import os
import sys

import numpy as np

from benchmark.harness import reference_lfm2
from benchmark.harness.device import log

PIN = "pallas_paged"

# The cell's own per-layer metrics: a file each under ``layer_metrics/`` with the reader
# it names, and **no entry in BENCHMARK.json**, whose ``per_layer`` holds the 128 metrics it
# may hold. A traced run reads them here into ``facts.layer_metrics_rag``; the ``benchmark``
# PR that makes room enters them, and this table goes.
RAG_METRICS = ("paged_attn_roofline.rag", "paged_attn_share.rag", "expert_matmul_share.rag",
               "conv_op_share.rag", "rows_per_expert.rag", "tail_slots_per_step.rag",
               "device_idle.rag", "mixed_step_ms_p50.rag", "hbm_peak.rag")

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "layer_types", "num_attention_heads", "num_key_value_heads",
    "conv_L_cache", "conv_bias", "num_dense_layers", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "use_expert_bias", "routed_scaling_factor", "norm_eps",
    "max_position_embeddings")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ``runners/serve_nemotron.py``'s serving of the check's sequences (two of them in slots that
# flushed sequences have just released) and three small helpers of its check, unedited
_nemotron = _private_copy("serve_nemotron")
bf16_values, longest_sample, _rel = (_nemotron.bf16_values, _nemotron.longest_sample,
                                     _nemotron._rel)


@functools.lru_cache(maxsize=None)
def _check():
    """``runners/serve_moonlight.py``'s check (sample, errors by position,
    ``summarize``), reading this configuration's reference and serving
    through ``runners/serve_nemotron.py``'s ``served_logits``."""
    module = _private_copy("serve_moonlight")
    module.reference_moonlight = reference_lfm2      # rows_at / head_at, the same signatures
    module.build_engine = build_engine
    module.served_logits = _nemotron.served_logits
    return module


@functools.lru_cache(maxsize=None)
def _expert_check():
    """``runners/serve_longcat.py``'s comparison of an expert layer alone
    (``expert_layer_errors``, ``summarize_expert_layer``), reading this
    configuration's reference (``experts_at``, the same signature)."""
    module = _private_copy("serve_longcat")
    module.reference_longcat = reference_lfm2
    module._check = _check
    return module


def lfm2_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``) → the program's ``Lfm2MoeConfig``; a key the program
    does not support is refused there."""
    from deepspeed_tpu.models.lfm2 import Lfm2MoeConfig
    rope = model["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not the default")
    return Lfm2MoeConfig(rope_theta=float(rope["rope_theta"]),
                         **{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.lfm2 import build_lfm2
    e = config["engine"]
    return InferenceEngineV2(
        model=build_lfm2(lfm2_config(config["model"])),
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
    """``reference_lfm2`` as the check reads it (``rows_at``, ``head_at``),
    keeping what the layers saw: ``inputs``, every expert feed-forward's
    input at the compared positions, [expert layers, b, n, D] a batch of
    the reference; and, of the **first** batch's longest sequence,
    ``conv``: ``(x, y, tail)`` a ``conv`` operator, and ``attn``: ``(x, y)``
    an attention operator - what it saw and gave a row, and the tail the
    sequence left - on the host."""
    head_at = staticmethod(reference_lfm2.head_at)

    def __init__(self, longest):
        self.inputs, self.conv, self.attn, self.longest = [], [], [], longest

    def rows_at(self, params, ids, positions, model):
        first = not self.inputs

        def keep(kind, layer, x, y, tail):
            if not first:
                return
            if kind == reference_lfm2.CONV:
                self.conv.append(tuple(np.asarray(t[self.longest]) for t in (x, y, tail)))
            else:
                self.attn.append(tuple(np.asarray(t[self.longest]) for t in (x, y)))

        rows, margins, inputs = reference_lfm2.layers_at(params, ids, positions, model, tap=keep)
        self.inputs.append(inputs)
        return rows, margins


DECODE_BUCKET = 8       # rows of the program that takes a single decode row of an operator's check


def _calls(S, budget, decode_rows):
    """The calls a sequence of ``S`` rows is served in: its first rows in
    chunks of ``budget`` as prompt steps have them, its last
    ``decode_rows`` one a call → [(first row, rows, the program's rows)]."""
    prompt = max(S - decode_rows, 0)
    cuts = list(range(0, prompt, budget)) + list(range(prompt, S))
    return [(r0, r1 - r0, budget if r1 - r0 > 1 else DECODE_BUCKET)
            for r0, r1 in zip(cuts, cuts[1:] + [S])]


def _padded(x, r0, n, rows):
    """→ (the call's rows [rows, D] float32, of which the first ``n`` are
    x's from ``r0`` and the rest padding's; seq; pos)."""
    part = np.zeros((rows, x.shape[1]), np.float32)
    part[:n] = x[r0:r0 + n]
    seq = np.where(np.arange(rows) < n, 0, 1).astype(np.int32)
    pos = np.where(np.arange(rows) < n, r0 + np.arange(rows), 0).astype(np.int32)
    return part, seq, pos


def served_conv_layer(engine, config, layer, x, drop_tails=False):
    """x [S, D] (one sequence's normalised stream into ``conv`` operator
    ``layer``) → (y [S, D] float32, the tail [K - 1, D] its slot holds
    afterwards): ``Lfm2Kind.conv_layer`` - the step programs' own function,
    the engine's weights in place - over a fresh slot pool whose slots are
    **not empty** (ones: position 0 has to ignore them), in
    :func:`_calls`' calls (the rows past the sequence's are padding's).
    ``drop_tails``: a control's - the pool zeroed between calls, what a
    carry that loses the tail at a chunk boundary leaves."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import Lfm2Kind
    cfg, budget = engine.model_config, config["engine"]["token_budget"]
    conv = jnp.ones((cfg.count("conv"), 3, cfg.conv_L_cache - 1, cfg.hidden_size), engine.dtype)
    tables = jnp.zeros((2, 1), jnp.int32)
    slots = jnp.asarray([[2], [0]], jnp.int32)

    def step(params, layer, x, conv, seq, pos):
        batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables, "seq_state": slots}
        return Lfm2Kind.conv_layer(params, cfg, layer, x, conv, batch)

    step = jax.jit(step, donate_argnums=(3,))
    y = []
    for r0, n, rows in _calls(x.shape[0], budget, config["reference"]["conv_layer"]["decode_rows"]):
        part, seq, pos = _padded(x, r0, n, rows)
        out, conv = step(engine.params, jnp.int32(layer), jnp.asarray(part, engine.dtype), conv,
                         seq, pos)
        if drop_tails:
            conv = jnp.zeros_like(conv)
        y.append(out[:n])
    y = np.asarray(jnp.concatenate(y).astype(jnp.float32))
    return y, np.asarray(conv[layer, 2].astype(jnp.float32))


def served_attention_layer(engine, config, layer, x, pool_dtype=None):
    """x [S, D] (one sequence's normalised stream into attention operator
    ``layer``) → y [S, D] float32: ``Lfm2Kind.attention_layer`` - the step
    programs' own function, the engine's weights in place, the engine's
    pinned attention implementation - over fresh pools of the sequence's
    blocks, ``token_budget`` rows a call as a prompt step has them (the
    last call's rows past the sequence are padding's). ``pool_dtype``:
    None, or a control's - the pools rounded to it between calls, as pools
    of that type would hold the keys and values."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import Lfm2Kind
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    cfg, budget = engine.model_config, config["engine"]["token_budget"]
    bs = config["engine"]["kv_block_size"]
    S = x.shape[0]
    blocks = -(-S // bs)
    impl = AttentionChoice(engine._attention.override)
    shape = (cfg.count("full_attention"), blocks + 1, bs, cfg.num_key_value_heads * cfg.head_dim)
    kc, vc = jnp.zeros(shape, engine.dtype), jnp.zeros(shape, engine.dtype)
    tables = jnp.asarray([list(range(1, blocks + 1)), [0] * blocks], jnp.int32)
    slots = jnp.zeros((2, 1), jnp.int32)

    def step(params, layer, x, kc, vc, seq, pos):
        batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables, "seq_state": slots}
        return Lfm2Kind.attention_layer(params, cfg, layer, x, kc, vc, batch, impl)

    step = jax.jit(step, donate_argnums=(3, 4))
    y = []
    for r0 in range(0, S, budget):
        n = min(budget, S - r0)
        part, seq, pos = _padded(x, r0, n, budget)
        out, kc, vc = step(engine.params, jnp.int32(layer), jnp.asarray(part, engine.dtype), kc, vc,
                           seq, pos)
        if pool_dtype is not None:
            # programs of their own: inside one, XLA may drop a round trip through a narrower type
            kc = jax.block_until_ready(kc.astype(pool_dtype)).astype(engine.dtype)
            vc = jax.block_until_ready(vc.astype(pool_dtype)).astype(engine.dtype)
        y.append(out[:n])
    return np.asarray(jnp.concatenate(y).astype(jnp.float32)), dict(impl.selected)


def _row_errors(have, want):
    return np.linalg.norm(have - want, axis=-1) / np.maximum(np.linalg.norm(want, axis=-1), 1e-30)


def conv_layer_readings(taps, read):
    """``taps``: :class:`Tapped`'s of the check's longest sequence, one a
    ``conv`` operator; ``read(layer, x)`` → the served (y, tail) or a
    control's. → (errors [layers, S]: the relative L2 error of the
    operator's output a row; tails [layers]: the relative L2 error of the
    tail the sequence leaves)."""
    errors, tails = [], []
    for layer, (x, y, tail) in enumerate(taps):
        have, have_tail = read(layer, np.asarray(x))
        errors.append(_row_errors(have, y))
        tails.append(_rel(have_tail, tail))
    return np.asarray(errors), np.asarray(tails)


def summarize_conv_layer(errors, tails, reference):
    """What is reported of the ``conv`` operators alone, and ``agrees``:
    every row's output by ``summarize`` with ``reference.conv_layer``'s
    limits (a layer is what a sequence is to the logits; no margin: nothing
    here is a step function), and every layer's tail under
    ``tail_tolerance``."""
    limits = reference["conv_layer"]
    out = _check().summarize(errors, np.zeros(errors.shape), limits)
    out.update(tail_max=float(tails.max()), rows=int(errors.shape[1]),
               by_layer_max=[float(e) for e in errors.max(axis=1)])
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all()
                         and np.isfinite(tails).all() and tails.max() <= limits["tail_tolerance"])
    return out


def attention_layer_errors(taps, read):
    """``taps``: :class:`Tapped`'s ``(x, y)`` an attention operator;
    ``read(layer, x)`` → the served y or a control's. → errors [layers,
    S]: the relative L2 error of the operator's output a row."""
    return np.asarray([_row_errors(read(layer, np.asarray(x)), y)
                       for layer, (x, y) in enumerate(taps)])


def summarize_attention_layer(errors, reference):
    """``summarize`` over every (layer, row) with
    ``reference.attention_layer``'s limits."""
    out = _check().summarize(errors, np.zeros(errors.shape), reference["attention_layer"])
    out.update(rows=int(errors.shape[1]), by_layer_max=[float(e) for e in errors.max(axis=1)])
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all())
    return out


def served_expert_layers(engine, config, x):
    """x [expert layers, N, D] → the served expert feed-forward of each on
    its rows, float32: ``Lfm2Kind.expert_layer`` (the step programs' own
    function, the engine's weights in place), ``token_budget`` rows a call
    as a prompt step has them (the last call's rows padded with zeros,
    which are tokens like the others here)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import Lfm2Kind
    cfg, rows = engine.model_config, config["engine"]["token_budget"]
    layer = jax.jit(lambda params, l, x: Lfm2Kind.expert_layer(params, cfg, l, x))
    out = np.zeros(x.shape, np.float32)
    for l in range(x.shape[0]):
        for start in range(0, x.shape[1], rows):
            part = np.zeros((rows, x.shape[2]), np.float32)
            n = min(rows, x.shape[1] - start)
            part[:n] = x[l, start:start + n]
            got = layer(engine.params, jnp.int32(l), jnp.asarray(part, engine.dtype))
            out[l, start:start + n] = np.asarray(got.astype(jnp.float32))[:n]
    return out


def reference_check(engine, config, seed):
    """The logits against the reference, then each new part alone on what
    the reference's layers saw → (what all four read, whether all
    agree)."""
    check, experts = _check(), _expert_check()
    check.reference_moonlight = tapped = Tapped(longest_sample(config["reference"]))
    try:
        errs, agrees = check.reference_check(engine, config, seed)
    finally:
        check.reference_moonlight = reference_lfm2
    reference = config["reference"]
    # the served stream is bf16: an operator reads the reference's input at bf16's values
    taps = [(bf16_values(x), y, tail) for x, y, tail in tapped.conv]
    errors, tails = conv_layer_readings(
        taps, lambda layer, x: served_conv_layer(engine, config, layer, x))
    errs["conv_layer"] = summarize_conv_layer(errors, tails, reference)
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
    return errs, bool(agrees and all(errs[k]["agrees"] for k in
                                     ("conv_layer", "attention_layer", "expert_layer")))


def state_facts(engine, config):
    """What the pools and the slots hold, as the engine states it, for the
    readers of the step records' counts."""
    cfg, model = engine.model_config, config["model"]
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "state_extra_bytes": {name: int(x.nbytes)
                                  for name, x in sorted(engine.state_extra.items())},
            "slot_bytes": engine.slot_pool.bytes_per_slot,
            "lfm2_shapes": {"conv_layers": cfg.count("conv"),
                            "attn_layers": cfg.count("full_attention"),
                            "expert_layers": cfg.num_moe_layers,
                            "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
                            "kv_itemsize": 2, "slots": engine.slot_pool.slots},
            "expert_share": {"moe_topk": model["num_experts_per_tok"],
                             "expert_layers": cfg.num_moe_layers,
                             "experts_held": model["num_experts"],
                             "routed": model["num_experts"], "zero": 0}}


def rag_metrics(bench, run):
    """:data:`RAG_METRICS` read of a traced run as ``run.py`` reads an
    entered metric: the metric's own file, its reader given the run. → {name:
    {"value", "unit"}}, a metric whose reader finds nothing left out."""
    out = {}
    for name in RAG_METRICS:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, _, attr = spec["reader"].partition(":")
        value = bench.load("readers", module.partition(".")[2], attr)(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def run(ctx):
    try:
        import deepspeed_tpu.models.lfm2  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_lfm2: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    serve = _private_copy("serve")
    verdict = {}

    def checked(engine, config, seed):
        errs, verdict["agrees"] = reference_check(engine, config, seed)
        verdict["state"] = state_facts(engine, config)
        return errs, verdict["agrees"]

    serve.build_engine, serve.reference_check = build_engine, checked
    result = serve.run(ctx)
    facts = result["facts"]
    impls = facts["attention_impls"]
    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN})
    result["correct"] = bool(verdict["agrees"] and pinned and result["failed"] == 0
                             and facts["compiled_after_warm_up"] == 0
                             and result["attempted"] > 0)
    facts.update(verdict["state"])
    if result.get("trace") is not None:
        facts["layer_metrics_rag"] = rag_metrics(ctx.bench, result)
    log(f"[serve_lfm2] programs {impls}; state {verdict['state']}; "
        f"correct {result['correct']}")
    return result
