"""Runner ``serve_nemotron``: the ``serve`` runner for Nemotron-H
(``nemotron3-super-ep4-11l``: Mamba-2 layers whose state is a slot, plain
attention layers whose keys and values are paged beside it, and an expert
layer that works in a latent and is one chip's share of an
expert-parallel deployment).

The client, the two loops, the warm-up, the window's accounting and the
result table are ``runners/serve.py``'s, unedited; the judging of the
logits is ``runners/serve_moonlight.py``'s, unedited (seeded sequences,
the longest prefilled over three SplitFuse chunks, then decode steps of
all through the pools and the slots, every compared position judged by
the reference's margin: ``summarize`` there says how), the serving of
those sequences is ``runners/serve_sala.py``'s and the comparison of the
expert layer alone ``runners/serve_longcat.py``'s, both unedited: this
file loads a private copy of each and gives them what is
this configuration's - the engine builder (the program's
``NemotronHConfig`` from the published keys and the share, the Pallas
paged kernel pinned), the served logits (the engine is told each prompt
before its first chunk, as the scheduler tells it, and **two sequences
take slots that others have just released**), the reference
(``harness/reference_nemotron_h.py``, given the same share) and the served
layers alone.

The logits alone cannot hold either new mechanism. A held pick is 5.5 of
a token's 22, each worth ~0.23 of one latent expert, beside a shared
expert on the full width; and a state that drifts by a part in a
thousand a step moves a logit row by less than bf16 rounding over eleven
layers does. So ``correct`` also compares **the new layers alone**, on
what the reference's layers saw:

- every ``M`` layer (:func:`mamba_layer_readings`,
  :func:`summarize_mamba_layer`): the served mixer -
  ``NemotronHKind.mamba_layer``, the step programs' own packed recurrence
  and slot reads and writes, the engine's weights in place - over the
  check's longest sequence - its first rows in chunks of the token budget,
  then ``reference.mamba_layer.decode_rows`` single decode rows - in a
  slot that held another state: its output a row, and **the state and
  the convolution's tail it leaves**, against the reference's
  token-by-token recurrence;
- every ``E`` layer (``serve_longcat.expert_layer_errors``): the served
  layer on its rows against the reference's.
"""

import functools
import importlib.util
import os
import sys

import numpy as np

from benchmark.harness import reference_nemotron_h
from benchmark.harness.device import log

PIN = "pallas_paged"

MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "num_attention_heads", "num_key_value_heads", "head_dim", "attention_bias",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "expand",
    "chunk_size", "mamba_hidden_act", "mamba_proj_bias", "use_conv_bias", "time_step_min",
    "time_step_max", "time_step_floor", "num_experts_per_tok", "moe_intermediate_size",
    "moe_latent_size", "moe_shared_expert_intermediate_size", "n_shared_experts", "n_group",
    "topk_group", "norm_topk_prob", "routed_scaling_factor", "mlp_hidden_act", "mlp_bias",
    "use_bias", "layer_norm_epsilon", "tie_word_embeddings", "max_position_embeddings")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_nemotron", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.lru_cache(maxsize=None)
def _check():
    """``runners/serve_moonlight.py``'s check (sample, errors by position,
    ``summarize``), reading this configuration's reference and serving
    through :func:`served_logits`."""
    module = _private_copy("serve_moonlight")
    module.reference_moonlight = reference_nemotron_h      # rows_at / head_at, the same signatures
    module.build_engine = build_engine
    module.served_logits = served_logits
    return module


@functools.lru_cache(maxsize=None)
def _expert_check():
    """``runners/serve_longcat.py``'s comparison of an expert layer alone
    (``expert_layer_errors``, ``summarize_expert_layer``), reading this
    configuration's reference (``experts_at``, the same signature)."""
    module = _private_copy("serve_longcat")
    module.reference_longcat = reference_nemotron_h
    module._check = _check
    return module


def nemotron_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``, ``published`` and ``share``) → the program's
    ``NemotronHConfig``: the router keeps the published number of columns,
    of which the file's ``n_routed_experts`` are held."""
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    return NemotronHConfig(
        n_routed_experts=model["published"]["n_routed_experts"],
        experts_held=model["n_routed_experts"],
        first_expert_held=model["share"]["first_expert_held"],
        **{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.nemotron_h import build_nemotron_h
    e = config["engine"]
    return InferenceEngineV2(
        model=build_nemotron_h(nemotron_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            implementation_overrides={} if rehearse else {"attention": PIN},
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


def served_logits(engine, config, seqs):
    """``serve_sala.served_logits`` (the engine is told every prompt before
    its first chunk, ``prefix_match``, where the scheduler tells it; prefill
    in SplitFuse steps of at most the token budget; ``reference.decode_steps``
    steps of one token a sequence through the pools) for an engine whose
    sequences own slots that are not cleared: first two sequences that are
    no part of the check are served (a prompt, two decode steps) and
    flushed, so that the slots the check's first two sequences take - the
    pool hands out the slot released last - hold another sequence's state
    and tail. → [B, 1 + decode_steps, V]."""
    vocab = config["model"]["vocab_size"]
    ghosts = [-(len(seqs) + 1), -(len(seqs) + 2)]
    for g, uid in enumerate(ghosts):
        tokens = (np.arange(40 + 7 * g, dtype=np.int32) * 31 + g) % vocab
        engine.prefix_match(uid, tokens)
        engine.put([uid], [tokens])
    for step in range(2):
        engine.put(ghosts, [np.asarray([step + 1], np.int32)] * len(ghosts))
    for uid in ghosts:
        engine.flush(uid)
    return _private_copy("serve_sala").served_logits(engine, config, seqs)


class Tapped:
    """``reference_nemotron_h`` as the check reads it (``rows_at``,
    ``head_at``), keeping what the layers saw: ``inputs``, every expert
    layer's input at the compared positions, [E layers, b, n, D] a batch
    of the reference; and ``mamba``, of the **first** batch's longest
    sequence, ``(x, y, state, tail)`` an ``M`` layer - what the mixer saw
    and gave a row, and the state and tail the sequence left - on the
    host."""
    head_at = staticmethod(reference_nemotron_h.head_at)

    def __init__(self, longest):
        self.inputs, self.mamba, self.longest = [], [], longest

    def rows_at(self, params, ids, positions, model):
        first = not self.inputs

        def keep(layer, x, y, state, tail):
            if first:
                self.mamba.append(tuple(np.asarray(t[self.longest]) for t in (x, y, state, tail)))

        rows, margins, inputs = reference_nemotron_h.layers_at(params, ids, positions, model,
                                                               tap=keep)
        self.inputs.append(inputs)
        return rows, margins


DECODE_BUCKET = 8       # rows of the program that takes a single decode row of the M layer's check


def served_mamba_layer(engine, config, layer, x, state_dtype=None):
    """x [S, D] (one sequence's normalised stream into ``M`` layer
    ``layer``) → (y [S, D] float32, the state [H, P, N] and the tail
    [K - 1, C] its slot holds afterwards): ``NemotronHKind.mamba_layer`` -
    the step programs' own function, the engine's weights in place - over
    a fresh slot pool whose slots are **not empty** (ones: position 0 has
    to ignore them): the sequence's first rows in calls of ``token_budget``
    rows as a prompt step has them, its last
    ``reference.mamba_layer.decode_rows`` rows one a call as decode steps
    have them (the rows past the sequence's are padding's). Hundreds of
    single rows, because that is where a state held in too few bits shows:
    every step rounds all of it again, and an increment of a thousandth of
    a slow head's state is under bfloat16's last place. ``state_dtype``:
    None, or a control's - the state rounded to it between calls, as a
    pool of that type would hold it."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import NemotronHKind
    cfg, budget = engine.model_config, config["engine"]["token_budget"]
    S = x.shape[0]
    Lm = cfg.count("M")
    ssm = jnp.ones((Lm, 3, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
                   jnp.float32)
    conv = jnp.ones((Lm, 3, cfg.conv_kernel - 1, cfg.conv_dim), engine.dtype)
    tables = jnp.zeros((2, 1), jnp.int32)
    slots = jnp.asarray([[2], [0]], jnp.int32)

    def step(params, layer, x, ssm, conv, seq, pos):
        batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables, "seq_state": slots}
        return NemotronHKind.mamba_layer(params, cfg, layer, x, ssm, conv, batch)

    step = jax.jit(step, donate_argnums=(3, 4))
    prompt = max(S - config["reference"]["mamba_layer"]["decode_rows"], 0)
    cuts = list(range(0, prompt, budget)) + list(range(prompt, S))
    y = []
    for r0, r1 in zip(cuts, cuts[1:] + [S]):
        n = r1 - r0
        rows = budget if n > 1 else DECODE_BUCKET
        part = np.zeros((rows, x.shape[1]), np.float32)
        part[:n] = x[r0:r1]
        seq = np.where(np.arange(rows) < n, 0, 1).astype(np.int32)
        pos = np.where(np.arange(rows) < n, r0 + np.arange(rows), 0).astype(np.int32)
        out, ssm, conv = step(engine.params, jnp.int32(layer), jnp.asarray(part, engine.dtype),
                              ssm, conv, seq, pos)
        if state_dtype is not None:
            # two programs of their own: inside one, XLA drops a round trip through a
            # narrower type (it may keep excess precision), and the control would be the program
            ssm = jax.block_until_ready(ssm.astype(state_dtype)).astype(jnp.float32)
        y.append(out[:n])
    y = np.asarray(jnp.concatenate(y).astype(jnp.float32))
    return y, np.asarray(ssm[layer, 2]), np.asarray(conv[layer, 2].astype(jnp.float32))


def _rel(have, want):
    return float(np.linalg.norm(np.asarray(have, np.float64) - want)
                 / max(np.linalg.norm(want), 1e-30))


def mamba_layer_readings(taps, read):
    """``taps``: :class:`Tapped`'s of the check's longest sequence, one an
    ``M`` layer; ``read(layer, x)`` → the served (y, state, tail) or a
    control's. → (errors [layers, S]: the relative L2 error of the mixer's
    output a row; states [layers], tails [layers]: the relative L2 error of
    the state and of the convolution's tail the sequence leaves)."""
    errors, states, tails = [], [], []
    for layer, (x, y, state, tail) in enumerate(taps):
        # the served stream is bf16: the mixer reads the reference's input at bf16's values
        have, have_state, have_tail = read(layer, np.asarray(x))
        scale = np.maximum(np.linalg.norm(y, axis=-1), 1e-30)
        errors.append(np.linalg.norm(have - y, axis=-1) / scale)
        states.append(_rel(have_state, state))
        tails.append(_rel(have_tail, tail))
    return np.asarray(errors), np.asarray(states), np.asarray(tails)


def summarize_mamba_layer(errors, states, tails, reference):
    """What is reported of the ``M`` layers alone, and ``agrees``: every
    row's output by ``summarize`` with ``reference.mamba_layer``'s limits
    (a layer is what a sequence is to the logits; no margin: nothing here
    is a step function), and every layer's state and tail under
    ``state_tolerance`` and ``tail_tolerance``."""
    limits = reference["mamba_layer"]
    out = _check().summarize(errors, np.zeros(errors.shape), limits)
    out.update(state_max=float(states.max()), state_min=float(states.min()),
               tail_max=float(tails.max()), rows=int(errors.shape[1]),
               by_layer_max=[float(e) for e in errors.max(axis=1)])
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all()
                         and np.isfinite(states).all() and np.isfinite(tails).all()
                         and states.max() <= limits["state_tolerance"]
                         and tails.max() <= limits["tail_tolerance"])
    return out


def served_expert_layers(engine, config, x):
    """x [E layers, N, D] → the served expert layer of each on its rows,
    float32: ``NemotronHKind.expert_layer`` (the step programs' own
    function, the engine's weights in place), ``token_budget`` rows a call
    as a prompt step has them (the last call's rows padded with zeros,
    which are tokens like the others here)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import NemotronHKind
    cfg, rows = engine.model_config, config["engine"]["token_budget"]
    layer = jax.jit(lambda params, l, x: NemotronHKind.expert_layer(params, cfg, l, x))
    out = np.zeros(x.shape, np.float32)
    for l in range(x.shape[0]):
        for start in range(0, x.shape[1], rows):
            part = np.zeros((rows, x.shape[2]), np.float32)
            n = min(rows, x.shape[1] - start)
            part[:n] = x[l, start:start + n]
            got = layer(engine.params, jnp.int32(l), jnp.asarray(part, engine.dtype))
            out[l, start:start + n] = np.asarray(got.astype(jnp.float32))[:n]
    return out


def bf16_values(a):
    """``a`` at the values the served bfloat16 stream can hold, float32."""
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32))


def longest_sample(reference):
    lengths = reference["sample_lengths"]
    return lengths.index(max(lengths))


def reference_check(engine, config, seed):
    """The logits against the reference, then each new layer alone on what
    the reference's layers saw → (what all three read, whether all
    agree)."""
    check, experts = _check(), _expert_check()
    check.reference_moonlight = tapped = Tapped(longest_sample(config["reference"]))
    try:
        errs, agrees = check.reference_check(engine, config, seed)
    finally:
        check.reference_moonlight = reference_nemotron_h
    taps = [(bf16_values(x), y, state, tail) for x, y, state, tail in tapped.mamba]
    errors, states, tails = mamba_layer_readings(
        taps, lambda layer, x: served_mamba_layer(engine, config, layer, x))
    errs["mamba_layer"] = summarize_mamba_layer(errors, states, tails, config["reference"])
    errors, held = experts.expert_layer_errors(
        engine.params, config, tapped.inputs, lambda x: served_expert_layers(engine, config, x))
    errs["expert_layer"] = experts.summarize_expert_layer(errors, held, config["reference"])
    return errs, bool(agrees and errs["mamba_layer"]["agrees"] and errs["expert_layer"]["agrees"])


def state_facts(engine, config):
    """What the pools and the slots hold, as the engine states it, and the
    share, for the readers of the step records' counts."""
    cfg, model = engine.model_config, config["model"]
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "state_extra_bytes": {name: int(x.nbytes)
                                  for name, x in sorted(engine.state_extra.items())},
            "slot_bytes": engine.slot_pool.bytes_per_slot,
            "nemotron_shapes": {"mamba_layers": cfg.count("M"), "attn_layers": cfg.count("*"),
                                "expert_layers": cfg.count("E"),
                                "slots": engine.slot_pool.slots},
            "expert_share": {"moe_topk": model["num_experts_per_tok"],
                             "expert_layers": cfg.count("E"),
                             "experts_held": model["n_routed_experts"],
                             "routed": model["published"]["n_routed_experts"], "zero": 0}}


def run(ctx):
    try:
        import deepspeed_tpu.models.nemotron_h  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_nemotron: the program in this checkout cannot run this "
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
    log(f"[serve_nemotron] programs {impls}; state {verdict['state']}; "
        f"correct {result['correct']}")
    return result
