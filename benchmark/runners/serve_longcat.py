"""Runner ``serve_longcat``: the ``serve`` runner for LongCat-Flash
(``longcat-flash-omni-ep32``: a latent paged state of two state layers a
model layer, and an expert layer that is one chip's share of an
expert-parallel deployment).

The client, the two loops, the warm-up, the window's accounting and the
result table are ``runners/serve.py``'s, unedited, and the reference
check of the logits is ``runners/serve_moonlight.py``'s, unedited (seeded
sequences, the longest prefilled over several SplitFuse chunks, then
decode steps through the latent cache, every compared position judged by
the reference's margin: ``summarize`` there says how): this file loads a
private copy of each and gives them what is this configuration's — the
engine builder (the program's ``LongcatFlashConfig`` from the published
keys and the share, the latent decode kernel pinned) and the reference
(``harness/reference_longcat.py``, given the same share, whose margin
counts only the picks that this share computes).

The logits alone cannot hold the held experts' grouped matmul: a token
has a held pick in about one expert layer in five, worth ~0.06 of one
expert, under the ~0.04 that bf16 rounding leaves in a logit row. So
``correct`` also compares **the expert layer alone**
(:func:`expert_layer_errors`, :func:`summarize_expert_layer`): the served ``M(x)`` of every double layer -
the program's own function, on the engine's weights in place, in steps of
the token budget's rows - against the reference's, on the inputs the
reference's expert layers saw at the compared positions.
"""

import functools
import importlib.util
import os
import sys

from benchmark.harness import reference_longcat
from benchmark.harness.device import log

PIN = "pallas_paged_mla"

MODEL_KEYS = (
    "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size", "num_layers",
    "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
    "zero_expert_num", "zero_expert_type", "moe_topk", "routed_scaling_factor", "rope_theta",
    "rms_norm_eps", "max_position_embeddings", "attention_method", "attention_bias")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_longcat", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.lru_cache(maxsize=None)
def _check():
    """``runners/serve_moonlight.py``'s check (sample, served logits, errors
    by position, ``summarize``), reading this configuration's reference."""
    module = _private_copy("serve_moonlight")
    module.reference_moonlight = reference_longcat     # rows_at / head_at of the same signatures
    module.build_engine = build_engine
    return module


class Tapped:
    """``reference_longcat`` as the check reads it (``rows_at``,
    ``head_at``), keeping what every ``rows_at`` saw go into the expert
    layers at the compared positions: ``inputs``, [double layers, b, n, D]
    a batch of the reference."""
    head_at = staticmethod(reference_longcat.head_at)

    def __init__(self):
        self.inputs = []

    def rows_at(self, params, ids, positions, model):
        rows, margins, inputs = reference_longcat.layers_at(params, ids, positions, model)
        self.inputs.append(inputs)
        return rows, margins


def served_expert_layers(engine, config, x):
    """x [double layers, N, D] → the served expert layer of each on its
    rows, float32: ``LongcatKind.expert_layer`` (the step programs' own
    function, the engine's weights in place), ``token_budget`` rows a
    call as a prompt step has them (the last call's rows padded with
    zeros, which are tokens like the others here)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.v2.model_runner import LongcatKind
    cfg, rows = engine.model_config, config["engine"]["token_budget"]
    layer = jax.jit(lambda params, l, x: LongcatKind.expert_layer(params, cfg, l, x))
    out = np.zeros(x.shape, np.float32)
    for l in range(x.shape[0]):
        for start in range(0, x.shape[1], rows):
            part = np.zeros((rows, x.shape[2]), np.float32)
            n = min(rows, x.shape[1] - start)
            part[:n] = x[l, start:start + n]
            got = layer(engine.params, jnp.int32(l), jnp.asarray(part, engine.dtype))
            out[l, start:start + n] = np.asarray(got.astype(jnp.float32))[:n]
    return out


def expert_layer_errors(params, config, inputs, read):
    """``inputs``: :class:`Tapped`'s, laid side by side as x [double layers,
    N, D] - the reference's expert-layer inputs at every compared
    position, rounded to the served dtype's values → (errors [double
    layers, N]: the relative L2 error, against the reference's ``M(x)`` on
    ``params`` and that x, of the ``M(x)`` that ``read(x)`` gives - the
    served program's (:func:`served_expert_layers`) or a control's; held
    [double layers, N]: whether the reference's router chose a held expert
    there). Where the reference's ``M(x)`` is nothing (every pick absent)
    the error is taken against a thousandth of x."""
    import jax.numpy as jnp
    import numpy as np
    model = config["model"]
    x = np.concatenate([np.asarray(i.astype(jnp.bfloat16).astype(jnp.float32))
                        .reshape(i.shape[0], -1, i.shape[-1]) for i in inputs], axis=1)
    have = read(x)
    errors, held = np.zeros(x.shape[:2]), np.zeros(x.shape[:2], bool)
    for l in range(x.shape[0]):
        want, weight = reference_longcat.experts_at(params, l, jnp.asarray(x[l])[None], model)
        want = np.asarray(want)[0]
        scale = np.maximum(np.linalg.norm(want, axis=-1), 1e-3 * np.linalg.norm(x[l], axis=-1))
        errors[l] = np.linalg.norm(have[l] - want, axis=-1) / scale
        held[l] = np.asarray(weight)[0] > 0
    return errors, held


def summarize_expert_layer(errors, held, reference):
    """Errors and held-pick marks [double layers, N] → what is reported of
    the expert layer, and ``agrees``: ``summarize`` over every (layer,
    position) with ``reference.expert_layer``'s limits - a layer is what a
    sequence is to the logits - and the readings at the positions with a
    held pick, which are the ones a fault of the held experts' grouped
    matmul moves."""
    import numpy as np
    out = _check().summarize(errors, np.zeros(errors.shape), reference["expert_layer"])
    at_held = errors[held]
    out.update(held_positions=int(held.sum()),
               held_over=int((at_held > reference["expert_layer"]["tolerance"]).sum()),
               held_median=float(np.median(at_held)) if at_held.size else None,
               held_min=float(at_held.min()) if at_held.size else None,
               held_max=float(at_held.max()) if at_held.size else None)
    out["agrees"] = bool(out["agrees"] and held.any() and np.isfinite(errors).all())
    return out


def reference_check(engine, config, seed):
    """The logits against the reference, then the expert layer alone on
    what the reference's expert layers saw → (what both read, whether both
    agree)."""
    check = _check()
    check.reference_moonlight = tapped = Tapped()
    try:
        errs, agrees = check.reference_check(engine, config, seed)
    finally:
        check.reference_moonlight = reference_longcat
    errors, held = expert_layer_errors(
        engine.params, config, tapped.inputs,
        lambda x: served_expert_layers(engine, config, x))
    errs["expert_layer"] = summarize_expert_layer(errors, held, config["reference"])
    return errs, bool(agrees and errs["expert_layer"]["agrees"])


def longcat_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``, ``published`` and ``share``) → the program's
    ``LongcatFlashConfig``: the router keeps the published number of
    routed columns, of which the file's ``n_routed_experts`` are held."""
    from deepspeed_tpu.models.longcat import LongcatFlashConfig
    return LongcatFlashConfig(
        n_routed_experts=model["published"]["n_routed_experts"],
        experts_held=model["n_routed_experts"],
        first_expert_held=model["share"]["first_expert_held"],
        **{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.longcat import build_longcat
    e = config["engine"]
    return InferenceEngineV2(
        model=build_longcat(longcat_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            implementation_overrides={} if rehearse else {"attention": PIN},
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


def state_facts(engine, model):
    """What the pool holds, as the engine states it: the roofline reader
    takes its shapes from here (``layers`` = the **state** layers, two a
    double layer); and the share, for the readers of the step records'
    counts."""
    itemsize, state_layers = 2, 2 * model["num_layers"]
    row = engine.state_bytes_per_token // (state_layers * itemsize)
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "latent_shapes": {"layers": state_layers, "heads": model["num_attention_heads"],
                              "rank": model["kv_lora_rank"],
                              "lanes": row - model["kv_lora_rank"], "itemsize": itemsize},
            "expert_share": {"moe_topk": model["moe_topk"], "expert_layers": model["num_layers"],
                             "experts_held": model["n_routed_experts"],
                             "routed": model["published"]["n_routed_experts"],
                             "zero": model["zero_expert_num"]}}


def run(ctx):
    try:
        import deepspeed_tpu.models.longcat  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_longcat: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    serve = _private_copy("serve")
    verdict = {}

    def checked(engine, config, seed):
        errs, verdict["agrees"] = reference_check(engine, config, seed)
        verdict["state"] = state_facts(engine, config["model"])
        return errs, verdict["agrees"]

    serve.build_engine, serve.reference_check = build_engine, checked
    result = serve.run(ctx)
    # serve.run asks every program for the KV kernel's name; this kind's is PIN
    facts = result["facts"]
    impls = facts["attention_impls"]
    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN})
    result["correct"] = bool(verdict["agrees"] and pinned and result["failed"] == 0
                             and facts["compiled_after_warm_up"] == 0
                             and result["attempted"] > 0)
    facts.update(verdict["state"])
    log(f"[serve_longcat] programs {impls}; state {verdict['state']}; "
        f"correct {result['correct']}")
    return result
