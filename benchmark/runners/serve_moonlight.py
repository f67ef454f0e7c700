"""Runner ``serve_moonlight``: the ``serve`` runner for the model kind
whose paged state is latent (Moonlight-16B-A3B, ``model_type:
deepseek_v3``).

The client, the two loops, the warm-up, the window's accounting and the
result table are ``runners/serve.py``'s, unedited: this file loads a
private copy of that module and gives it three things of its own — the
engine builder (the program's ``MoonlightConfig`` from the published
keys, the latent decode kernel pinned), the reference check (prefill of
seeded sequences, the longest over several SplitFuse chunks, then
several decode steps through the latent cache, against
``harness/reference_moonlight.py`` at the compared positions only) and
the name every program has to report for ``correct``.
"""

import functools
import importlib.util
import os
import sys

import numpy as np

from benchmark.harness import reference_moonlight
from benchmark.harness.device import log

PIN = "pallas_paged_mla"

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "first_k_dense_replace", "moe_layer_freq", "n_routed_experts", "num_experts_per_tok",
    "n_shared_experts", "scoring_func", "topk_method", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor", "rope_theta", "rms_norm_eps",
    "max_position_embeddings", "hidden_act", "attention_bias", "tie_word_embeddings")


@functools.lru_cache(maxsize=None)
def _serve():
    """A copy of ``runners/serve.py`` that is this runner's alone (the
    harness loads this file anew for every run, and this copy with it)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve.py")
    spec = importlib.util.spec_from_file_location("_benchmark_runners_serve_for_moonlight", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def moonlight_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``) → the program's ``MoonlightConfig``; a key the
    program does not support is refused there."""
    from deepspeed_tpu.models.moonlight import MoonlightConfig
    return MoonlightConfig(rope_scaling=model.get("rope_scaling"),
                           **{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_moonlight
    e = config["engine"]
    return InferenceEngineV2(
        model=build_moonlight(moonlight_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            implementation_overrides={} if rehearse else {"attention": PIN},
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


def sample_lengths(reference):
    """The prefill length of every sequence of the check: the
    configuration's ``sample_lengths``, then its ``short_samples``."""
    short = reference["short_samples"]
    return list(reference["sample_lengths"]) + [short["tokens"]] * short["count"]


def reference_sample(config, seed):
    """The seeded sequences of the check, each ``reference.decode_steps``
    longer than its prefill → (the sequences; the compared positions
    [B, 1 + decode_steps]: the prefill's last and every decode step's; the
    batches the reference runs them in, ``(first, ids [b, S])`` - the
    ``sample_lengths`` padded to the longest, and the short ones, which are
    of one length: one batch of all would pad each to the longest)."""
    rng = np.random.default_rng(seed)
    vocab = config["model"]["vocab_size"]
    reference = config["reference"]
    lengths, steps = sample_lengths(reference), reference["decode_steps"]
    seqs = [rng.integers(0, vocab, n + steps, dtype=np.int32) for n in lengths]
    positions = np.asarray([[n - 1 + j for j in range(steps + 1)] for n in lengths])
    n_long = len(reference["sample_lengths"])
    padded = np.zeros((n_long, max(len(s) for s in seqs[:n_long])), np.int32)
    for i, s in enumerate(seqs[:n_long]):
        padded[i, :len(s)] = s
    return seqs, positions, [(0, padded), (n_long, np.stack(seqs[n_long:]))]


def served_logits(engine, config, seqs):
    """Prefill in SplitFuse steps of at most the token budget (a sequence
    longer than what is left of a step's budget goes on in the next, so
    the longest runs over several chunks beside the others), then
    ``reference.decode_steps`` steps of one token a sequence through the
    cache. → [B, 1 + decode_steps, V]."""
    lengths = sample_lengths(config["reference"])
    budget = config["engine"]["token_budget"]
    uids = [-(i + 1) for i in range(len(seqs))]
    fed = [0] * len(seqs)
    rows = [[] for _ in seqs]
    while any(f < n for f, n in zip(fed, lengths)):
        room, batch = budget, []
        for i, n in enumerate(lengths):
            take = min(n - fed[i], room)
            if take > 0:
                batch.append((i, take))
                room -= take
        out = engine.put([uids[i] for i, _ in batch],
                         [seqs[i][fed[i]:fed[i] + take] for i, take in batch])
        for row, (i, take) in zip(out, batch):
            fed[i] += take
            if fed[i] == lengths[i]:
                rows[i].append(row)
    for j in range(config["reference"]["decode_steps"]):
        out = engine.put(uids, [s[n + j:n + j + 1] for s, n in zip(seqs, lengths)])
        for i, row in enumerate(out):
            rows[i].append(row)
    for uid in uids:
        engine.flush(uid)
    return np.asarray(rows)


def reference_errors(params, config, seed, read):
    """→ (errors [B, 1 + decode_steps]: the relative L2 error, against the
    float32 reference's logits on ``params``, of the logits that ``read``
    gives at every compared position (a sequence's prefill end, then each
    decode step); margins, of that shape: the smallest over the expert
    layers of the reference router's margin there; whether every logit
    read was finite). ``read(first, ids, positions)``, once a batch of the
    reference → ``i → logits [1 + decode_steps, V]`` of its sequence ``i``:
    the served program's (:func:`reference_check`), or a control's."""
    import jax.numpy as jnp
    rel_err = _serve().rel_err
    model = config["model"]
    _, positions, batches = reference_sample(config, seed)
    errors, margins = np.zeros(positions.shape), np.zeros(positions.shape)
    finite = True
    for first, ids in batches:
        at = positions[first:first + len(ids)]
        rows, margin = reference_moonlight.rows_at(params, jnp.asarray(ids), at, model)
        margins[first:first + len(ids)] = np.asarray(margin).min(axis=0)
        logits_of = read(first, jnp.asarray(ids), at)
        for i in range(len(ids)):
            # the head a sequence at a time: every position's logits at once would be 2.3 GB
            want = np.asarray(reference_moonlight.head_at(params, rows[i:i + 1], model))[0]
            have = np.asarray(logits_of(i))
            finite = finite and bool(np.isfinite(have).all())
            errors[first + i] = [rel_err(h, w) for h, w in zip(have, want)]
    return errors, margins, finite


def summarize(errors, margins, reference):
    """Per-position errors and router margins [B, n], the configuration's
    ``reference`` → what is reported, and ``agrees``.

    The router is a step function: it takes 6 of 64 experts by score, and
    where the 6th and 7th lie closer than the bf16 rounding a hidden state
    has collected, the served program and the float32 reference - both
    right - take different experts in that layer. Such a position reads
    0.065-0.65 where the others read 0.013-0.028, with nothing in between,
    and on the chip about two in five do: 68 % of those whose margin (6th
    over 7th of score + bias in the reference, smallest over the layers)
    is under 0.0005, 24 % at 0.003-0.004, 4.8 % at 0.006-0.0085, none of 151
    above 0.012 (the configuration's ``reference.why`` has the readings). So a
    position over ``reference.tolerance`` is not a fault by itself, but
    how many there may be is bounded, and the bound tightens with the
    margin, which is the reference's alone to say: every ``[margin,
    share]`` of ``reference.flipped_share_max`` holds the share of
    positions over the tolerance, among those whose margin is above
    ``margin``, to ``share``; the first is over all positions. Each
    sequence alone - the one prefilled over several chunks too - is held
    to ``reference.flipped_share_max_a_sequence``. A fault or a lower
    precision that moves every position fails every bound; one that
    moves some has to hide among the positions the reference calls
    fragile, in their proportion."""
    over = errors > reference["tolerance"]
    tiers = []
    for margin, share in reference["flipped_share_max"]:
        among = margins > margin if margin > 0 else np.ones(margins.shape, bool)
        tiers.append({"margin_over": margin, "positions": int(among.sum()),
                      "over": int(over[among].sum()), "share_max": share})
    by_sequence = over.mean(axis=1)
    agrees = bool(all(t["positions"] > 0 and t["over"] <= t["share_max"] * t["positions"]
                      for t in tiers)
                  and by_sequence.max() <= reference["flipped_share_max_a_sequence"])
    return {"agrees": agrees, "positions": int(errors.size), "tiers": tiers,
            "flipped_share": float(over.mean()),
            "flipped_share_by_sequence_max": float(by_sequence.max()),
            "largest_margin_over_tolerance": float(margins[over].max()) if over.any() else None,
            "largest_under_tolerance": float(errors[~over].max()) if not over.all() else None,
            "median": float(np.median(errors)), "max": float(errors.max())}


def reference_check(engine, config, seed):
    got = served_logits(engine, config, reference_sample(config, seed)[0])
    errors, margins, finite = reference_errors(
        engine.params, config, seed, lambda first, ids, positions: lambda i: got[first + i])
    errs = summarize(errors, margins, config["reference"])
    return errs, finite and errs["agrees"]


def run(ctx):
    try:
        import deepspeed_tpu.models.moonlight  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_moonlight: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    serve = _serve()
    verdict = {}

    def checked(engine, config, seed):
        errs, verdict["agrees"] = reference_check(engine, config, seed)
        # what the pool holds, as the engine states it; the roofline reader takes the
        # pooled row's width from here (rank + the rotated key's lanes), not from a guess
        model, itemsize = config["model"], 2
        row = engine.state_bytes_per_token // (model["num_hidden_layers"] * itemsize)
        verdict["state"] = {
            "state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "latent_shapes": {"layers": model["num_hidden_layers"],
                              "heads": model["num_attention_heads"],
                              "rank": model["kv_lora_rank"],
                              "lanes": row - model["kv_lora_rank"], "itemsize": itemsize}}
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
    log(f"[serve_moonlight] programs {impls}; state {verdict['state']}; "
        f"correct {result['correct']}")
    return result
