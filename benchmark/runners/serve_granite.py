"""Runner ``serve_granite``: Granite 4.0-H (``granite4-h-small-ep4-10l``:
nine Mamba-2 layers to one position-free attention layer, 18 of 72 small
experts behind every mixer) served as **multi-turn sessions** behind the
prefix cache, whose snapshots of a sequence's Mamba state let a turn start
where the conversation's last turn stopped.

**The client** is this file's (:func:`run_sessions`; the flights, the sweep,
the window's token count and the tracer are ``runners/serve.py``'s, unedited,
a private copy): ``clients`` sessions always in flight, each on its system
prompt. A turn's prompt is **built from the ids the client received** - the
system prompt, then every earlier message and answer of the session, then the
new message - so the history is a true prefix of it; the request names the
system prompt's length as a ``cache_breakpoint``. After a turn's last token the
client thinks (the generator's ``think_s``), then sends the next turn; a
finished session is replaced by the deck's next. ``serve_tok_s`` counts prompt
tokens "whether computed or served from a cache" (``serve.window_tokens``), so
here most of it is cached history: ``prompt_cached_share`` says how much.

**``correct``** is decided at the published widths by what the timed engine
produced, against ``harness/reference_granite.py`` given the same share
(float32, ``jax.default_matmul_precision("highest")``, whole sequences from
token 0):

1. the logits of seeded sequences prefilled in SplitFuse steps beside each
   other, then decoded through the pools and the slots
   (``runners/serve_nemotron.py``'s check, a private copy given this
   configuration's engine, reference and layers: it is ``serve_moonlight``'s
   ``summarize`` over ``serve_sala``'s ``served_logits``);
2. **a resumed turn** (:func:`resume_readings`): a sequence runs a turn and
   retires; its next turn - the first turn's tokens and more - is acquired from
   the cache, ``cached_tokens`` asserted to be the last block boundary the first
   turn crossed, and its logits are compared with the reference's forward over
   the *whole* token string - and, since a logit barely moves with a Mamba
   state (the skip ``D x`` and nine other layers carry it), **the state itself**:
   every Mamba layer's state and tail in the sequence's slot a few rows behind
   the restored snapshot, against what the reference leaves after those tokens
   run from token 0;
3. every Mamba layer alone over prompt chunks and single decode rows - output,
   state and tail it leaves (:func:`served_mamba_layer`);
4. every feed-forward alone (:func:`served_expert_layers`);
5. the attention layer alone (:func:`served_attention_layer`): it is one mixer
   of ten and the logits barely move with the scale of its scores, so
   ``attention_multiplier`` is held here.
"""

import functools
import importlib.util
import os
import sys
import time

import numpy as np

from benchmark.harness import reference_granite
from benchmark.harness.device import log
from benchmark.harness.stats import percentile

PIN = "pallas_paged"

# The cell's own per-layer metrics: a file each under ``layer_metrics/`` with the reader it
# names, and **no entry in BENCHMARK.json**, whose ``per_layer`` holds the 128 metrics it may
# hold. A traced run reads them here into ``facts.layer_metrics_sessions``; the ``benchmark``
# PR that makes room enters them, and this table goes.
SESSIONS_METRICS = ("prompt_cached_share.sessions", "snapshot_copy_share.sessions",
                    "ssm_state_roofline.sessions", "ssm_state_share.sessions",
                    "expert_matmul_roofline.sessions", "held_rows_per_expert.sessions",
                    "state_slots_per_step.sessions", "resume_ttft_p50_ms.sessions",
                    "device_idle.sessions", "hbm_peak.sessions")
DECODE_BUCKET = 8       # rows of the program that takes a single decode row of the layer's check

MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types", "embedding_multiplier",
    "residual_multiplier", "attention_multiplier", "logits_scaling", "num_attention_heads",
    "num_key_value_heads", "attention_bias", "position_embedding_type", "mamba_n_heads",
    "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv", "mamba_expand",
    "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias", "num_experts_per_tok",
    "intermediate_size", "shared_intermediate_size", "hidden_act", "normalization_function",
    "rms_norm_eps", "tie_word_embeddings", "max_position_embeddings")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_granite", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.lru_cache(maxsize=None)
def _nemotron():
    """``runners/serve_nemotron.py``'s check (the logits by ``serve_moonlight``'s
    ``summarize``, every Mamba layer alone, every expert layer alone), reading
    this configuration's reference (the same signatures), engine and layers."""
    module = _private_copy("serve_nemotron")
    module.reference_nemotron_h = reference_granite
    module.build_engine = build_engine
    module.served_mamba_layer = served_mamba_layer
    module.served_expert_layers = served_expert_layers
    module.Tapped.head_at = staticmethod(reference_granite.head_at)   # bound when the class was made
    return module


def granite_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``, ``published`` and ``share``) → the program's
    ``GraniteHybridConfig``: the router keeps the published number of columns,
    of which the file's ``num_local_experts`` are held."""
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig
    return GraniteHybridConfig(
        num_local_experts=model["published"]["num_local_experts"],
        experts_held=model["num_local_experts"],
        first_expert_held=model["share"]["first_expert_held"],
        **{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            PrefixCacheConfig, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.granite_hybrid import build_granite_hybrid
    e = config["engine"]
    return InferenceEngineV2(
        model=build_granite_hybrid(granite_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            implementation_overrides={} if rehearse else {"attention": PIN},
            prefix_cache=PrefixCacheConfig(enabled=True, snapshot_slots=e["snapshot_slots"]),
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


# ----------------------------------------------------------------------------
# the layers alone
# ----------------------------------------------------------------------------


def served_mamba_layer(engine, config, layer, x, state_dtype=None):
    """``serve_nemotron.served_mamba_layer`` for this kind: x [S, D] (one
    sequence's normalised stream into mamba layer ``layer``) → (y [S, D]
    float32, the state [H, P, N] and the tail [K - 1, C] its slot holds
    afterwards), through ``GraniteHybridKind.mamba_layer`` - the step programs'
    own function, the engine's weights in place - over a fresh slot pool whose
    slots hold ones: the first rows in calls of ``token_budget`` rows, the last
    ``reference.mamba_layer.decode_rows`` rows one a call. ``state_dtype``: a
    control's - the state rounded to it between calls."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import GraniteHybridKind
    cfg, budget = engine.model_config, config["engine"]["token_budget"]
    S, Lm = x.shape[0], cfg.count("mamba")
    ssm = jnp.ones((Lm, 3, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state), jnp.float32)
    conv = jnp.ones((Lm, 3, cfg.mamba_d_conv - 1, cfg.conv_dim), engine.dtype)
    tables = jnp.zeros((2, 1), jnp.int32)
    slots = jnp.asarray([[2], [0]], jnp.int32)

    def step(params, layer, x, ssm, conv, seq, pos):
        batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables, "seq_state": slots}
        return GraniteHybridKind.mamba_layer(params, cfg, layer, x, ssm, conv, batch)

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
            # narrower type, and the control would be the program
            ssm = jax.block_until_ready(ssm.astype(state_dtype)).astype(jnp.float32)
        y.append(out[:n])
    y = np.asarray(jnp.concatenate(y).astype(jnp.float32))
    return y, np.asarray(ssm[layer, 2]), np.asarray(conv[layer, 2].astype(jnp.float32))


def served_expert_layers(engine, config, x):
    """x [L, N, D] → the served feed-forward of each layer on its rows, float32:
    ``GraniteHybridKind.expert_layer`` (the step programs' own router, share,
    grouped matmul over the table of every layer's held experts and shared
    expert), ``token_budget`` rows a call."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import GraniteHybridKind
    cfg, rows = engine.model_config, config["engine"]["token_budget"]
    layer = jax.jit(lambda params, l, x: GraniteHybridKind.expert_layer(params, cfg, l, x))
    out = np.zeros(x.shape, np.float32)
    for l in range(x.shape[0]):
        for start in range(0, x.shape[1], rows):
            part = np.zeros((rows, x.shape[2]), np.float32)
            n = min(rows, x.shape[1] - start)
            part[:n] = x[l, start:start + n]
            got = layer(engine.params, jnp.int32(l), jnp.asarray(part, engine.dtype))
            out[l, start:start + n] = np.asarray(got.astype(jnp.float32))[:n]
    return out


def served_attention_layer(engine, config, layer, x):
    """x [S, D] (one sequence's normalised stream into attention layer
    ``layer``) → y [S, D] float32: ``GraniteHybridKind.attention_layer`` - the
    step programs' own projections, scaling of the queries by
    ``attention_multiplier``, writes into the pools and paged attention, the
    engine's weights in place - as one sequence's prompt in calls of
    ``token_budget`` rows over fresh pools of its own."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import GraniteHybridKind
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    from deepspeed_tpu.ops.pallas.paged_attention import query_tiles
    cfg, e = engine.model_config, config["engine"]
    budget, bs = e["token_budget"], e["kv_block_size"]
    S = x.shape[0]
    blocks = -(-S // bs)
    width = cfg.num_key_value_heads * cfg.head_dim
    kc = jnp.zeros((cfg.count("attention"), blocks + 1, bs, width), engine.dtype)
    vc = jnp.zeros_like(kc)
    tables = jnp.asarray([list(range(1, blocks + 1)), [0] * blocks], jnp.int32)
    pinned = set(engine.attention_impls.values())
    choice = AttentionChoice(PIN if pinned == {PIN} else None)

    def step(params, x, kc, vc, seq, pos, live):
        batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables, "live_rows": live}
        batch["query_tiles"] = query_tiles(seq, pos, 1, live, blocks)
        return GraniteHybridKind.attention_layer(params, cfg, layer, x, kc, vc, batch, choice)

    step = jax.jit(step, donate_argnums=(2, 3))
    y = []
    for r0 in range(0, S, budget):
        n = min(budget, S - r0)
        part = np.zeros((budget, x.shape[1]), np.float32)
        part[:n] = x[r0:r0 + n]
        seq = np.where(np.arange(budget) < n, 0, 1).astype(np.int32)
        pos = np.where(np.arange(budget) < n, r0 + np.arange(budget), 0).astype(np.int32)
        out, kc, vc = step(engine.params, jnp.asarray(part, engine.dtype), kc, vc, seq, pos,
                           jnp.int32(n))
        y.append(out[:n])
    return np.asarray(jnp.concatenate(y).astype(jnp.float32))


def attention_layer_readings(taps, read):
    """``taps``: ``(x, y)`` of the check's longest sequence, one an attention
    layer; ``read(layer, x)`` → the served y or a control's. → errors
    [layers, S]: the relative L2 error of the mixer's output a row."""
    errors = []
    for layer, (x, y) in enumerate(taps):
        have = read(layer, np.asarray(x))
        scale = np.maximum(np.linalg.norm(y, axis=-1), 1e-30)
        errors.append(np.linalg.norm(have - y, axis=-1) / scale)
    return np.asarray(errors)


def summarize_attention_layer(errors, reference):
    out = _nemotron()._check().summarize(errors, np.zeros(errors.shape),
                                         reference["attention_layer"])
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all())
    return out


def tapped(longest):
    """``serve_nemotron.Tapped`` (``rows_at`` / ``head_at`` of this
    configuration's reference, keeping every feed-forward's input at the compared
    positions and, of the first batch's longest sequence, what each Mamba layer
    saw, gave and left) that also keeps ``attention``: ``(x, y)`` an attention
    layer of that sequence."""

    class Both(_nemotron().Tapped):
        def __init__(self, longest):
            super().__init__(longest)
            self.attention = []

        def rows_at(self, params, ids, positions, model):
            first = not self.inputs

            def keep(layer, x, y, state, tail):
                if first:
                    self.mamba.append(tuple(np.asarray(t[self.longest])
                                            for t in (x, y, state, tail)))

            def keep_attention(layer, x, y):
                if first:
                    self.attention.append((np.asarray(x[self.longest]),
                                           np.asarray(y[self.longest])))

            rows, margins, inputs = reference_granite.layers_at(
                params, ids, positions, model, tap=keep, tap_attention=keep_attention)
            self.inputs.append(inputs)
            return rows, margins

    return Both(longest)


# ----------------------------------------------------------------------------
# a resumed turn
# ----------------------------------------------------------------------------


def resume_sample(config, seed):
    """The seeded token string of the resumed turn's check → (the string; the
    first turn's prompt length and decode steps; the second turn's prompt
    length; the block boundary the second turn must start at; the compared
    positions [1, n]: the ends of the second turn's first ``near_chunks`` chunks
    of ``near_tokens`` rows - **right behind the restored state**, where a wrong
    one shows before the recurrence has forgotten it - then its prefill's end
    and each of its decode steps)."""
    r = config["reference"]["resume"]
    first, steps, more, decode = r["first_prompt"], r["first_steps"], r["more"], r["decode_steps"]
    rng = np.random.default_rng(seed + 17)
    seq = rng.integers(0, config["model"]["vocab_size"], first + steps + more + decode,
                       dtype=np.int32)
    second = first + steps + more
    bs = config["engine"]["kv_block_size"]
    boundary = (first + steps) // bs * bs
    assert boundary == first + steps, "the first turn ends on a block boundary: the twin's steps"
    near = [boundary + r["near_tokens"] * (k + 1) - 1 for k in range(r["near_chunks"])]
    assert near[-1] < second - 1
    return seq, first, steps, second, boundary, np.asarray(
        [near + [second - 1 + j for j in range(decode + 1)]])


def _prefill(engine, uid, seq, start, stop, budget):
    row = None
    for at in range(start, stop, budget):
        row = engine.put([uid], [seq[at:min(at + budget, stop)]])[0]
    return row


def resume_readings(engine, config, seed, tamper=None, resumed=True):
    """Check (2): a sequence runs a turn (a prompt, then decode rows through the
    pools and the slots, the string's own tokens fed) and retires; its next
    turn's prompt - the first turn's tokens and ``more`` - is acquired from the
    cache and must start at the last block boundary the first turn crossed; →
    (its logits at the compared positions [1, n, V] (:func:`resume_sample`);
    **the rows of its slot** - every Mamba layer's state and tail - as they
    stood behind the near chunks, on the host; what the cache said).
    ``tamper(engine, slot, older)``: a control's hand on the resumed sequence's
    slot before its first row (``older``: the slot's rows as they stood a block
    before the snapshot's boundary, on the host). ``resumed`` False: **the same
    tokens in the same steps as one sequence that never retires** - what a
    resumed turn has to be, bit for bit: the snapshot is a copy and the
    programs are the same."""
    seq, first, steps, second, boundary, positions = resume_sample(config, seed)
    budget, bs = config["engine"]["token_budget"], config["engine"]["kv_block_size"]
    r = config["reference"]["resume"]
    uid = -901

    def slot_rows(uid):
        slot = engine.state_manager.query(uid).state_row[0]
        return {name: np.asarray(engine.state_extra[name][:, slot].astype("float32"))
                for name in engine.kind.slot_state}

    before = engine.prefix_cache.stats()
    _prefill(engine, uid, seq, engine.prefix_match(uid, seq[:first]), first, budget)
    older = None
    for j in range(steps):
        engine.put([uid], [seq[first + j:first + j + 1]])
        if first + j + 1 == boundary - bs:
            older = slot_rows(uid)
    cached = first + steps
    if resumed:
        engine.flush(uid)
        uid -= 1
        cached = engine.prefix_match(uid, seq[:second])
        if cached != boundary:
            raise AssertionError(f"the resumed turn was acquired with cached_tokens={cached}, not "
                                 f"{boundary}: the cache held no snapshot of its history's end")
        if tamper is not None:
            tamper(engine, engine.state_manager.query(uid).state_row[0], older)
    rows, at = [], cached
    for _ in range(r["near_chunks"]):
        rows.append(_prefill(engine, uid, seq, at, at + r["near_tokens"], budget))
        at += r["near_tokens"]
    held = slot_rows(uid)
    rows.append(_prefill(engine, uid, seq, at, second, budget))
    for j in range(r["decode_steps"]):
        rows.append(engine.put([uid], [seq[second + j:second + j + 1]])[0])
    engine.flush(uid)
    after = engine.prefix_cache.stats()
    said = {"cached_tokens": int(cached), "boundary": int(boundary),
            "tokens_saved": after["tokens_saved"] - before["tokens_saved"],
            "snapshots_restored": after["snapshots_restored"] - before["snapshots_restored"]}
    return np.asarray(rows)[None], held, said


def resume_errors(params, config, seed, logits, held):
    """→ (errors, margins [1, n] of a resumed turn's ``logits`` against the
    reference's forward over the whole string; states, tails [mamba layers]:
    the relative L2 error of the slot's rows ``held`` behind the near chunks
    against the state and the tail the reference leaves after **those tokens
    run from token 0**)."""
    import jax.numpy as jnp
    seq, _, _, _, boundary, positions = resume_sample(config, seed)
    model, r = config["model"], config["reference"]["resume"]
    rows, margin = reference_granite.rows_at(params, jnp.asarray(seq[None]), positions, model)
    want = np.asarray(reference_granite.head_at(params, rows, model))[0]
    rel = _nemotron()._check()._serve().rel_err
    errors = np.asarray([[rel(h, w) for h, w in zip(logits[0], want)]])
    left = []
    reference_granite.hidden(
        params, jnp.asarray(seq[None, :boundary + r["near_chunks"] * r["near_tokens"]]), model,
        tap=lambda i, x, y, state, tail: left.append((np.asarray(state[0]), np.asarray(tail[0]))))
    states = np.asarray([rel(held["ssm"][i], state) for i, (state, _) in enumerate(left)])
    tails = np.asarray([rel(held["conv"][i], tail) for i, (_, tail) in enumerate(left)])
    return errors, np.asarray(margin).min(axis=0), states, tails


def twin_drift(held, logits, twin):
    """→ the largest relative L2 distance between a resumed turn's readings
    (its slot's rows behind the near chunks, entry by entry and layer by layer;
    its logits) and its twin's (``resume_readings(resumed=False)``): 0.0 where
    the resumed turn is the unbroken sequence, bit for bit."""
    rel = _nemotron()._check()._serve().rel_err
    twin_logits, twin_held, _ = twin
    apart = [rel(held[name][i], twin_held[name][i]) for name in held
             for i in range(len(held[name]))]
    apart += [rel(a, b) for a, b in zip(logits[0], twin_logits[0])]
    return float(max(apart))


def summarize_resume(errors, margins, states, tails, drift, said, reference):
    """The resumed turn's logits by ``summarize`` with ``reference.resume``'s
    limits; every Mamba layer's state and tail behind the restored snapshot
    under its ``state_tolerance`` / ``tail_tolerance`` (against the float32
    reference: the bfloat16 stream's error through ten layers is in them); and
    its distance from its unbroken twin under ``twin_tolerance`` (exact: any
    fault of the snapshot is in it and nothing else)."""
    limits = reference["resume"]
    out = _nemotron()._check().summarize(errors, margins, limits)
    out.update(said, state_max=float(states.max()), state_min=float(states.min()),
               tail_max=float(tails.max()), twin_drift=drift)
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all()
                         and np.isfinite(states).all() and np.isfinite(tails).all()
                         and states.max() <= limits["state_tolerance"]
                         and tails.max() <= limits["tail_tolerance"]
                         and drift <= limits["twin_tolerance"]
                         and said["cached_tokens"] == said["boundary"] > 0)
    return out


def resume_check(engine, config, seed):
    twin = resume_readings(engine, config, seed, resumed=False)
    logits, held, said = resume_readings(engine, config, seed)
    errors, margins, states, tails = resume_errors(engine.params, config, seed, logits, held)
    return summarize_resume(errors, margins, states, tails, twin_drift(held, logits, twin), said,
                            config["reference"])


def reference_check(engine, config, seed):
    """The logits against the reference (1), then each layer alone on what the
    reference's layers saw - every Mamba layer (3), the attention layer, every
    feed-forward (4) - as ``serve_nemotron.reference_check`` runs them, then a
    resumed turn (2) → (what all read, whether all agree)."""
    nem = _nemotron()
    check, experts = nem._check(), nem._expert_check()
    check.reference_moonlight = kept = tapped(nem.longest_sample(config["reference"]))
    try:
        errs, agrees = check.reference_check(engine, config, seed)
    finally:
        check.reference_moonlight = reference_granite
    taps = [(nem.bf16_values(x), y, state, tail) for x, y, state, tail in kept.mamba]
    errors, states, tails = nem.mamba_layer_readings(
        taps, lambda layer, x: served_mamba_layer(engine, config, layer, x))
    errs["mamba_layer"] = nem.summarize_mamba_layer(errors, states, tails, config["reference"])
    errors = attention_layer_readings(
        [(nem.bf16_values(x), y) for x, y in kept.attention],
        lambda layer, x: served_attention_layer(engine, config, layer, x))
    errs["attention_layer"] = summarize_attention_layer(errors, config["reference"])
    errors, held = experts.expert_layer_errors(
        engine.params, config, kept.inputs, lambda x: served_expert_layers(engine, config, x))
    errs["expert_layer"] = experts.summarize_expert_layer(errors, held, config["reference"])
    errs["resume"] = resume_check(engine, config, seed)
    return errs, bool(agrees and all(errs[k]["agrees"] for k in (
        "mamba_layer", "attention_layer", "expert_layer", "resume")))


# ----------------------------------------------------------------------------
# the sessions
# ----------------------------------------------------------------------------


class Session:
    """One conversation as its client holds it: the ids so far (the system
    prompt, then messages and answers as sent and received), its turns."""
    __slots__ = ("system_len", "history", "turns", "turn", "flight", "wake")

    def __init__(self, system, entry, turn=0, history=None):
        self.system_len = len(system)
        self.history = [int(t) for t in system]
        if history is not None:
            self.history.extend(int(t) for t in history)
        self.turns, self.turn = entry["turns"], turn
        self.flight, self.wake = None, None


def run_sessions(serve, client, traffic, seconds, tracer, sessions_facts):
    """Every client always has a session; a session is sending a turn, waiting
    for its answer, or thinking. → (t_open, the turns that ended inside the
    window)."""
    clock = client.clock
    deck, systems, dealt = traffic["deck"], traffic["systems"], 0
    t0 = clock()
    t_open = t0 + traffic["preroll_s"]
    client.open_at, client.close_at = t_open, t_open + seconds
    deadline = client.close_at + traffic["tail_s"]
    sessions = []
    for c in range(traffic["clients"]):
        entry, start = deck[dealt % len(deck)], traffic["start"][c]
        dealt += 1
        s = Session(systems[entry["system"]], entry, start["turn"], start["history"])
        s.wake = t0 + start["delay_s"]
        sessions.append(s)
    by_flight = {}
    measured = []

    def send(c):
        s = sessions[c]
        turn = s.turns[s.turn]
        s.history.extend(int(t) for t in turn["message"])
        handle = client.gateway.submit(s.history, max_new_tokens=turn["max_new"],
                                       cache_breakpoints=(s.system_len,))
        flight = serve.Flight(handle, s.wake, clock(), len(s.history), turn["max_new"], False, c)
        client.live.append(flight)
        s.flight, s.wake = flight, None
        by_flight[id(flight)] = (c, s.turn)

    while True:
        now = clock()
        for c, s in enumerate(sessions):
            if s.wake is not None and now >= s.wake and now < deadline:
                send(c)
        for f in client.sweep():
            c, turn_index = by_flight.pop(id(f))
            s = sessions[c]
            if client.in_window(f.ended):
                measured.append(f)
                sessions_facts["turns"].append(
                    (turn_index, f.prompt_len, f.tokens,
                     None if f.first is None else (f.first - f.sent) * 1e3))
            if f.error is not None:
                sessions_facts["errors"].append(repr(f.error))
            if f.error is None:
                s.history.extend(int(t) for t in f.handle.result(timeout=5))     # the ids received
            think = s.turns[s.turn]["think_s"]
            s.turn += 1
            s.flight = None
            if s.turn >= len(s.turns) or f.error is not None:
                entry = deck[dealt % len(deck)]
                dealt += 1
                sessions[c] = s = Session(systems[entry["system"]], entry)
                sessions_facts["sessions_ended"] += 1
            s.wake = f.ended + think
        now = clock()
        if client.queued_mid is None and now >= t_open + seconds / 2:
            client.queued_mid = client.gateway.inflight()["queued"]
        if now >= client.close_at:
            if client.queued_end is None:
                client.queued_end = client.gateway.inflight()["queued"]
                tracer.stop_at_close()
            if now >= deadline or all(f.first is not None for f in client.live
                                      if f.sent < client.close_at):
                break
        tracer.maybe_start(now, client.close_at)
        time.sleep(serve.POLL_S)
    return t_open, measured


def warm_up(serve, gateway, config, traffic):
    """``serve.warm_up``'s one request through every program, then **every
    system prompt once**, each with its breakpoint, so that the slot-to-slot
    copy is compiled and every system prompt's snapshot is in the cache before
    the pre-roll's first session asks for it; then one of them again, which is
    served from its snapshot (the restore's first run)."""
    serve.warm_up(gateway, config)
    for system in list(traffic["systems"]) + [traffic["systems"][0]]:
        prompt = np.concatenate([system, np.arange(70, dtype=np.int32)])
        tokens = gateway.submit(prompt, max_new_tokens=2,
                                cache_breakpoints=(len(system),)).result(timeout=900)
        if len(tokens) != 2:
            raise RuntimeError(f"a system prompt's warm-up returned {len(tokens)} tokens of 2")


def state_facts(engine, config):
    """What the pools and the slots hold, as the engine states it, and the
    share, for the readers of the step records' counts."""
    cfg, model = engine.model_config, config["model"]
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "state_extra_bytes": {name: int(x.nbytes)
                                  for name, x in sorted(engine.state_extra.items())},
            "slot_bytes": engine.slot_pool.bytes_per_slot,
            "granite_shapes": {"mamba_layers": cfg.count("mamba"),
                               "attn_layers": cfg.count("attention"),
                               "expert_layers": cfg.num_hidden_layers,
                               "slots": engine.slot_pool.slots,
                               "heads": cfg.mamba_n_heads, "head_dim": cfg.mamba_d_head,
                               "state_size": cfg.mamba_d_state, "groups": cfg.mamba_n_groups},
            "expert_share": {"moe_topk": model["num_experts_per_tok"],
                             "expert_layers": cfg.num_hidden_layers,
                             "experts_held": model["num_local_experts"],
                             "routed": model["published"]["num_local_experts"], "zero": 0,
                             "hidden": model["hidden_size"],
                             "expert_width": model["intermediate_size"]}}


def run(ctx):
    try:
        import deepspeed_tpu.models.granite_hybrid  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_granite: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    from deepspeed_tpu.serving import ServingConfig, ServingGateway
    from benchmark.harness import spans
    serve = _private_copy("serve")
    clock = time.perf_counter
    config, seconds = ctx.config, ctx.seconds
    traffic = ctx.generate(vocab=config["model"]["vocab_size"])
    engine = build_engine(config, ctx.seed, ctx.rehearse)
    log(f"[serve_granite] engine built at {ctx.age():.1f}s; compiles {ctx.meter.totals()}")
    errs, agrees = reference_check(engine, config, ctx.seed)
    state = state_facts(engine, config)
    log(f"[serve_granite] reference check {errs} at {ctx.age():.1f}s; "
        f"compiles {ctx.meter.totals()}")
    counts = spans.instrument(engine, clock)
    gateway = ServingGateway(engine, config=ServingConfig(
        max_queue_depth=config["engine"]["max_queue_depth"], default_max_new_tokens=16,
        max_burst=config["engine"]["max_burst"]))
    sessions_facts = {"turns": [], "errors": [], "sessions_ended": 0}
    try:
        warm_up(serve, gateway, config, traffic)
        log(f"[serve_granite] warm at {ctx.age():.1f}s; compiles {ctx.meter.totals()}")
        compiles_before = ctx.meter.totals()
        counts_before = counts.snapshot()
        cache_before = engine.prefix_cache.stats()
        syncs_before, emitted_before = engine.host_syncs, engine.tokens_emitted
        client = serve.Client(gateway, clock)
        tracer = serve.WindowTracer(ctx.trace, clock, ctx.keep_trace)
        t_open, measured = run_sessions(serve, client, traffic, seconds, tracer, sessions_facts)
        setup_s = ctx.age_at(t_open)
        compiled_in_run = ctx.meter.totals()["compiles"] - compiles_before["compiles"]
        counts_after = counts.snapshot()
        cache_after = engine.prefix_cache.stats()
        syncs, emitted = engine.host_syncs - syncs_before, engine.tokens_emitted - emitted_before
        impls = dict(engine.attention_impls)
        state_steps = {str(k): v for k, v in engine.state_step_impls.items()}
        device = ctx.describe_device()
        snapshot = gateway.snapshot()
        records = _request_records(engine)
    finally:
        gateway.shutdown()
    log(f"[serve_granite] window closed; live high water {client.high_water}, queued mid/end "
        f"{client.queued_mid}/{client.queued_end}, engine {counts_after}; cache {cache_after}")

    attempted = len(measured)
    failed = sum(1 for f in measured if f.error is not None) + len(sessions_facts["errors"])
    ended_inside = [f for f in client.done
                    if f.ended is not None and client.in_window(f.ended) and f.error is None]
    timed = [f for f in ended_inside if f.tokens >= 2]
    tpot = [(f.last - f.first) * 1e3 / (f.tokens - 1) for f in timed]
    decode_s = sum(f.last - f.first for f in timed)
    decode_n = sum(f.tokens - 1 for f in timed)
    steps = counts_after["model_steps"] - counts_before["model_steps"]
    fed = counts_after["tokens_fed"] - counts_before["tokens_fed"]
    turns = sessions_facts["turns"]
    resumed = [ms for turn, _, _, ms in turns if turn >= 1 and ms is not None]
    prompt_tokens = sum(n for _, n, _, _ in turns)
    # of the requests that ended inside the window, by their records: the prompt tokens the
    # cache served
    inside = [r for r in records if r.get("ended_ns") and r["status"] == "completed"
              and r["uid"] in {f.handle.uid for f in ended_inside}]
    cached_tokens = sum(r["prefix_cached_tokens"] for r in inside)
    record_prompt = sum(r["prompt_len"] for r in inside)

    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN})
    correct = bool(agrees and pinned and compiled_in_run == 0 and failed == 0 and attempted > 0)
    facts = {
        "reference_rel_err": errs, "attention_impls": {str(k): v for k, v in impls.items()},
        "state_step_impls": state_steps,
        "compiled_after_warm_up": compiled_in_run, "compile_meter": compiles_before,
        "requests_ended_in_window": len(ended_inside), "high_water": client.high_water,
        "queued_mid": client.queued_mid, "queued_end": client.queued_end,
        "completed_per_s": len(ended_inside) / seconds,
        "tpot_mean_ms": decode_s * 1e3 / decode_n if decode_n else None,
        "tpot_p50_ms": percentile(tpot, 50),
        "gateway_counters": snapshot["counters"],
        "sessions": {"turns_ended_in_window": len(turns),
                     "sessions_ended": sessions_facts["sessions_ended"],
                     "errors": sessions_facts["errors"][:5],
                     "prompt_tokens": prompt_tokens,
                     "prompt_tokens_by_record": record_prompt,
                     "prompt_cached_tokens": cached_tokens,
                     "prompt_len_p50": percentile([n for _, n, _, _ in turns], 50),
                     "resume_ttft_p50_ms": percentile(resumed, 50),
                     "resume_ttft_p90_ms": percentile(resumed, 90),
                     "first_turn_ttft_p50_ms": percentile(
                         [ms for turn, _, _, ms in turns if turn == 0 and ms is not None], 50),
                     "turns_by_depth": np.bincount([t for t, _, _, _ in turns],
                                                   minlength=8).tolist() if turns else []},
        "prefix_cache": {k: (cache_after[k] - cache_before[k]
                             if isinstance(cache_after[k], int) and k not in (
                                 "cached_blocks", "evictable_blocks", "snapshots_cached")
                             else cache_after[k]) for k in cache_after},
    }
    facts.update(state)
    observed = {
        "setup_s": setup_s,
        "serve_tok_s": serve.window_tokens(client) / seconds,
        "tokens_per_step": fed / steps if steps else None,
        "host_syncs_per_tok": syncs / emitted if emitted else None,
        "compile_s": compiles_before["compile_s"] + compiles_before["trace_lower_s"],
    }
    log(f"[serve_granite] programs {impls}; state steps {state_steps}; correct {correct}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "observed": observed, "device": device, "facts": facts,
              "trace": tracer.capture.trace if tracer.capture else None,
              "trace_window_s": tracer.capture.window_s if tracer.capture else None}
    if ctx.trace and result["trace"] is not None:
        facts["layer_metrics_sessions"] = sessions_metrics(ctx.bench, result)
        from benchmark.harness import trace
        facts["census"] = {name: round(s, 4) for name, s in sorted(
            trace.op_seconds(result["trace"]).items(), key=lambda kv: -kv[1])[:24]}
    return result


def sessions_metrics(bench, run):
    """:data:`SESSIONS_METRICS` read of a traced run as ``run.py`` reads an
    entered metric: the metric's own file, its reader given the run and the
    file. → {name: {"value", "unit"}}, a metric whose reader finds nothing
    left out."""
    import json
    out = {}
    for name in SESSIONS_METRICS:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, _, attr = spec["reader"].partition(":")
        value = bench.load("readers", module.partition(".")[2], attr)(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def _request_records(engine):
    from deepspeed_tpu.utils import tracing
    return [r for r in tracing.snapshot()["requests"] if r.get("engine") == engine.trace_id]
