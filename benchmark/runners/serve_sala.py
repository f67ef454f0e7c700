"""Runner ``serve_sala``: the ``serve`` runner for MiniCPM-SALA
(``minicpm-sala-16l``: sparse-attention layers whose paged reads are a
selection, beside linear-attention layers whose state is a slot).

The client, the two loops, the warm-up and the result table are
``runners/serve.py``'s, unedited, and the judging of the logits is
``runners/serve_moonlight.py``'s, unedited (seeded sequences prefilled in
SplitFuse steps, then decode steps of all through the pools, every
compared position judged by the reference's margin: ``summarize`` there
says how): this file loads a private copy of each and gives them what is
this configuration's — the engine builder (the program's
``MiniCPMSalaConfig`` from the published keys and the ``assumed`` sparse
sizes, the Pallas paged kernel pinned), the served logits (the engine is
told each prompt's length before its first chunk, as the scheduler tells
it), the reference (``harness/reference_sala.py``) and **where the window
lays a prompt's tokens** (:func:`window_tokens`: between the first token
the client saw before the prompt's own and that one, not over the
prompt's whole wait; this cell's prompts wait tens of seconds behind one
another, and no other cell's do).

Top-64 of a few hundred blocks is a step function, and with random
weights the blocks score nearly alike, so the program and the reference —
both right — pick differently at some (row, key-value head)s: the
reference's margin between its 64th and 65th block says where that may
be. A differing pick moves little of a logit row (one block of 64 in a
softmax over 4096 rows), so the logits alone cannot hold the selection.
``correct`` therefore also compares **the selection and the sparse layer
alone** (:func:`sparse_layer_readings`, :func:`summarize_sparse_layer`):
the served mixer of every sparse layer — ``SalaKind.sparse_layer``, the
step programs' own writes, selection and paged attention, the engine's
weights in place, rows in steps of the token budget — on the inputs the
reference's sparse layers saw for the check's longest sequence: the blocks
each (row, key-value head) read against the reference's explicit top-k,
and the mixer's output against the reference's.
"""

import functools
import importlib.util
import os
import sys

import numpy as np

from benchmark.harness import reference_sala
from benchmark.harness.device import log

PIN = "pallas_paged"

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "lightning_nh", "lightning_nkv", "lightning_head_dim",
    "lightning_scale", "lightning_use_rope", "attn_use_rope", "qk_norm", "use_output_norm",
    "use_output_gate", "attn_use_output_gate", "mixer_types", "layer_ids", "scale_emb",
    "scale_depth", "dim_model_base", "rope_theta", "rms_norm_eps", "max_position_embeddings",
    "hidden_act", "attention_bias", "tie_word_embeddings")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_sala", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.lru_cache(maxsize=None)
def _check():
    """``runners/serve_moonlight.py``'s check (sample, errors by position,
    ``summarize``), reading this configuration's reference and serving
    through :func:`served_logits`."""
    module = _private_copy("serve_moonlight")
    module.reference_moonlight = reference_sala        # rows_at / head_at of the same signatures
    module.build_engine = build_engine
    module.served_logits = served_logits
    return module


def with_sparse(config):
    """The configuration as the reference reads it: the ``assumed`` sparse
    sizes beside the published keys, under ``model.sparse``."""
    sparse = {k: v for k, v in config["assumed"]["sparse_config"].items() if k != "why"}
    return dict(config, model={**config["model"], "sparse": sparse})


def sala_config(config):
    """The configuration file (the keys of the published ``config.json``,
    ``published``, ``layer_ids`` and ``assumed.sparse_config``) → the
    program's ``MiniCPMSalaConfig``; a key the program does not support is
    refused there."""
    from deepspeed_tpu.models.minicpm_sala import MiniCPMSalaConfig
    model = config["model"]
    sparse = {f"sparse_{k}": v for k, v in config["assumed"]["sparse_config"].items()
              if k != "why"}
    return MiniCPMSalaConfig(
        published_num_hidden_layers=model["published"]["num_hidden_layers"], **sparse,
        **{k: (tuple(model[k]) if isinstance(model[k], list) else model[k])
           for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.minicpm_sala import build_minicpm_sala
    e = config["engine"]
    return InferenceEngineV2(
        model=build_minicpm_sala(sala_config(config)),
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
    """``serve_moonlight.served_logits`` for an engine that wants to know a
    prompt's length before its first chunk (``prefix_match``, where the
    scheduler tells it): prefill in SplitFuse steps of at most the token
    budget, then ``reference.decode_steps`` steps of one token a sequence
    through the pools. → [B, 1 + decode_steps, V]."""
    lengths = _check().sample_lengths(config["reference"])
    budget = config["engine"]["token_budget"]
    uids = [-(i + 1) for i in range(len(seqs))]
    for uid, seq, n in zip(uids, seqs, lengths):
        engine.prefix_match(uid, seq[:n])
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


class Tapped:
    """``reference_sala`` as the check reads it (``rows_at``, ``head_at``),
    keeping what the sparse layers of the **first** sequence saw and gave:
    ``taps``, ``(x, y, chosen, margin)`` a sparse layer, on the host (a
    layer's x and y are 136 MB each at the cell's size)."""
    head_at = staticmethod(reference_sala.head_at)

    def __init__(self, n_sparse):
        self.taps, self.n_sparse = [], n_sparse

    def keep(self, *tapped):
        if len(self.taps) < self.n_sparse:
            self.taps.append(tuple(np.asarray(t) for t in tapped))

    def rows_at(self, params, ids, positions, model):
        return reference_sala.rows_at(params, ids, positions, model, tap=self.keep)


def served_sparse_layer(engine, config, layer, x, sparse_from, select=None):
    """x [S, D] (one sequence's normalised stream into sparse layer
    ``layer``) → (y [S, D] float32, tables [S, Hkv, W], counts [S, Hkv]):
    ``SalaKind.sparse_layer`` — the step programs' own function, the
    engine's weights in place — over fresh pools of the sequence's blocks,
    ``token_budget`` rows a call as a prompt step has them (the last
    call's rows past the sequence are padding's). ``select``: None, or a
    stand-in for the program's selection (a control's)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2 import model_runner
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    cfg, rows = engine.model_config, config["engine"]["token_budget"]
    kind = model_runner.SalaKind
    S, bs = x.shape[0], cfg.sparse_block_size
    blocks = -(-S // bs)
    impl = AttentionChoice(engine._attention.override)
    shape = (kind.state_layers(cfg), blocks + 1, bs, cfg.head_dim)
    kc, vc = jnp.zeros(shape, engine.dtype), jnp.zeros(shape, engine.dtype)
    kb = jnp.zeros(shape[:2] + (bs // cfg.sparse_kernel_stride, cfg.head_dim), engine.dtype)
    tables = np.zeros((2, blocks), np.int32)
    tables[0] = 1 + np.arange(blocks)
    state = np.asarray([[1, int(sparse_from)], [0, 0]], np.int32)

    def step(params, x, kc, vc, kb, seq, pos):
        batch = {"token_seq": seq, "token_pos": pos, "block_tables": jnp.asarray(tables),
                 "seq_state": jnp.asarray(state)}
        return kind.sparse_layer(params, cfg, layer, x, kc, vc, kb, batch, impl)

    real = model_runner._sala_select
    if select is not None:
        model_runner._sala_select = functools.partial(select, real)
    try:
        step = jax.jit(step, donate_argnums=(2, 3, 4))
        y, tabs, counts = [], [], []
        for r0 in range(0, S, rows):
            n = min(rows, S - r0)
            part = np.zeros((rows, x.shape[1]), np.float32)
            part[:n] = x[r0:r0 + n]
            seq = np.where(np.arange(rows) < n, 0, 1).astype(np.int32)
            pos = np.where(np.arange(rows) < n, r0 + np.arange(rows), 0).astype(np.int32)
            out, kc, vc, kb, tab, count = step(engine.params, jnp.asarray(part, engine.dtype),
                                               kc, vc, kb, seq, pos)
            y.append(np.asarray(out.astype(jnp.float32))[:n])
            tabs.append(np.asarray(tab)[:n])
            counts.append(np.asarray(count)[:n])
    finally:
        model_runner._sala_select = real
    return np.concatenate(y), np.concatenate(tabs), np.concatenate(counts)


def sparse_layer_readings(config, taps, read):
    """``taps``: :class:`Tapped`'s of the check's first (longest, sparsely
    prefilled) sequence, one a sparse layer; ``read(layer, x)`` → the
    served (y, tables, counts) or a control's. → (errors [layers, S]: the
    relative L2 error of the mixer's output a row; same [layers, S]: whether
    both key-value heads read exactly the reference's blocks; margins
    [layers, S]: the reference's, the smaller head's, +inf where every block
    is read)."""
    errors, same, margins = [], [], []
    for layer, (x, y, chosen, margin) in enumerate(taps):
        want, chosen = np.asarray(y), np.asarray(chosen)                    # [S, D], [Hkv, S, NB]
        have, tables, counts = read(layer, np.asarray(x))
        scale = np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
        errors.append(np.linalg.norm(have - want, axis=-1) / scale)
        S, Hkv, W = tables.shape
        read_blocks = np.zeros((Hkv, S, chosen.shape[-1] + 1), bool)        # last: "no block"
        cols = np.where(np.arange(W)[None, None, :] < counts[..., None],
                        np.minimum(tables, chosen.shape[-1]), chosen.shape[-1])
        np.put_along_axis(read_blocks, np.moveaxis(cols, 1, 0), True, axis=-1)
        same.append((read_blocks[..., :-1] == chosen).all(axis=(0, 2)))
        margins.append(np.asarray(margin))
    return np.asarray(errors), np.asarray(same), np.asarray(margins)


def summarize_sparse_layer(errors, same, margins, reference):
    """What is reported of the sparse layer alone, and ``agrees``: the
    mixer's output by ``summarize`` with ``reference.sparse_layer``'s limits
    (a layer is what a sequence is to the logits, the selection's margin
    the tiers'), and the selection itself — of the rows whose margin is
    above ``margin``, at least ``share`` read exactly the reference's
    blocks, for every ``[margin, share]`` of ``selection_agreement_min``."""
    limits = reference["sparse_layer"]
    finite = np.where(np.isfinite(margins), margins, 1e9)
    out = _check().summarize(errors, finite, limits)
    tiers = []
    for margin, share in limits["selection_agreement_min"]:
        among = finite > margin if margin > 0 else np.ones(finite.shape, bool)
        tiers.append({"margin_over": margin, "rows": int(among.sum()),
                      "same": int(same[among].sum()), "share_min": share})
    selecting = np.isfinite(margins)
    out.update(selection_tiers=tiers, rows_selecting=int(selecting.sum()),
               same_share_selecting=float(same[selecting].mean()) if selecting.any() else None,
               same_share_all=float(same.mean()))
    out["agrees"] = bool(out["agrees"] and selecting.any() and np.isfinite(errors).all()
                         and all(t["rows"] > 0 and t["same"] >= t["share_min"] * t["rows"]
                                 for t in tiers))
    return out


def first_sparse_from(config):
    """``sparse_from`` of the check's first sequence: 0 where its prompt
    has ``dense_len`` tokens or more."""
    dense_len = config["assumed"]["sparse_config"]["dense_len"]
    return 0 if config["reference"]["sample_lengths"][0] >= dense_len else dense_len - 1


def reference_check(engine, config, seed):
    """The logits against the reference, then the selection and the sparse
    layer alone on what the reference's sparse layers saw → (what both
    read, whether both agree)."""
    config = with_sparse(config)
    check = _check()
    tapped = Tapped(config["model"]["mixer_types"].count(reference_sala.SPARSE))
    check.reference_moonlight = tapped
    try:
        errs, agrees = check.reference_check(engine, config, seed)
    finally:
        check.reference_moonlight = reference_sala
    sparse_from = first_sparse_from(config)
    errors, same, margins = sparse_layer_readings(
        config, tapped.taps,
        lambda layer, x: served_sparse_layer(engine, config, layer, x, sparse_from))
    errs["sparse_layer"] = summarize_sparse_layer(errors, same, margins, config["reference"])
    return errs, bool(agrees and errs["sparse_layer"]["agrees"])


def prompt_spans(client):
    """Every flight that has its first token → (flight, begun): ``begun``
    is the later of when the request was sent and the first token the
    client saw before this flight's own, whichever flight's that was.
    The server computes queued prompts one after another (the
    scheduler's live requests keep their order of arrival), so until that
    token came it was still on another prompt and cannot have begun this
    one. A client can see that much of a prefill from its own streams."""
    spans, before = [], -np.inf
    for f in sorted((f for f in client.done + client.live if f.first is not None),
                    key=lambda f: f.first):
        spans.append((f, max(f.sent, before)))
        before = f.first
    return spans


def window_tokens(client):
    """``serve.window_tokens`` for prompts that queue behind one another:
    every generated token received in the window, and of each prompt the
    share that lies in it of the time **in which it can have been
    computed** (:func:`prompt_spans`), where ``serve.py`` takes the share of
    the whole time between sending and first token. The two are one number
    wherever a prompt does not wait for another's prefill, which is every
    other cell (prompts of one or two steps). Here a prompt is 20-48 steps
    of the engine and waits 10-45 s behind the prompts before it - the
    first wave is 393k tokens - so laying it evenly over its wait counts
    most of a prompt that was computed inside the window outside it, and
    by how much is the seed's order of the deck: sets of six seeds spread
    0.09-0.22 by that rule while every prompt of every run was computed
    at 6.9-7.3k tokens a second (PERF.md section 6, PR 34). A prompt's tokens are still counted once,
    from the client's clock alone; a prompt whose first token came with
    another's counts whole at that instant."""
    prompts = 0.0
    for f, begun in prompt_spans(client):
        if f.first <= begun:
            prompts += f.prompt_len if client.in_window(f.first) else 0
            continue
        inside = min(f.first, client.close_at) - max(begun, client.open_at)
        if inside > 0:
            prompts += f.prompt_len * inside / (f.first - begun)
    return client.generated_in_window + prompts


def window_facts(client, by_wait):
    """What both rules read, the longest wait between two deliveries of
    one stream inside the window (a stall of the engine shows here), and
    every prompt behind the count: seconds after the window opened at which
    it was sent, can have been begun and gave its first token, and its
    length."""
    seconds = client.close_at - client.open_at
    return {"serve_tok_s_by_wait": by_wait / seconds,
            "generated_tok_s": client.generated_in_window / seconds,
            "gap_max_ms": max(client.gaps_ms, default=None),
            "prompts": [[round(f.sent - client.open_at, 3), round(begun - client.open_at, 3),
                         round(f.first - client.open_at, 3), f.prompt_len]
                        for f, begun in prompt_spans(client)]}


def state_facts(engine, config):
    """What the pools hold, as the engine states it, for the readers: the
    sparse layers' shapes (``sala_shapes``) behind the roofline's bytes."""
    cfg = engine.model_config
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "sala_shapes": {"sparse_layers": len(cfg.sparse_positions),
                            "linear_layers": len(cfg.linear_positions),
                            "heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
                            "head_dim": cfg.head_dim, "block_size": cfg.sparse_block_size,
                            "topk": cfg.sparse_topk, "itemsize": 2},
            "state_extra_bytes": {name: int(x.nbytes)
                                  for name, x in sorted(engine.state_extra.items())}}


def run(ctx):
    try:
        import deepspeed_tpu.models.minicpm_sala  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_sala: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    serve = _private_copy("serve")
    verdict = {}

    def checked(engine, config, seed):
        errs, verdict["agrees"] = reference_check(engine, config, seed)
        verdict["state"] = state_facts(engine, config)
        return errs, verdict["agrees"]

    by_wait = serve.window_tokens

    def counted(client):
        verdict["window"] = window_facts(client, by_wait(client))
        return window_tokens(client)

    serve.build_engine, serve.reference_check = build_engine, checked
    serve.window_tokens = counted
    result = serve.run(ctx)
    facts = result["facts"]
    impls = facts["attention_impls"]
    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN})
    result["correct"] = bool(verdict["agrees"] and pinned and result["failed"] == 0
                             and facts["compiled_after_warm_up"] == 0
                             and result["attempted"] > 0)
    facts.update(verdict["state"])
    facts["window"] = verdict["window"]
    log(f"[serve_sala] programs {impls}; state {verdict['state']}; correct {result['correct']}")
    return result
