"""Runner ``serve_laguna``: the ``serve`` runner for Laguna
(``laguna-xs2-ep8-20l``: window (512) and full attention layers 3 : 1 of 64
and 48 query heads over 8 key-value heads of 128, the window layers' keys and
values in a pool of their own that holds a window a sequence, a gate a head,
half-rotary YaRN, and behind every attention a routed feed-forward that is one
chip's share of an 8-way expert-parallel deployment - a dense one in the
leading layer).

The client, the two loops, the warm-up, the window's accounting and the
result table are ``runners/serve.py``'s, unedited; the judging of the logits
and the serving of the check's sequences are ``runners/serve_moonlight.py``'s,
unedited (seeded sequences, the longest prefilled over four SplitFuse chunks,
the short ones sharing chunks, then decode steps of all through both pools,
every compared position judged by the reference's margin: ``summarize`` there
says how); the comparison of the expert layer alone is
``runners/serve_longcat.py``'s, the padding of a call's rows and the errors a
row ``runners/serve_lfm2.py``'s, a sequence's cut into a check's calls
``runners/serve_jamba.py``'s, all unedited: this file loads a private copy of
each and gives them what is this configuration's - the engine builder (the
program's ``LagunaConfig`` from the published keys and the share, the Pallas
paged kernel pinned), the reference (``harness/reference_laguna.py``, given
the same share) and the served layers alone.

**The check's sequences are served with the window pool held short**
(``reference.window_blocks_free``): all but that many of its blocks are
withheld while the check's sequences run, so the allocator's queue goes round
and the longest sequence takes blocks that the others released behind their
windows (``facts.reference_error.window_pool`` says how many; ``correct``
wants some).

The logits alone cannot hold the new mechanisms: a window layer that reads a
block too few or too many, a gate left out of a layer, a rotation over the
wrong columns or a dropped held pick each move a logit row by about what bf16
rounding over twenty layers does. So ``correct`` also compares **the layers
alone**, at the published widths, on what the reference's layers saw:

- every window layer and every full layer (:func:`served_attention_layer`):
  the served mixer - ``LagunaKind.attention_layer``, the step programs' own
  projections, rotation, writes into its pool (the window layers' through a
  ring of a table), the pinned paged kernel at its query group (8 under a
  window, 6 over the whole context) and the gate a head - over the check's
  longest sequence, its first rows in calls of the token budget as a prompt
  step has them (query tiles; a window layer's cross the window's edge), its
  last ``reference.*_attention_layer.decode_rows`` rows one a call in the
  decode program's rows: its output a row against the reference's masked
  softmax over all rows;
- every routed feed-forward (``serve_longcat.expert_layer_errors``): the
  served layer on its rows against the reference's.

A closed loop has no arrival to count a first token from; what a client
waits between sending a request and its first token is in the line's
``facts.window`` (``ttft_p50_ms``, ``ttft_p90_ms``), under no bound.
"""

import contextlib
import functools
import importlib.util
import json
import os
import sys

import numpy as np

from benchmark.harness import reference_laguna
from benchmark.harness.device import log

PIN = "pallas_paged"
FULL, WINDOW = reference_laguna.FULL, reference_laguna.WINDOW
CHECKS = {FULL: "full_attention_layer", WINDOW: "window_attention_layer"}

# The cell's own per-layer metrics: a file each under ``layer_metrics/`` with the reader
# it names, and **no entry in BENCHMARK.json**, whose ``per_layer`` holds the 128 metrics it
# may hold. A traced run reads them here into ``facts.layer_metrics_repochat``; the
# ``benchmark`` PR that makes room enters them, and this table goes.
REPOCHAT_METRICS = ("window_attn_roofline.repochat", "paged_attn_roofline.repochat",
                    "window_attn_share.repochat", "paged_attn_share.repochat",
                    "expert_matmul_share.repochat", "held_rows_per_expert.repochat",
                    "window_blocks_per_seq.repochat", "tokens_per_step.repochat",
                    "mixed_step_ms_p50.repochat", "gap_engine_ms.repochat",
                    "device_idle.repochat", "hbm_peak.repochat", "gate_queued.repochat")

MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "max_position_embeddings", "attention_bias", "rms_norm_eps",
    "num_experts_per_tok", "moe_intermediate_size", "shared_expert_intermediate_size",
    "tie_word_embeddings", "gating", "sliding_window", "rope_parameters", "layer_types",
    "moe_apply_router_weight_on_input", "partial_rotary_factor", "mlp_layer_types",
    "moe_routed_scaling_factor", "num_attention_heads_per_layer", "moe_router_logit_softcapping")


def _private_copy(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_benchmark_runners_{name}_for_laguna", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# three small helpers of ``runners/serve_nemotron.py``'s check, ``runners/serve_lfm2.py``'s
# padding of a call's rows and errors a row, ``runners/serve_jamba.py``'s cut of a sequence into
# a check's calls, its one jitted program a layer kind kept on the engine, and a closed loop's
# time to first token: all unedited
_nemotron, _lfm2, _jamba = (_private_copy("serve_nemotron"), _private_copy("serve_lfm2"),
                            _private_copy("serve_jamba"))
bf16_values, longest_sample = _nemotron.bf16_values, _nemotron.longest_sample
_padded, _row_errors, attention_layer_errors = (_lfm2._padded, _lfm2._row_errors,
                                                _lfm2.attention_layer_errors)
_calls, _programs, window_facts = _jamba._calls, _jamba._programs, _jamba.window_facts


@functools.lru_cache(maxsize=None)
def _check():
    """``runners/serve_moonlight.py``'s check (sample, served logits, errors
    by position, ``summarize``), reading this configuration's reference."""
    module = _private_copy("serve_moonlight")
    module.reference_moonlight = reference_laguna      # rows_at / head_at, the same signatures
    module.build_engine = build_engine
    return module


@functools.lru_cache(maxsize=None)
def _expert_check():
    """``runners/serve_longcat.py``'s comparison of an expert layer alone
    (``expert_layer_errors``, ``summarize_expert_layer``), reading this
    configuration's reference (``experts_at``, the same signature)."""
    module = _private_copy("serve_longcat")
    module.reference_longcat = reference_laguna
    module._check = _check
    return module


def laguna_config(model):
    """The configuration file's ``model`` (the keys of the published
    ``config.json``, ``published`` and ``share``) → the program's
    ``LagunaConfig``: the router keeps the published number of columns, of
    which the file's ``num_experts`` are held; a key the program does not
    support is refused there."""
    from deepspeed_tpu.models.laguna import LagunaConfig
    return LagunaConfig(
        num_experts=model["published"]["num_experts"], experts_held=model["num_experts"],
        first_expert_held=model["share"]["first_expert_held"],
        **{k: model[k] for k in MODEL_KEYS if k in model})


def build_engine(config, seed, rehearse):
    import jax
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.laguna import build_laguna
    e = config["engine"]
    return InferenceEngineV2(
        model=build_laguna(laguna_config(config["model"])),
        config=RaggedInferenceEngineConfig(
            kv_block_size=e["kv_block_size"], num_kv_blocks=e["num_kv_blocks"],
            num_window_blocks=e["num_window_blocks"],
            implementation_overrides={} if rehearse else {"attention": PIN},
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=e["token_budget"],
                max_ragged_sequence_count=e["max_ragged_sequence_count"],
                max_tracked_sequences=e["max_tracked_sequences"],
                max_context=e["max_context"])),
        rng=jax.random.key(seed % (2 ** 31 - 1), impl="rbg"))


class Tapped:
    """``reference_laguna`` as the check reads it (``rows_at``, ``head_at``),
    keeping what the layers saw: ``inputs``, every routed feed-forward's input
    at the compared positions, [Ls, b, n, D] a batch of the reference; and, of
    the **first** batch's longest sequence, ``attn[kind]``: ``(x, y)`` a layer
    of that kind - the normalised input and the output a row - on the host."""
    head_at = staticmethod(reference_laguna.head_at)

    def __init__(self, longest):
        self.inputs, self.attn, self.longest = [], {FULL: [], WINDOW: []}, longest

    def rows_at(self, params, ids, positions, model):
        first = not self.inputs

        def keep(kind, layer, x, y):
            if first:
                self.attn[kind].append(tuple(np.asarray(t[self.longest]) for t in (x, y)))

        rows, margins, inputs = reference_laguna.layers_at(params, ids, positions, model,
                                                           tap=keep)
        self.inputs.append(inputs)
        return rows, margins


@contextlib.contextmanager
def short_window_pool(engine, free):
    """The engine's window pool with all but ``free`` of its free blocks
    withheld, so that what sequences release behind their windows is what
    others are given; → a dict that says, afterwards, how many blocks were
    given a second time."""
    pool = engine.window_pool
    held = pool.reserve(max(0, pool.free_blocks - free))
    given, again = set(), [0]
    reserve = pool.reserve

    def counting(n):
        ids = reserve(n)
        again[0] += len(given.intersection(int(b) for b in ids))
        given.update(int(b) for b in ids)
        return ids

    pool.reserve = counting
    facts = {"blocks_free": int(pool.free_blocks), "released_before": pool.released}
    try:
        yield facts
    finally:
        del pool.reserve
        pool.free(held)
        facts.update(blocks_given=len(given), blocks_given_again=again[0],
                     released=pool.released - facts.pop("released_before"),
                     high_water=pool.high_water - len(held), in_use_after=pool.in_use)
        pool.high_water = pool.in_use       # the run's own high water starts here


def served_attention_layer(engine, config, kind, layer, x):
    """x [S, D] (one sequence's normalised stream into attention layer
    ``layer`` of ``kind``) → (y [S, D] float32, the attention implementation
    each program got): ``LagunaKind.attention_layer`` - the step programs' own
    function, the engine's weights in place, the engine's pinned attention
    implementation, the gate a head - over fresh pools: a full layer's the
    sequence's blocks under its table, a window layer's a ring of
    ``window_pool.ring`` blocks under the ring's table (a block's column is
    its number modulo the ring's: what the sequence wrote ``ring`` blocks
    earlier lies where it writes now). In :func:`_calls`' calls: the token
    budget's rows a prompt call, the decode program's
    (``max_ragged_sequence_count``) a single row."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import LagunaKind
    from deepspeed_tpu.inference.v2.modules.heuristics import AttentionChoice
    cfg, e = engine.model_config, config["engine"]
    bs, S = e["kv_block_size"], x.shape[0]
    blocks = -(-S // bs)
    ring = engine.window_pool.ring
    held = ring if kind == WINDOW else blocks
    shape = (cfg.count(kind), held + 1, bs, cfg.num_key_value_heads * cfg.head_dim)
    kc, vc = jnp.zeros(shape, engine.dtype), jnp.zeros(shape, engine.dtype)
    tables = jnp.asarray([list(range(1, blocks + 1)), [0] * blocks], jnp.int32)
    rings = jnp.asarray([list(range(1, ring + 1)), [0] * ring], jnp.int32)

    def make():
        impl = AttentionChoice(engine._attention.override)

        def step(params, layer, x, kc, vc, tables, seq, pos):
            batch = {"token_seq": seq, "token_pos": pos, "block_tables": tables,
                     "seq_state": rings}
            if x.shape[0] != e["token_budget"]:
                batch["query_tiles"] = None         # a decode program: a row a sequence
            else:
                from deepspeed_tpu.ops.pallas.paged_attention import query_tiles
                batch["query_tiles"] = query_tiles(seq, pos, 1, jnp.sum(seq < 1), tables.shape[1])
            return LagunaKind.attention_layer(params, cfg, kind, layer, x, kc, vc, batch, impl)

        return jax.jit(step, donate_argnums=(3, 4)), impl

    step, impl = _programs(engine, f"attention.{kind}.{blocks}", make)
    y = []
    for r0, n, rows in _calls(S, e["token_budget"], config["reference"][CHECKS[kind]]["decode_rows"],
                              e["max_ragged_sequence_count"]):
        part, seq, pos = _padded(x, r0, n, rows)
        out, kc, vc = step(engine.params, jnp.int32(layer), jnp.asarray(part, engine.dtype), kc, vc,
                           tables, seq, pos)
        y.append(out[:n])
    return np.asarray(jnp.concatenate(y).astype(jnp.float32)), dict(impl.selected)


def served_expert_layers(engine, config, x):
    """x [Ls, N, D] → the served routed feed-forward of each layer on its
    rows, float32: ``LagunaKind.expert_layer`` (the step programs' own
    function, the engine's weights in place), ``token_budget`` rows a call
    as a prompt step has them (the last call's rows padded with zeros, which
    are tokens like the others here)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_runner import LagunaKind
    cfg, rows = engine.model_config, config["engine"]["token_budget"]
    layer = _programs(engine, "experts", lambda: jax.jit(
        lambda params, l, x: LagunaKind.expert_layer(params, cfg, l, x)))
    out = np.zeros(x.shape, np.float32)
    for l in range(x.shape[0]):
        for start in range(0, x.shape[1], rows):
            part = np.zeros((rows, x.shape[2]), np.float32)
            n = min(rows, x.shape[1] - start)
            part[:n] = x[l, start:start + n]
            got = layer(engine.params, jnp.int32(l), jnp.asarray(part, engine.dtype))
            out[l, start:start + n] = np.asarray(got.astype(jnp.float32))[:n]
    return out


def summarize_attention_layer(errors, limits):
    """``summarize`` over every (layer, row) with the layer kind's limits."""
    out = _check().summarize(errors, np.ones(errors.shape), limits)
    out.update(rows=int(errors.shape[1]), by_layer_max=[float(e) for e in errors.max(axis=1)])
    out["agrees"] = bool(out["agrees"] and np.isfinite(errors).all())
    return out


def reference_check(engine, config, seed):
    """The logits against the reference - the check's sequences served with
    the window pool held short -, then each layer kind alone on what the
    reference's layers saw → (what all four read, whether all agree)."""
    check, experts = _check(), _expert_check()
    reference = config["reference"]
    check.reference_moonlight = tapped = Tapped(longest_sample(reference))
    try:
        with short_window_pool(engine, reference["window_blocks_free"]) as pool:
            got = check.served_logits(engine, config, check.reference_sample(config, seed)[0])
        errors, margins, finite = check.reference_errors(
            engine.params, config, seed, lambda first, ids, positions: lambda i: got[first + i])
    finally:
        check.reference_moonlight = reference_laguna
    errs = check.summarize(errors, margins, reference)
    errs["window_pool"] = pool
    agrees = bool(finite and errs["agrees"] and pool["blocks_given_again"] > 0
                  and pool["in_use_after"] == 0)
    for kind, name in CHECKS.items():
        impls = {}

        def attention(layer, x, kind=kind, impls=impls):
            y, selected = served_attention_layer(engine, config, kind, layer, x)
            impls.update(selected)
            return y

        # the served stream is bf16: a mixer reads the reference's input at bf16's values
        errors = attention_layer_errors([(bf16_values(x), y) for x, y in tapped.attn[kind]],
                                        attention)
        errs[name] = dict(summarize_attention_layer(errors, reference[name]),
                          impls={str(k): v for k, v in impls.items()})
        agrees = agrees and errs[name]["agrees"]
    errors, held = experts.expert_layer_errors(
        engine.params, config, tapped.inputs, lambda x: served_expert_layers(engine, config, x))
    errs["expert_layer"] = experts.summarize_expert_layer(errors, held, reference)
    return errs, bool(agrees and errs["expert_layer"]["agrees"])


def state_facts(engine, config):
    """What the two pools hold, as the engine states it, and the share, for
    the readers of the step records' counts."""
    cfg, model = engine.model_config, config["model"]
    row = 2 * cfg.num_key_value_heads * cfg.head_dim * engine.kv_cache.k.dtype.itemsize
    return {"state_kind": engine.state_kind,
            "state_bytes_per_token": engine.state_bytes_per_token,
            "state_extra_bytes": {name: int(x.nbytes)
                                  for name, x in sorted(engine.state_extra.items())},
            "laguna_shapes": {"full_layers": cfg.count(FULL), "window_layers": cfg.count(WINDOW),
                              "kv_row_bytes": row, "window": cfg.sliding_window,
                              "block_size": engine.block_size,
                              "sequences": engine.state_manager.max_tracked_sequences},
            "expert_share": {"moe_topk": model["num_experts_per_tok"],
                             "expert_layers": cfg.count("sparse"),
                             "experts_held": model["num_experts"],
                             "routed": model["published"]["num_experts"], "zero": 0}}


def repochat_metrics(bench, run):
    """:data:`REPOCHAT_METRICS` read of a traced run as ``run.py`` reads an
    entered metric: the metric's own file, its reader given the run and the
    file. → {name: {"value", "unit"}}, a metric whose reader finds nothing
    left out."""
    out = {}
    for name in REPOCHAT_METRICS:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, _, attr = spec["reader"].partition(":")
        value = bench.load("readers", module.partition(".")[2], attr)(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def run(ctx):
    try:
        import deepspeed_tpu.models.laguna  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model kind: fail at once, cleanly
        sys.exit(f"serve_laguna: the program in this checkout cannot run this "
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
    pool = verdict.pop("engine").window_pool
    pinned = ctx.rehearse or (bool(impls) and set(impls.values()) == {PIN})
    result["correct"] = bool(verdict["agrees"] and pinned and result["failed"] == 0
                             and facts["compiled_after_warm_up"] == 0
                             and result["attempted"] > 0)
    facts.update(verdict["state"])
    # the window pool over the whole run: blocks in use at its end, released, its high water,
    # and how often the gate held a request back for it
    facts["window_pool"] = pool.stats()
    facts["window"] = verdict["window"]
    # TPOT is no metric of this cell: the line stays short
    facts["tpot_by_request"] = []
    if result.get("trace") is not None:
        facts["layer_metrics_repochat"] = repochat_metrics(ctx.bench, result)
    log(f"[serve_laguna] programs {impls}; state {verdict['state']}; window pool "
        f"{facts['window_pool']}; correct {result['correct']}")
    return result
