"""Runner ``train_mellum``: ``runners/train.py`` by private copy of its loop,
with this configuration's model (``deepspeed_tpu.models.mellum``: the Llama
family's training block under Mellum 2's published keys), mesh (``expert`` =
the cell's chips: every layer's 64 experts spread 16 a chip, everything else
under ZeRO-2 over the same chips) and check.

``correct``, beyond what ``train.py`` checks (finite, falling, no compile
after warm-up, step 2's loss against the reference's on the weights step 2
starts from), all at the timed size on the timed chips, against
``harness/reference_mellum.py``:

- **per-position NLL** of every sequence (one a chip) by the loss's own path
  (``LlamaForCausalLM(per_position=True)``), largest difference: the mean loss
  at a random initialisation is ln V whatever the mask;
- **the gradient's global norm** the engine reads on step 2 (the timed
  program's own output) against the norm of the reference's gradient, computed
  a layer at a time over the same chips;
- the attention half of one ``sliding_attention`` layer, of the
  ``full_attention`` layer, and one expert half, **each alone** through the
  program's own modules (``LlamaAttention`` / ``MoE`` under the engine's mesh)
  on the stream the reference has entering it: output and input-gradient,
  largest difference over the largest reference magnitude;
- ``rows_beyond_passes`` = 0 on every step record.

``reference`` in the configuration's file holds each limit with its reason.
"""

import json
import math
import os
import shutil
import sys
import tempfile
import time


from benchmark.harness import reference_mellum as reference
from benchmark.harness import trace
from benchmark.harness.device import log

TRACE_S = 4.0
# The cell's own per-layer metrics: a file each under ``layer_metrics/`` with the reader
# it names, and **no entry in BENCHMARK.json**, whose ``per_layer`` holds the 128 metrics it
# may hold. A traced run reads them here into ``facts.layer_metrics_moe8k``.
MOE8K_METRICS = ("train_mfu.moe8k", "expert_matmul_roofline.moe8k", "flash_window_roofline.moe8k",
                 "flash_full_roofline.moe8k", "moe_exchange_share.moe8k",
                 "expert_matmul_share.moe8k", "expert_rows_max_over_mean.moe8k",
                 "step_prog_ms_p50.moe8k", "step_host_ms_p50.moe8k", "device_idle.moe8k",
                 "hbm_peak.moe8k", "collective_exposed.moe8k")
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_act",
    "attention_bias", "layer_types", "mlp_layer_types", "max_position_embeddings",
    "max_window_layers", "num_experts", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
    "rope_parameters", "sliding_window", "use_sliding_window", "tie_word_embeddings")


def mellum_config(model):
    from deepspeed_tpu.models.mellum import MellumConfig
    kw = {k: model[k] for k in MODEL_KEYS if k in model}
    for k in ("layer_types", "mlp_layer_types"):
        kw[k] = tuple(kw[k])
    return MellumConfig(**kw)


def build(ctx, ids):
    """The engine over the cell's chips → (engine, the model's flax module,
    the MellumConfig)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.mellum import build_mellum
    from deepspeed_tpu.parallel.topology import make_mesh_topology
    config, trainer = ctx.config, ctx.config["trainer"]
    n_dev = len(ctx.devices)
    if trainer["expert_parallel"] != n_dev and not ctx.rehearse:
        raise ValueError(f"the configuration spreads its experts over "
                         f"{trainer['expert_parallel']} chips, the cell has {n_dev}")
    cfg = mellum_config(config["model"])
    model = build_mellum(cfg, remat=trainer["remat"], remat_policy=trainer["remat_policy"],
                         attention_impl="auto" if ctx.rehearse else "flash",
                         moe_aux_loss_coef=trainer["moe_aux_loss_coef"])
    sequences = ids.shape[0]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh_topology(expert=n_dev, data=1, devices=ctx.devices),
        config={"train_batch_size": sequences,
                # the batch lies over ("data", "expert"): the trainer's data world is 1
                "train_micro_batch_size_per_gpu": sequences,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": trainer["bf16"]},
                "optimizer": trainer["optimizer"],
                **({"scheduler": trainer["scheduler"]} if trainer.get("scheduler") else {}),
                "zero_optimization": {"stage": trainer["zero_stage"]},
                "steps_per_print": 10 ** 9})
    return engine, model, cfg


def system_programs(model, seq_len):
    """The program's side of the check as jitted functions of (parameters,
    inputs): ``nll(params, ids)`` by the loss's own path, and each half of a
    layer alone through the program's own modules → (output, the cotangent
    pulled back to the stream): ``attention_sliding`` / ``attention_full`` /
    ``experts`` ``(layer's params, h, ct)``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaAttention, RMSNorm
    from deepspeed_tpu.moe.layer import MoE, TopKGate
    llama = model.config
    positions = jnp.arange(seq_len)[None, :]

    def attention(kind):
        def fn(lp, h):
            a = RMSNorm(eps=llama.rms_norm_eps).apply({"params": lp["input_layernorm"]}, h)
            return LlamaAttention(llama, kind=kind).apply({"params": lp["self_attn"]}, a,
                                                          positions)[0]
        return fn

    def picks(lp, h):
        m = RMSNorm(eps=llama.rms_norm_eps).apply({"params": lp["post_attention_layernorm"]}, h)
        gate = TopKGate(num_experts=llama.moe_num_experts, k=llama.moe_top_k, drop_tokens=False)
        return gate.apply({"params": lp["moe_mlp"]["deepspeed_moe"]["gate"]}, m)[2]

    def experts(lp, h):
        m = RMSNorm(eps=llama.rms_norm_eps).apply({"params": lp["post_attention_layernorm"]}, h)
        moe = MoE(hidden_size=llama.hidden_size, intermediate_size=llama.moe_intermediate_size,
                  num_experts=llama.moe_num_experts, k=llama.moe_top_k, drop_tokens=False)
        return moe.apply({"params": lp["moe_mlp"]}, m)[0]

    def alone(fn):
        def run(lp, h, ct):
            out, vjp = jax.vjp(lambda x: fn(lp, x), h)
            return out, vjp(ct)[0]
        return jax.jit(run)

    return {"nll": jax.jit(lambda p, x: model.apply({"params": p}, x, x, per_position=True)[0]),
            "attention_sliding": alone(attention(reference.SLIDING)),
            "attention_full": alone(attention(reference.FULL)), "experts": alone(experts),
            "picks": jax.jit(picks)}


def system_readings(engine, model, cfg, ids, streams, seed):
    """What the check reads of the program (no reference in it but the streams
    its halves are given): per-position NLL, and each half alone → dict of
    arrays; ``cotangents`` are the seeded ones the halves' gradients pull back."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    dtype = engine.compute_dtype
    programs = system_programs(model, ids.shape[1])
    placed = jax.device_put(ids, NamedSharding(engine.mesh, P("expert")))
    out = {"nll": programs["nll"](engine.params, placed), "cotangents": {}}
    layers = engine.params["model"]["layers"]
    for name, l, fn in halves(cfg.layer_types):
        lp = jax.tree.map(lambda x: x[l], layers)
        h = streams[name].astype(dtype)
        key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), l)
        ct = jax.jit(lambda k: jax.random.normal(k, h.shape, dtype),
                     out_shardings=h.sharding)(key)
        out[name] = programs[fn](lp, h, ct)
        out["cotangents"][name] = ct
        if fn == "experts":
            out["picks"] = programs["picks"](lp, h)
    return out


def halves(layer_types):
    """The halves the check runs alone: (its name, the layer, which program)."""
    types = list(layer_types)
    sliding, full = types.index(reference.SLIDING), types.index(reference.FULL)
    return (("attn_window", sliding, "attention_sliding"), ("attn_full", full, "attention_full"),
            ("experts", sliding, "experts"))


def half_streams(layer_types, ref_streams, ref_mid):
    """The stream each half is given: the reference's entering the layer for
    an attention half, the one after its attention half for the expert half."""
    out = {}
    for name, l, fn in halves(layer_types):
        out[name] = ref_mid[l] if fn == "experts" else ref_streams[l]
    return out


def reference_readings(params, ids, config, system, faults=reference.NONE, gnorm=True):
    """The reference's side of every comparison, on ``params`` (the engine's
    own arrays, read before the next step donates them) → dict. ``system``
    holds the streams and cotangents the halves were given."""
    import jax
    import jax.numpy as jnp
    model = config["model"]
    coef = float(config["trainer"]["moe_aux_loss_coef"])
    if "float8" in faults:      # every matrix rounded once, for every reading below
        params = reference.rounded_to(params, jnp.float8_e4m3fn)
        faults = frozenset(faults) - {"float8"}
    nll, loss, streams = reference.forward(params, ids, model, faults, coef)
    out = {"nll": nll, "loss": float(loss)}
    if gnorm:
        out["grad_norm"], out["grad_norm_parts"] = reference.grad_norm(
            params, ids, model, streams, faults, coef)
    m = reference._static(model)
    for name, l, fn in halves(model["layer_types"]):
        h = system["streams"][name].astype(jnp.float32)
        ct = system["cotangents"][name].astype(jnp.float32)
        kn = reference.knobs(model, model["layer_types"][l], ids.shape[1], faults)
        if fn == "experts":     # the program's own picks: reference_mellum.route says why
            kn["picks"] = system["picks"]
        out[name] = reference.half_alone(reference.layer_of(params, model, l), h, ct, kn, model=m,
                                         half="experts" if fn == "experts" else "attention")
    del streams
    return out


def given_streams(params, ids, config):
    """The streams the halves are given, from the reference on the model as
    published (never a control's): entering the first sliding layer, entering
    the full layer, and after the first sliding layer's attention half."""
    import jax.numpy as jnp
    model = config["model"]
    m = reference._static(model)
    _, _, streams = reference.forward(params, ids, model)
    types = list(model["layer_types"])
    mid = {}
    l = types.index(reference.SLIDING)
    mid[l] = streams[l] + reference.half_alone(
        reference.layer_of(params, model, l), streams[l], jnp.zeros_like(streams[l]),
        reference.knobs(model, types[l], ids.shape[1]), model=m, half="attention")[0]
    return half_streams(types, streams, mid)


def compare(system, ref, limits):
    """→ ({reading: [value, limit]}, whether every reading is under its limit)."""
    import jax.numpy as jnp
    table = {"nll_max_abs": [float(jnp.max(jnp.abs(system["nll"].astype(jnp.float32) - ref["nll"]))),
                             limits["nll_tolerance"]]}
    if "loss" in system:
        table["loss_abs"] = [abs(system["loss"] - ref["loss"]), limits["tolerance"]]
    if "grad_norm" in system and "grad_norm" in ref:
        table["grad_norm_rel"] = [abs(system["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"],
                                  limits["grad_norm_tolerance"]]
    for name in ("attn_window", "attn_full", "experts"):
        for i, what in enumerate(("out", "dx")):
            got, want = system[name][i].astype(jnp.float32), ref[name][i]
            table[f"{name}_{what}"] = [float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))),
                                       limits["half_tolerance"]]
    # the tokens with a clear margin (route_margin) for which the program's router did not
    # pick the reference's own top k
    table["experts_picks_differ_share"] = [
        float(jnp.mean(ref["experts"][2] <= -limits["route_margin"])), limits["picks_differ_max"]]
    return table, all(v < limit for v, limit in table.values())


def moe8k_metrics(bench, run):
    """:data:`MOE8K_METRICS` read of a traced run as ``run.py`` reads an
    entered metric → {name: {"value", "unit"}}, a metric whose reader finds
    nothing left out."""
    out = {}
    for name in MOE8K_METRICS:
        with open(bench.path("layer_metrics", f"{name}.json")) as f:
            spec = json.load(f)
        module, _, attr = spec["reader"].partition(":")
        value = bench.load("readers", module.partition(".")[2], attr)(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def run(ctx):
    try:
        import deepspeed_tpu.models.mellum  # noqa: F401
    except ImportError as e:
        # a checkout from before the program had this model: fail at once, cleanly
        sys.exit(f"train_mellum: the program in this checkout cannot run this "
                 f"configuration ({e}) - nothing was run")
    config, seconds = ctx.config, ctx.seconds
    os.environ["DS_SEED"] = str(ctx.seed % (2 ** 31 - 1))
    import jax

    from deepspeed_tpu.utils import tracing

    clock = time.perf_counter
    n_dev = len(ctx.devices)
    ids = ctx.generate(vocab=config["model"]["vocab_size"])["ids"]
    sequences, seq_len = ids.shape
    if sequences % n_dev:
        raise ValueError(f"{sequences} sequences a step do not divide over {n_dev} chips")
    engine, model, cfg = build(ctx, ids)
    feed = (ids[None], ids[None])  # [gas=1, sequences, seq_len]: inputs and labels

    def step():
        return float(engine.train_batch(batch=feed))

    # step 1 compiles and makes the state; the reference then runs on the weights step 2
    # will start from (read before that step donates them), so step 2's loss and gradient
    # norm are the ones compared
    losses = [step()]
    log(f"[train] first step done at {ctx.age():.1f}s, loss {losses[0]:.4f}")
    from jax.sharding import NamedSharding, PartitionSpec as P
    placed = jax.device_put(ids, NamedSharding(engine.mesh, P("expert")))
    streams = given_streams(engine.params, placed, config)
    system = system_readings(engine, model, cfg, ids, streams, ctx.seed)
    system["streams"] = streams
    log(f"[train] the program's readings at {ctx.age():.1f}s")
    ref = reference_readings(engine.params, placed, config, system)
    log(f"[train] reference loss {ref['loss']:.4f}, gradient norm {ref['grad_norm']:.4f} "
        f"at {ctx.age():.1f}s")
    losses.append(step())
    system["loss"], system["grad_norm"] = losses[1], float(engine.global_grad_norm)
    table, agrees = compare(system, ref, config["reference"])
    log(f"[train] engine loss {losses[1]:.4f}, gradient norm {system['grad_norm']:.4f}; "
        f"check {json.dumps(table)} at {ctx.age():.1f}s")
    del system, ref, streams
    losses.append(step())
    compiles_before = ctx.meter.totals()

    # the profiler's file is kept until its scoped ops are read (readers/mellum.py)
    kept = ctx.keep_trace or (tempfile.mkdtemp(prefix="bench_mellum_") if ctx.trace else None)
    capture = trace.Capture(keep=kept) if ctx.trace else None
    t_open = clock()
    setup_s = ctx.age_at(t_open)
    steps, t_last = 0, t_open
    while t_last - t_open < seconds:
        if capture is not None and not capture.started and t_last - t_open >= seconds - TRACE_S:
            capture.start(clock)
        losses.append(step())
        steps += 1
        t_last = clock()
    if capture is not None:
        if not capture.started:  # a window shorter than the traced part
            capture.start(clock)
            losses.append(step())
        capture.stop(clock)
    elapsed = t_last - t_open
    compiled_in_run = ctx.meter.totals()["compiles"] - compiles_before["compiles"]
    device = ctx.describe_device()
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    records = [s for s in tracing.snapshot()["steps"]
               if s["kind"] == "train" and s["engine"] == engine.trace_id]
    counts = [s["counts"] for s in records if s["counts"]]
    beyond = sum(c["rows_beyond_passes"] for c in counts)
    mesh_shape = {k: int(v) for k, v in engine.mesh.shape.items()}
    engine.destroy()

    finite = all(math.isfinite(l) for l in losses)
    falling = losses[-1] < losses[0]
    counted = bool(counts) or n_dev == 1
    correct = bool(finite and falling and agrees and compiled_in_run == 0 and steps > 0
                   and beyond == 0 and counted)
    tokens = steps * sequences * seq_len
    facts = {"losses_first": losses[:4], "loss_last": losses[-1], "check": table,
             "steps": steps, "elapsed_s": elapsed, "params": int(n_params),
             "compiled_after_warm_up": compiled_in_run, "mesh": mesh_shape,
             "step_ms": elapsed / steps * 1e3 if steps else None,
             "step_counts_last": counts[-1] if counts else None,
             "rows_beyond_passes": beyond,
             # the steps in which some rank's held picks took more than a pass in some layer
             "steps_with_a_second_pass": sum(
                 c["n_share_passes"] > len(config["model"]["layer_types"]) for c in counts),
             "train_records": [{"start_ns": s["start_ns"], "end_ns": s["end_ns"],
                                "counts": s["counts"]} for s in records[-8:]],
             "moe8k_shapes": {"sequences": int(sequences), "seq_len": int(seq_len),
                              "chips": n_dev, "model": {k: config["model"][k] for k in (
                                  "hidden_size", "moe_intermediate_size", "num_attention_heads",
                                  "num_key_value_heads", "head_dim", "num_experts",
                                  "num_experts_per_tok", "vocab_size", "sliding_window",
                                  "layer_types")}}}
    observed = {"setup_s": setup_s,
                "train_tok_s_chip": tokens / elapsed / n_dev if steps else None,
                "compile_s": compiles_before["compile_s"] + compiles_before["trace_lower_s"]}
    result = {"correct": correct, "attempted": steps, "failed": 0 if finite else steps,
              "observed": observed, "device": device, "facts": facts,
              "trace": capture.trace if capture else None,
              "trace_window_s": capture.window_s if capture else None}
    if ctx.trace:
        path = os.path.join(kept, "trace.xplane.pb")
        if os.path.isfile(path):
            facts["moe_exchange"] = ctx.bench.load("readers", "mellum", "scoped_ops")(path)
        facts["largest_collectives"] = ctx.bench.load("readers", "mellum", "largest_collectives")(
            capture.trace)
        if not ctx.keep_trace:
            shutil.rmtree(kept, ignore_errors=True)
        facts["layer_metrics_moe8k"] = moe8k_metrics(ctx.bench, result)
    return result
