#!/usr/bin/env python3
"""Bring-up smoke: the two main paths, end to end, on the chip.

    python3 chip_smoke.py          # one process, no arguments, no network

What it does, at the published width of the repo's ``mistral-7b`` preset
(hidden 4096 / FFN 14336 / 32 Q / 8 KV heads / head_dim 128 / vocab
32000 — no width is cut, only ``num_hidden_layers``, sized from the
device's own ``bytes_limit``), with weights random from a fixed seed:

1. **kernels** — each Pallas kernel on the two paths (flash attention
   fwd+bwd, fused RMSNorm, paged decode attention), alone, on a small
   input: its lowered program holds the Mosaic custom call and its
   result agrees with the kernel's own XLA reference.
2. **train** — ``deepspeed_tpu.initialize`` (ZeRO-3, bf16, Adam) over
   every device the process sees (``data=N``), then ``train_batch`` steps
   on one seeded batch at S=2048. Loss finite and falling, no recompile
   after the first steps, the step program holds the flash and RMSNorm
   kernels, and every ZeRO-3 parameter / master / moment leaf is split
   over all N devices.
3. **serve** — ``InferenceEngineV2`` + ``ServingGateway`` built as
   ``bin/ds_serve`` builds them (``tensor_parallel_degree=N``, the paged
   kernel pinned). Prefill-then-decode logits agree with the flax
   model's full forward; concurrent streaming requests with mixed prompt
   lengths all complete with the token count they asked for; the same
   prompt served twice gives the same greedy stream; ``drain()`` returns.

Nothing here falls back: no TPU is an error before any work, a kernel
pin that cannot be honoured raises, and any phase that raises makes the
exit code non-zero. The printed numbers are facts about this run
(versions, bytes, seconds), not metrics. The last line of stdout is
``{"ok": true, "device": {...}}`` with the device as JAX reports it.
"""

import dataclasses
import gc
import json
import math
import os
import re
import sys
import threading
import time

MODEL = "mistral-7b"
# bf16 params + fp32 master + two fp32 Adam moments + fp32 grads
TRAIN_BYTES_PER_PARAM = 18
# Shares of each device's bytes_limit the resident state may take; the
# rest is for activations, the gathered layer, logits and the KV pool.
# Corrected from memory_stats() on a v5e (PERF.md, Bring-up): a training
# step peaked near 14 bytes a parameter, a serving engine at its weights
# plus the pool.
TRAIN_STATE_SHARE = 0.75
SERVE_WEIGHT_SHARE = 0.5


class SmokeFailure(AssertionError):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Everything that sizes a run; :func:`plan_for` derives it from the
    devices (a drive of the phases on the CPU passes a debug-size one)."""
    preset: str
    train_layers: int
    serve_layers: int
    seq_len: int = 2048
    micro_batch: int = 2          # sequences per device per step
    train_steps: int = 4
    token_budget: int = 512       # SplitFuse budget: longer prompts are split
    prompt_lens: tuple = (32, 64, 128, 256, 384, 512, 640, 768, 896, 1024)
    new_tokens: tuple = (32, 48, 64)
    ref_len: int = 32             # tokens of the logits-vs-reference check


def plan_for(devices):
    """Depth from what the devices report: the deepest model whose
    resident state fits the stated share of ``bytes_limit`` summed over
    the devices, capped at the published depth."""
    from deepspeed_tpu.models.llama import LLAMA_CONFIGS
    cfg = LLAMA_CONFIGS[MODEL]
    limit = min(d.memory_stats()["bytes_limit"] for d in devices) * len(devices)
    kv_width = cfg.num_key_value_heads * cfg.head_dim
    per_layer = (2 * cfg.hidden_size * (cfg.hidden_size + kv_width)   # q, o, k, v
                 + 3 * cfg.hidden_size * cfg.intermediate_size        # gate, up, down
                 + 2 * cfg.hidden_size)                               # two norms
    outside = 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size  # embed, head, norm

    def depth(share, bytes_per_param):
        layers = int((share * limit / bytes_per_param - outside) // per_layer)
        return max(1, min(cfg.num_hidden_layers, layers))

    return Plan(preset=MODEL,
                train_layers=depth(TRAIN_STATE_SHARE, TRAIN_BYTES_PER_PARAM),
                serve_layers=depth(SERVE_WEIGHT_SHARE, 2))


def compile_totals():
    """What the program's own recorder counted of JAX's compile events so far
    (``utils/tracing.process_counters``: backend compiles, and seconds tracing,
    lowering and compiling or reading a program back from the persistent
    cache). Programs compile on the serving pump thread too."""
    from deepspeed_tpu.utils import tracing
    _, _, compile_ns, compiles = tracing.process_counters()
    return {"compiles": compiles, "compile_s": round(compile_ns / 1e9, 2)}


def resident_bytes(devices, what):
    """``bytes_in_use`` per device, which must be even: state that all
    landed on the first device is the failure this looks for."""
    used = [d.memory_stats()["bytes_in_use"] for d in devices]
    check(max(used) - min(used) <= 0.2 * max(used),
          f"{what}: bytes_in_use differs by more than 20% across devices: {used}")
    return used


def mosaic_kernels(lowered):
    """Names of the Mosaic (compiled Pallas) kernels in a lowered
    program: each is a ``tpu_custom_call`` carrying its ``kernel_name``.
    An interpreted kernel or an XLA reference leaves no such call."""
    text = lowered.as_text()
    return sorted(set(re.findall(r'@tpu_custom_call\(.*?kernel_name = "(\w+)"', text)))


def rel_err(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def run_phase(name, devices, fn):
    before = compile_totals()
    t0 = time.perf_counter()
    facts = fn()
    facts["wall_s"] = round(time.perf_counter() - t0, 1)
    facts.update({k: round(v - before[k], 2) for k, v in compile_totals().items()})
    # the process's high-water mark so far: a later phase shows here only if it went higher
    facts["peak_bytes_in_use"] = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(f"[{name}] {json.dumps(facts)}", flush=True)
    return facts


# --------------------------------------------------------------------- kernels
def kernels_phase(cfg):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.model_runner import _rms as xla_rms_norm
    from deepspeed_tpu.models.llama import einsum_attention
    from deepspeed_tpu.ops.pallas import default_interpret
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.fused_norms import fused_rms_norm
    from deepspeed_tpu.ops.pallas.paged_attention import (paged_decode_attention,
                                                          xla_paged_attention)

    check(not default_interpret(), "kernels would run interpreted by default on this backend")
    rng = np.random.default_rng(0)
    H, Hkv, Dh, D = (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
                     cfg.hidden_size)
    facts = {}

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)

    def run(name, fn, ref, args, kernels, tol):
        lowered = jax.jit(fn).lower(*args)
        found = mosaic_kernels(lowered)
        check(set(kernels) <= set(found),
              f"{name}: lowered program holds Mosaic kernels {found}, expected {kernels}")
        err = max(rel_err(g, w) for g, w in zip(jax.tree.leaves(lowered.compile()(*args)),
                                                jax.tree.leaves(jax.jit(ref)(*args))))
        check(err < tol, f"{name}: relative error {err:.3e} vs its XLA reference (tol {tol})")
        facts[name] = {"mosaic": found, "rel_err": float(f"{err:.3e}")}

    # flash attention, forward and backward; 640 is not a block multiple
    q, k, v, w = (normal(2, 640, 4, Dh) for _ in range(4))

    def attn_grads(attend):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))
        return lambda q, k, v: jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    run("flash_attention",
        attn_grads(lambda q, k, v: flash_attention(q, k, v, causal=True, force_pallas=True)),
        attn_grads(lambda q, k, v: einsum_attention(q, k, v, causal=True)),
        (q, k, v), ("flash_attention_fwd", "flash_attention_dkv", "flash_attention_dq"), 3e-2)

    # fused RMSNorm at the model's hidden size, against the serving path's
    # XLA RMSNorm; 300 rows pad the row tile
    run("fused_rms_norm", lambda x, s: fused_rms_norm(x, s, 1e-5),
        lambda x, s: xla_rms_norm(x, s, 1e-5), (normal(300, D), normal(D)),
        ("fused_rms_norm",), 1e-2)

    # paged decode attention at the model's head geometry: 24 tokens at
    # assorted positions over 8-block tables into layer 1 of a 3-layer,
    # 64-block pool
    T, L, NB, bs, MB = 24, 3, 64, 16, 8
    qd, kc, vc = normal(T, H, Dh), normal(L, NB, bs, Hkv * Dh), normal(L, NB, bs, Hkv * Dh)
    tabs = jnp.asarray(rng.integers(1, NB, (T, MB)), jnp.int32)
    pos = jnp.asarray(rng.integers(0, MB * bs, (T,)), jnp.int32)
    run("paged_decode_attention", paged_decode_attention, xla_paged_attention,
        (qd, kc, vc, tabs, pos, jnp.int32(1)), ("paged_decode_attention",), 1e-2)
    return facts


# ----------------------------------------------------------------------- train
def check_zero3_sharded(engine, n_dev):
    """What "sharded" means: each leaf lives on all N devices and each
    device holds 1/N of it — every fp32 master and Adam-moment leaf, and
    every parameter at or above the policy's persistence threshold
    (smaller ones are ZeRO-3's persistent, replicated parameters)."""
    import jax
    threshold = engine.sharding_policy.param_persistence_threshold
    trees = {"params": [x for x in jax.tree.leaves(engine.params) if x.size >= threshold],
             "master_params": jax.tree.leaves(engine.master_params),
             "opt_state": [x for x in jax.tree.leaves(engine.opt_state) if x.ndim > 0]}
    counts = {}
    for name, leaves in trees.items():
        check(leaves, f"ZeRO-3 {name}: no leaves to check")
        for leaf in leaves:
            shards = leaf.addressable_shards
            held = {s.device for s in shards}
            check(len(held) == n_dev and all(s.data.size * n_dev == leaf.size for s in shards),
                  f"ZeRO-3 {name} leaf {leaf.shape} is not split over {n_dev} devices: "
                  f"{len(held)} devices, shard shapes {[s.data.shape for s in shards]}")
        counts[name] = len(leaves)
    return counts


def train_phase(plan, devices):
    import numpy as np

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import build_llama
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import make_mesh_topology
    from deepspeed_tpu.utils import tracing

    n_dev = len(devices)
    model = build_llama(plan.preset, num_hidden_layers=plan.train_layers,
                        attention_impl="flash")
    config = {
        "train_batch_size": plan.micro_batch * n_dev,
        "train_micro_batch_size_per_gpu": plan.micro_batch,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 3e-4}},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=config, mesh=make_mesh_topology(data=n_dev, devices=devices))
    ids = np.random.default_rng(0).integers(
        0, model.config.vocab_size, (1, plan.micro_batch * n_dev, plan.seq_len), dtype=np.int32)

    losses, step_s, step_seqs = [], [], []
    for _ in range(plan.train_steps):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch=(ids, ids))))
        step_s.append(round(time.perf_counter() - t0, 3))
        step_seqs.append(tracing.RECORDER.steps[-1].seq)      # the step's own `train` record
    # the recorder's events say which step compiled what
    compiled = [e for e in tracing.snapshot()["events"]
                if e["kind"] == "compile" and e["name"] == "backend_compile_duration"]
    step_programs = [[e["program"] for e in compiled if e["seq"] == seq] for seq in step_seqs]
    check(all(math.isfinite(l) for l in losses), f"non-finite training loss: {losses}")
    # unit-variance logits over V classes: ln V plus about a half
    check(abs(losses[0] - math.log(model.config.vocab_size)) < 1.5,
          f"first loss {losses[0]:.3f} is not that of a random init "
          f"(ln {model.config.vocab_size} = {math.log(model.config.vocab_size):.3f})")
    check(losses[-1] < losses[0], f"loss did not fall on a repeated batch: {losses}")
    # the fused step train_batch just ran; it compiles once, in the first step
    step_fn, _ = engine._train_batch_fn()
    step_name = f"jit({step_fn.__name__})"
    check(step_programs[0].count(step_name) == 1
          and not any(step_name in later for later in step_programs[1:]),
          f"the train step {step_name} did not compile exactly once, in the first step: "
          f"programs compiled per step {step_programs}")
    # ...lowered again on the same arguments, to read its kernels
    lowered = step_fn.lower(engine.params, engine.master_params, engine.opt_state,
                            engine.scaler_state, jnp.float32(0), engine._dropout_rng,
                            engine._shard_batch(((ids, ids), {}), extra_leading=1))
    kernels = mosaic_kernels(lowered)
    expected = {"fused_rms_norm", "flash_attention_fwd", "flash_attention_dkv",
                "flash_attention_dq"}
    check(expected <= set(kernels),
          f"train step holds Mosaic kernels {kernels}, expected at least {sorted(expected)}")

    spread = check_zero3_sharded(engine, n_dev)
    resident = resident_bytes(devices, "ZeRO-3 state after training")
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    engine.destroy()
    del engine, lowered, step_fn
    groups.destroy_mesh()
    gc.collect()
    return {"layers": plan.train_layers, "params": int(n_params), "mesh": {"data": n_dev},
            "batch": [plan.micro_batch * n_dev, plan.seq_len], "losses": losses,
            "step_s": step_s, "step_compiles": [len(p) for p in step_programs],
            "mosaic": kernels,
            "zero3_leaves_split": spread, "resident_bytes": resident}


# ----------------------------------------------------------------------- serve
def serve_phase(plan, devices):
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig, InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.models import build_llama
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.serving import ServingConfig, ServingGateway

    n_dev = len(devices)
    model = build_llama(plan.preset, num_hidden_layers=plan.serve_layers, remat=False)
    check(model.config.num_key_value_heads % n_dev == 0,
          f"{n_dev} devices do not divide {model.config.num_key_value_heads} KV heads")
    n_requests = len(plan.prompt_lens)
    max_new = max(plan.new_tokens)
    pinned = "pallas_paged" if n_dev == 1 else "pallas_paged_sharded"
    engine = InferenceEngineV2(model=model, config=RaggedInferenceEngineConfig(
        tensor_parallel_degree=n_dev,
        kv_block_size=16,
        implementation_overrides={"attention": pinned},
        state_manager=DSStateManagerConfig(
            max_ragged_batch_size=plan.token_budget,
            max_ragged_sequence_count=max(16, n_requests),
            max_tracked_sequences=max(16, n_requests),
            max_context=max(plan.prompt_lens) + max_new)))
    n_params = sum(x.size for x in jax.tree.leaves(engine.params))
    if n_dev > 1:
        check("tensor" in engine.kv_cache.k.sharding.spec,
              f"KV pool is not sharded over 'tensor': {engine.kv_cache.k.sharding}")
        for leaf in (engine.kv_cache.k, engine.kv_cache.v):
            check(len({s.device for s in leaf.addressable_shards}) == n_dev,
                  "KV pool does not live on every device")
    resident = resident_bytes(devices, "serving weights and KV pool")

    rng = np.random.default_rng(1)
    vocab = model.config.vocab_size

    # Prefill, then one decode step through the cache, against the flax
    # model's full forward on the same weights. Logits, not tokens: with
    # random weights the largest logit changes on rounding.
    ids = rng.integers(0, vocab, plan.ref_len, dtype=np.int32)
    if engine.mesh is not None:
        groups.set_mesh(engine.mesh)  # the model reads its layout from the global mesh
    want = np.asarray(jax.jit(lambda p, x: model.apply({"params": p}, x))(
        engine.params, jnp.asarray(ids)[None]), np.float32)[0]
    groups.destroy_mesh()
    prefill = engine.put([-1], [ids[:-1]])[0]
    decode = engine.put([-1], [ids[-1:]])[0]
    engine.flush(-1)
    ref_err = {"prefill": rel_err(prefill, want[-2]), "decode": rel_err(decode, want[-1])}
    check(np.isfinite(prefill).all() and np.isfinite(decode).all(), "non-finite served logits")
    # bf16 weights and activations on both sides, different op order
    check(max(ref_err.values()) < 5e-2,
          f"served logits disagree with the model's forward: relative error {ref_err}")

    gateway = ServingGateway(engine, config=ServingConfig(default_max_new_tokens=max_new))
    prompts = [rng.integers(0, vocab, n, dtype=np.int32) for n in plan.prompt_lens]
    wanted = [plan.new_tokens[i % len(plan.new_tokens)] for i in range(n_requests)]
    streams, errors = {}, []

    def client(i):
        try:
            handle = gateway.submit(prompts[i], max_new_tokens=wanted[i], priority=i % 3)
            streams[i] = (handle, list(handle.tokens(timeout=600)))
        except BaseException as e:  # surfaced below: a dead pump fails the smoke
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    if errors:
        raise errors[0]
    check(not any(t.is_alive() for t in threads), "a streaming client is still waiting")
    concurrent_s = round(time.perf_counter() - t0, 1)
    for i, (handle, toks) in sorted(streams.items()):
        check(handle.status == "completed" and len(toks) == wanted[i],
              f"request {i} (prompt {plan.prompt_lens[i]}): status {handle.status}, "
              f"{len(toks)} tokens of {wanted[i]}")
        check(all(0 <= t < vocab for t in toks), f"request {i}: token outside the vocabulary")

    # the same prompt twice, each time alone, so that both runs take the
    # same schedule through the same programs: the streams must be equal
    twice = [list(gateway.submit(prompts[2], max_new_tokens=wanted[0]).tokens(timeout=600))
             for _ in range(2)]
    check(twice[0] == twice[1] and len(twice[0]) == wanted[0],
          f"one prompt, two greedy streams: {twice[0][:8]}… vs {twice[1][:8]}…")

    impls = engine.attention_impls
    check(impls and set(impls.values()) == {pinned}
          and {engine.max_tokens, engine.max_seqs} <= set(impls),
          f"attention implementations by program token count: {impls}, pinned {pinned}")
    gateway.drain()
    snap = gateway.snapshot()
    counters = snap["counters"]
    check(snap["state"] == "stopped", f"gateway state after drain: {snap['state']}")
    check(counters["failed"] == 0 and counters["completed"] == n_requests + 2,
          f"gateway counters: {counters}")
    return {"layers": plan.serve_layers, "params": int(n_params), "mesh": {"tensor": n_dev},
            "resident_bytes": resident, "attention_impls": {str(k): v for k, v in impls.items()},
            "logits_rel_err_vs_model": {k: float(f"{v:.3e}") for k, v in ref_err.items()},
            "requests": n_requests + 2, "prompt_lens": list(plan.prompt_lens),
            "tokens_generated": counters["tokens_generated"],
            "engine_steps": counters["engine_steps"], "concurrent_wall_s": concurrent_s}


# ------------------------------------------------------------------------ main
def require_tpu(who):
    """→ the devices, all TPU; any other platform ends the process non-zero
    before any work, naming what was found."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"{who}: this runs on a TPU; JAX found platform {platform!r} "
                 f"({devices[0].device_kind} x{len(devices)}) — nothing was run")
    return devices


def main():
    import jax
    devices = require_tpu("chip_smoke")
    platform = devices[0].platform

    import jaxlib
    import numpy as np

    from deepspeed_tpu.models.llama import LLAMA_CONFIGS
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not importable"

    # one host<->device scalar round trip: dispatch a trivial program, wait, read it back
    bump = jax.jit(lambda v: v + 1)
    x = jax.device_put(np.float32(0))
    float(bump(x))
    trips = []
    for _ in range(20):
        t0 = time.perf_counter()
        float(bump(x))
        trips.append(time.perf_counter() - t0)
    plan = plan_for(devices)
    print("[device] " + json.dumps({
        **device, "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version, "bytes_limit": devices[0].memory_stats()["bytes_limit"],
        "scalar_round_trip_ms": round(sorted(trips)[len(trips) // 2] * 1e3, 3),
        "compile_cache": cache_dir,
        "compile_cache_entries_at_start": len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        "plan": dataclasses.asdict(plan)}), flush=True)

    t0 = time.perf_counter()
    run_phase("kernels", devices, lambda: kernels_phase(LLAMA_CONFIGS[plan.preset]))
    run_phase("train", devices, lambda: train_phase(plan, devices))
    run_phase("serve", devices, lambda: serve_phase(plan, devices))
    print("[total] " + json.dumps({"wall_s": round(time.perf_counter() - t0, 1),
                                   **compile_totals()}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
