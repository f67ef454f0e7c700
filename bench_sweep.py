"""Perf experiment harness for the north-star config (not the driver bench).

Every invocation appends its experiments to ``bench_sweep_results.json``
(one JSON dict per run: argv, per-experiment metrics) so sweep numbers
survive the scrollback.  ``--trace PATH.trace.jsonl`` replays a recorded
serving trace (see deepspeed_tpu.autotuning) through a gateway built
from the ambient DS_* / DS_AUTOTUNE_CONFIG environment instead of
running a training sweep — the serving-side twin of the MFU lanes.

Like ``bench.py`` it measures the chip only: no TPU is an error before
any work, the peak comes from ``bench.PEAK_FLOPS`` by ``device_kind``,
and an experiment that raised is recorded and makes the exit code
non-zero.
"""

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import _peak_flops, _require_tpu

RESULTS_PATH = os.environ.get("BENCH_SWEEP_RESULTS_PATH",
                              "bench_sweep_results.json")
RESULTS = []  # every run()/run_trace() appends one record


def _flush_results():
    """Write this invocation's records alongside the printed lines;
    exit non-zero if any experiment raised."""
    if not RESULTS:
        return
    payload = {"argv": sys.argv[1:], "results": RESULTS}
    with open(RESULTS_PATH, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"results -> {RESULTS_PATH}")
    failed = [r["name"] for r in RESULTS if "error" in r]
    if failed:
        sys.exit(f"bench_sweep: {len(failed)} experiment(s) raised: {failed}")


def run(name, *, hidden=1536, inter=4096, layers=16, heads=16, B=4, S=2048,
        stage=3, remat=True, remat_policy="full", attention_impl="auto",
        steps=6, warmup=2, gas=1):
    import deepspeed_tpu
    from deepspeed_tpu.models import build_llama
    from deepspeed_tpu.parallel import groups
    groups.destroy_mesh()

    model = build_llama("160m", hidden_size=hidden, intermediate_size=inter,
                        num_hidden_layers=layers, num_attention_heads=heads,
                        num_key_value_heads=heads, max_position_embeddings=max(2048, S),
                        remat=remat, remat_policy=remat_policy,
                        attention_impl=attention_impl)
    config = {
        "train_batch_size": B * gas,
        "train_micro_batch_size_per_gpu": B,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": stage},
        "steps_per_print": 1000000,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, model.config.vocab_size,
                                  size=(B * gas, S)).astype(np.int32))
    try:
        for _ in range(warmup):
            engine.train_batch(batch=(ids, ids))
        jax.block_until_ready(engine.params)
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            engine.train_batch(batch=(ids, ids))
            jax.block_until_ready(engine.params)
            times.append(time.perf_counter() - t0)
        dt = min(times)
    except Exception as e:
        # recorded so the rest of the sweep still runs; the invocation
        # exits non-zero (see _flush_results)
        print(f"{name}: FAILED {type(e).__name__}: {str(e)[:160]}")
        RESULTS.append({"name": name,
                        "error": f"{type(e).__name__}: {str(e)[:160]}"})
        return None
    n_params = int(sum(np.prod(x.shape) for x in jax.tree.leaves(engine.params)))
    tokens = B * gas * S
    dense = 6.0 * n_params * tokens
    attn = 12.0 * layers * tokens * S * hidden
    peak = _peak_flops(jax.devices()[0])
    mfu = (dense + attn) / dt / peak
    print(f"{name}: params={n_params/1e6:.0f}M step={dt*1e3:.1f}ms "
          f"tok/s={tokens/dt:,.0f} MFU={mfu:.3f} (dense-only {dense/dt/peak:.3f})")
    RESULTS.append({"name": name, "params": n_params,
                    "step_ms": round(dt * 1e3, 2),
                    "tok_s": round(tokens / dt, 1), "mfu": round(mfu, 4)})
    return mfu


def run_trace(path):
    """Replay a recorded ``.trace.jsonl`` through a gateway built from
    the ambient environment (DS_* knobs + optional DS_AUTOTUNE_CONFIG),
    so a sweep can score env/tuned-config variants against the same
    real traffic the offline tuner searched."""
    from deepspeed_tpu.autotuning import ServingTrace, replay_lockstep
    from deepspeed_tpu.inference.structured import byte_vocab
    from deepspeed_tpu.inference.v2 import (DSStateManagerConfig,
                                            InferenceEngineV2,
                                            RaggedInferenceEngineConfig,
                                            StructuredConfig)
    from deepspeed_tpu.models import build_llama
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.serving import ServingConfig, ServingGateway

    trace = ServingTrace.load(path)
    s = trace.summary()
    # v3 traces may carry per-request sampling specs and raw schemas;
    # schemas need the constrained-decoding slabs plus a tokenizer
    # surface (byte vocab here — real deployments pass their own
    # token_strings) recompiled against THIS config's vocab
    constrained = any(getattr(r, "schema", None) is not None for r in trace)
    groups.destroy_mesh()
    need_ctx = int(s["mean_prompt_len"] + s["mean_max_new"]) * 4
    model = build_llama("7b", hidden_size=3072, intermediate_size=8192,
                        num_hidden_layers=22, num_attention_heads=24,
                        num_key_value_heads=8,
                        max_position_embeddings=2048,
                        vocab_size=32000, remat=False)
    block, n_seqs, batch, vocab = 32, 16, 512, 32000
    max_ctx = max(block * 4, -(-need_ctx // block) * block)
    engine = InferenceEngineV2(
        model=model,
        config=RaggedInferenceEngineConfig(
            kv_block_size=block,
            structured=StructuredConfig(enabled=constrained),
            state_manager=DSStateManagerConfig(
                max_ragged_batch_size=batch,
                max_ragged_sequence_count=n_seqs,
                max_tracked_sequences=n_seqs,
                max_context=max_ctx)))
    # ServingGateway applies DS_AUTOTUNE_CONFIG (if set) on top of the
    # defaults, so `DS_AUTOTUNE_CONFIG=tuned.json bench_sweep --trace t`
    # scores exactly what the offline tuner shipped
    scfg = ServingConfig(
        token_strings=byte_vocab(vocab) if constrained else None,
        # constrained lanes stop at the schema's accept states; without
        # an EOS id the DFA would have no legal token there
        eos_token_id=2 if constrained else None)
    gw = ServingGateway(engine, config=scfg, auto_start=False)
    report = replay_lockstep(gw, trace)
    rec = {"name": f"trace:{os.path.basename(path)}", "trace": s,
           "serving_config": {
               k: getattr(gw.config, k)
               for k in ("token_budget", "max_burst", "max_queue_depth")},
           "gen_tok_s": round(report.gen_tok_s, 1),
           "p50_ttft_ms": report.p50_ttft_ms,
           "p99_ttft_ms": report.p99_ttft_ms,
           "completed": report.completed}
    print(f"{rec['name']}: {len(trace)} reqs gen_tok_s={rec['gen_tok_s']} "
          f"p99_ttft_ms={rec['p99_ttft_ms']} cfg={rec['serving_config']}")
    RESULTS.append(rec)
    gw.drain()
    return rec


if __name__ == "__main__":
    _require_tpu("bench_sweep.py")
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        if i + 1 >= len(sys.argv):
            sys.exit("--trace requires a .trace.jsonl path")
        run_trace(sys.argv[i + 1])
        _flush_results()
        sys.exit(0)
    which = sys.argv[1] if len(sys.argv) > 1 else "sweep"
    if which == "sweep":
        run("A: r01 config (zero1,remat-full)", stage=1, steps=8)
        run("C: zero1 dots remat", stage=1, remat_policy="dots", steps=8)
        run("D: zero3 dots", stage=3, remat_policy="dots", steps=8)
        run("B: zero3 no remat", stage=3, remat=False, steps=8)
        run("E: zero3 dots B=8", stage=3, remat_policy="dots", B=8, steps=8)
    elif which == "base":
        run("A: r01 config (zero1,remat-full)", stage=1)
    elif which == "noremat":
        run("B: no remat", remat=False)
    elif which == "dots":
        run("C: dots remat", remat_policy="dots")
    elif which == "z3":
        run("D: zero3 dots", stage=3, remat_policy="dots")
    elif which == "b8":
        run("E: zero3 dots B=8", stage=3, remat_policy="dots", B=8)
    elif which == "big":
        run("F: ~1B zero3 dots", hidden=2048, inter=5504, layers=20, heads=16,
            stage=3, remat_policy="dots")
    elif which == "einsum":
        run("G: einsum attention dots", remat_policy="dots", attention_impl="einsum")
    elif which == "gas":
        # r4 finding: the fused-scan dispatch amortization keeps paying
        # past gas=32 (0.548 @32 -> 0.563 @64 -> 0.568 @128); S=4096
        # regressed (0.536 — flash runs the longer rows less efficiently).
        # r4 late sweep (post recompile-fix, warmup=2): gas=192 -> 0.572
        # (+0.4pp for a 54.6s step); gas=256 crashed the TPU worker
        # ("worker process crashed or restarted" — likely a step-duration
        # watchdog at ~73s). Headline stays gas=128: the marginal MFU is
        # not worth a step time that flirts with the watchdog.
        run("H0: B4 S2048 gas32 dots z3", stage=3, remat_policy="dots",
            B=4, S=2048, gas=32, steps=3, warmup=1)
        run("H3: B4 S2048 gas64 dots z3", stage=3, remat_policy="dots",
            B=4, S=2048, gas=64, steps=3, warmup=1)
        run("H5: B4 S2048 gas128 dots z3", stage=3, remat_policy="dots",
            B=4, S=2048, gas=128, steps=2, warmup=1)
    _flush_results()
