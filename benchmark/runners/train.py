"""Runner ``train``: ``deepspeed_tpu.initialize`` + ``engine.train_batch``
over every chip of the cell (ZeRO stage, precision and optimizer from the
configuration's ``trainer``), one seeded batch a step.

``train_batch`` returns after the step's outputs are on the host (it
reads the gradient norm), so a step that has returned is complete; the
loss is read inside the timed region as well. The window counts the steps
that completed inside it and ends with the step in flight when its
seconds are up; the rate divides by the time those steps took.
"""

import math
import os
import time

import numpy as np

from benchmark.harness import reference, trace
from benchmark.harness.device import log
from benchmark.runners.serve import llama_config

TRACE_S = 4.0


def run(ctx):
    config, seconds = ctx.config, ctx.seconds
    # the program seeds parameter initialisation from DS_SEED
    os.environ["DS_SEED"] = str(ctx.seed % (2 ** 31 - 1))
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import deepspeed_tpu
    from deepspeed_tpu.models import build_llama
    from deepspeed_tpu.parallel.topology import make_mesh_topology

    clock = time.perf_counter
    trainer = config["trainer"]
    n_dev = len(ctx.devices)
    batch = ctx.generate(vocab=config["model"]["vocab_size"])
    ids = batch["ids"]
    sequences, seq_len = ids.shape
    if sequences % n_dev:
        raise ValueError(f"{sequences} sequences a step do not divide over {n_dev} chips")
    model = build_llama(dataclasses.replace(
        llama_config(config["model"]), remat=trainer["remat"],
        remat_policy=trainer["remat_policy"],
        attention_impl="auto" if ctx.rehearse else "flash"))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=make_mesh_topology(data=n_dev, devices=ctx.devices),
        config={"train_batch_size": sequences,
                "train_micro_batch_size_per_gpu": sequences // n_dev,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": trainer["bf16"]},
                "optimizer": trainer["optimizer"],
                "zero_optimization": {"stage": trainer["zero_stage"]},
                "steps_per_print": 10 ** 9})
    feed = (ids[None], ids[None])  # [gas=1, sequences, seq_len]: inputs and labels

    def step():
        return float(engine.train_batch(batch=feed))

    # step 1 compiles and makes the state; the reference then runs on the
    # weights step 2 will start from, so step 2's loss is the one compared
    losses = [step()]
    log(f"[train] first step done at {ctx.age():.1f}s, loss {losses[0]:.4f}")
    placed = jax.device_put(ids, NamedSharding(engine.mesh, P("data")))
    want = float(reference.loss(engine.params, placed, config["model"]))
    losses.append(step())
    ref_err = abs(losses[1] - want)
    log(f"[train] reference loss {want:.4f}, engine {losses[1]:.4f} at {ctx.age():.1f}s")
    losses.append(step())
    compiles_before = ctx.meter.totals()

    capture = trace.Capture(keep=ctx.keep_trace) if ctx.trace else None
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
    engine.destroy()

    finite = all(math.isfinite(l) for l in losses)
    falling = losses[-1] < losses[0]
    agrees = ref_err < config["reference"]["tolerance"]
    correct = bool(finite and falling and agrees and compiled_in_run == 0 and steps > 0)
    tokens = steps * sequences * seq_len
    facts = {"losses_first": losses[:4], "loss_last": losses[-1], "reference_loss": want,
             "reference_abs_err": ref_err, "steps": steps, "elapsed_s": elapsed,
             "params": int(n_params), "compiled_after_warm_up": compiled_in_run,
             "step_ms": elapsed / steps * 1e3 if steps else None}
    observed = {"setup_s": setup_s,
                "train_tok_s_chip": tokens / elapsed / n_dev if steps else None,
                "compile_s": compiles_before["compile_s"] + compiles_before["trace_lower_s"]}
    return {"correct": correct, "attempted": steps, "failed": 0 if finite else steps,
            "observed": observed, "device": device, "facts": facts,
            "trace": capture.trace if capture else None,
            "trace_window_s": capture.window_s if capture else None}
