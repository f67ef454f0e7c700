#!/usr/bin/env python3
"""``control.py``'s recipe on the ``jamba2-3b`` configuration: the program's
reading and the controls', per seed, on the chip at the size the cell runs:

    python3 benchmark/tests/control_jamba.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a pool
just large enough for the check's sequences) and prints per seed what
``correct`` reads - the served logits against the float32 reference
(``harness/reference_jamba.py``; ``runners/serve_moonlight.py``
``summarize``, which ``runners/serve_jamba.py`` uses), every Mamba layer
alone (its output a row, the state and the convolution's tail it leaves)
and both attention layers alone - and, for the first ``--control`` seeds,
of the controls, each of which has to come out as not correct:

``float8``
    that reference with every matrix and vector of a layer, the embedding
    rows (the head is the embedding: tied) and the residual stream between
    layers rounded to float8 e4m3 with one scale a tensor, the arithmetic
    float32. It moves every position, and the logits fail it; and a Mamba
    mixer of it alone (``float8``'s ``mamba_layer``: the reference's mixer on
    float8 weights, on what the float32 reference's layer saw) leaves a
    state that fails the state's limit.
``state_bf16``
    the served Mamba layer with **its state carried in bfloat16**: what a
    slot pool of the stream's type would hold (rounded after every call, as
    a pool of that type rounds what is written to it). The Mamba layer
    alone has to fail it, by the state it leaves: that is what holds the
    configuration's float32 to its word.

Errors by position are written to ``chiprun_out/control_jamba.<seed>.json``
(too long for the output's end). A benchmark run never runs this;
``test_jamba_cell.py`` keeps it at debug size.
"""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import reference_jamba as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402

F8 = jnp.float8_e4m3fn
F32 = jnp.float32


def _rounded_layer(stack, layer, dtype):
    """Layer ``layer`` of a stack, every leaf rounded to ``dtype``, with a
    leading axis of one. **Not jitted**: op by op, the cast down and the cast
    back are programs of their own; inside one program the TPU compiler may
    keep the excess precision and drop the round trip (a Mamba mixer on
    weights "rounded" inside a jit read closer to the float32 reference than
    the bfloat16 program did: PERF.md, PR 45)."""
    return jax.tree.map(lambda x: _rounded(x[layer], dtype)[None], stack)


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_jamba.rows_at``'s rows in the next precision down."""
    mamba, attn = reference.layer_kwargs(model)
    m = params["model"]
    seen = dict.fromkeys(reference.STACKS, 0)
    zero = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(m["embed_tokens"], ids), dtype)
        for position, kind in enumerate(reference.layer_kinds(model)):
            low = _rounded_layer(m[reference.STACKS[kind]], jnp.int32(seen[kind]), dtype)
            if kind == reference.MAMBA:
                h = reference._mamba_layer(low, zero, h, **mamba)[0]
            else:
                h = reference._attention_layer(low, zero, h, **attn)[0]
            h = _rounded(h, dtype)
            h = reference._feed_forward(_rounded_layer(m["ffn"], jnp.int32(position), dtype), zero,
                                        h, eps=attn["eps"])
            h = jax.block_until_ready(_rounded(h, dtype))
            seen[kind] += 1
    return jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)


def mamba_rounded(params, layer, x, model, dtype):
    """Mamba layer ``layer``'s mixer of the reference on weights rounded to
    ``dtype``, alone, on the normalised x [S, D] from a zero start → (y [S,
    D], the state [N, I], the tail [K - 1, I])."""
    mamba, _ = reference.layer_kwargs(model)
    low = jax.tree.map(lambda w: w[0].astype(F32),
                       _rounded_layer(params["model"]["mamba_layers"], jnp.int32(layer), dtype))
    I = low["D"].shape[0]
    with jax.default_matmul_precision("highest"):
        y, state, tail = jax.jit(functools.partial(reference.mamba_mixer, **mamba))(
            low, jnp.asarray(x, F32)[None], jnp.zeros((1, mamba["state_size"], I), F32),
            jnp.zeros((1, mamba["kernel"] - 1, I), F32))
    return np.asarray(y[0]), np.asarray(state[0]), np.asarray(tail[0])


def measure(bench, config, seed, rehearse, control=True, prepare=None):
    """→ what ``correct`` reads of the program and, with ``control``, of
    each control against the same reference; errors by position too.
    ``prepare(engine)``: a test's hook, before anything is read of the
    engine."""
    runner = bench.load("runners", "serve_jamba", "run").__globals__
    check = runner["_check"]()
    # the check's own sequences need few blocks; the cell's pool is not under test here
    steps, block = config["reference"]["decode_steps"], config["engine"]["kv_block_size"]
    need = sum(-(-(n + steps) // block) + 1 for n in check.sample_lengths(config["reference"]))
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=need + 3))
    engine = runner["build_engine"](config, seed, rehearse)
    if prepare is not None:
        prepare(engine)
    params, model = engine.params, config["model"]
    by_position = lambda a: [[round(float(e), 5) for e in row] for row in a]  # noqa: E731
    out = {"seed": seed,
           "attention_impls": {str(k): v for k, v in engine.attention_impls.items()}}
    head8 = {"model": {"final_layernorm": params["model"]["final_layernorm"],
                       "embed_tokens": _rounded(params["model"]["embed_tokens"], F8)}}

    got = check.served_logits(engine, config, check.reference_sample(config, seed)[0])

    def program(first, ids, positions):
        return lambda i: got[first + i]

    def float8(first, ids, positions):
        rows = rows_rounded(params, ids, positions, model, F8)
        return lambda i: reference.head_at(head8, rows[i:i + 1], model)[0]

    tapped = runner["Tapped"](runner["longest_sample"](config["reference"]))
    check.reference_moonlight = tapped
    try:
        for name, read in (("program", program), ("float8", float8)):
            if name == "program" or control:
                errors, margins, _ = check.reference_errors(params, config, seed, read)
                out[name] = dict(check.summarize(errors, margins, config["reference"]),
                                 min=float(errors.min()))
                out[name + "_by_position"] = by_position(errors)
    finally:
        check.reference_moonlight = reference

    # the Mamba layers alone, on what the reference's saw of the longest sequence
    taps = [(runner["bf16_values"](x), y, state, tail) for x, y, state, tail in tapped.mamba]
    served = runner["served_mamba_layer"]
    layers = {"program": lambda layer, x: served(engine, config, layer, x),
              "state_bf16": lambda layer, x: served(engine, config, layer, x,
                                                    state_dtype=jnp.bfloat16),
              "float8": lambda layer, x: mamba_rounded(params, layer, x, model, F8)}
    for name, read in layers.items():
        if name == "program" or control:
            errors, states, tails = runner["mamba_layer_readings"](taps, read)
            out.setdefault(name, {})["mamba_layer"] = dict(
                runner["summarize_mamba_layer"](errors, states, tails, config["reference"]),
                states=[float(s) for s in states], tails=[float(t) for t in tails])

    # both attention layers alone
    errors = runner["attention_layer_errors"](
        [(runner["bf16_values"](x), y) for x, y in tapped.attn],
        lambda layer, x: runner["served_attention_layer"](engine, config, layer, x)[0])
    out["program"]["attention_layer"] = runner["summarize_attention_layer"](
        errors, config["reference"])
    engine.destroy()
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="jamba2-3b")
    parser.add_argument("--seed", type=int, action="append", required=True)
    parser.add_argument("--control", type=int, default=2,
                        help="run the controls for the first N seeds")
    args = parser.parse_args()
    from benchmark.harness import device, spec
    bench = spec.Benchmark(ROOT)
    device.require_devices(1)
    device.enable_compile_cache()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for i, seed in enumerate(args.seed):
        got = measure(bench, bench.config(args.config), seed, False, control=i < args.control)
        with open(os.path.join(ROOT, "chiprun_out", f"control_jamba.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps({k: v for k, v in got.items() if not k.endswith("_by_position")}),
              flush=True)


if __name__ == "__main__":
    main()
