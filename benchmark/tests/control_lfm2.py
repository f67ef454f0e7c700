#!/usr/bin/env python3
"""``control.py``'s recipe on the ``lfm2-24b-a2b-10l`` configuration: the
program's reading and the controls', per seed, on the chip at the size the
cell runs:

    python3 benchmark/tests/control_lfm2.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a
pool just large enough for the check's sequences) and prints per seed what
``correct`` reads - the served logits against the float32 reference
(``harness/reference_lfm2.py``; ``runners/serve_moonlight.py``
``summarize``, which ``runners/serve_lfm2.py`` uses), every ``conv``
operator alone (its output a row and the tail it leaves), every attention
operator alone and every expert feed-forward alone - and, for the first
``--control`` seeds, of the controls, each of which has to come out as not
correct:

``float8``
    that reference with every matrix and vector of a layer, the embedding
    rows (the head too: it is tied) and the residual stream between layers
    rounded to float8 e4m3 with one scale a tensor, the arithmetic float32.
    It moves every position, and the logits fail it in every tier.
``tails_dropped``
    the served ``conv`` operator with **its tail lost at every chunk
    boundary** (the slot pool zeroed after every call). The ``conv``
    operator alone has to fail it, by the two rows that follow each
    boundary and by the tail it leaves.
``kv_float8``
    the served attention operator with **its keys and values held in
    float8 e4m3** (the pools rounded between calls). The attention
    operator alone has to fail it.
``pick_left_out``
    that reference's expert feed-forward with **one pick a token left
    out** (the largest-weighted one: what a grouped matmul does that drops
    a row). The expert layer alone has to fail it.

Errors and margins by position are written to
``chiprun_out/control_lfm2.<seed>.json`` (too long for the output's end).
A benchmark run never runs this; ``test_lfm2_cell.py`` keeps it at debug
size.
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

from benchmark.harness import reference_lfm2 as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402

F8 = jnp.float8_e4m3fn


@functools.partial(jax.jit, static_argnames=("dtype",))
def _rounded_layer(stack, layer, dtype):
    return jax.tree.map(lambda x: _rounded(x[layer], dtype)[None], stack)


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_lfm2.rows_at``'s rows in the next precision down."""
    eps, attn, moe = reference.layer_kwargs(model)
    m = params["model"]
    n_dense = int(model["num_dense_layers"])
    seen = {reference.CONV: 0, reference.ATTENTION: 0}
    zero = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(m["embed_tokens"], ids), dtype)
        for t, kind in enumerate(model["layer_types"]):
            stack = m["conv_layers" if kind == reference.CONV else "attn_layers"]
            low = _rounded_layer(stack, jnp.int32(seen[kind]), dtype)
            if kind == reference.CONV:
                h = reference._conv_layer(low, zero, h, eps=eps)[0]
            else:
                h = reference._attention_layer(low, zero, h, **attn)[0]
            seen[kind] += 1
            h = jax.block_until_ready(_rounded(h, dtype))
            if t < n_dense:
                low = _rounded_layer(m["dense_ffn"], jnp.int32(t), dtype)
                h = reference._dense_ffn(low, zero, h, eps=eps)
            else:
                low = _rounded_layer(m["moe_ffn"], jnp.int32(t - n_dense), dtype)
                x = reference._ffn_norm(low, zero, h, eps=eps)
                h = h + reference._experts(low, zero, x, **moe)[0]
            h = jax.block_until_ready(_rounded(h, dtype))
    return jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)


def router_pick_left_out(x, gate, **kw):
    """The reference's router with the largest-weighted pick of every
    token given no weight."""
    weights, margin = reference._router(x, gate, **kw)
    largest = jnp.argmax(weights, axis=-1)
    return jnp.where(jnp.arange(weights.shape[-1]) == largest[..., None], 0.0, weights), margin


def measure(bench, config, seed, rehearse, control=True, prepare=None):
    """→ what ``correct`` reads of the program and, with ``control``, of
    each control against the same reference; errors and margins by
    position too. ``prepare(engine)``: a test's hook, before anything is
    read of the engine."""
    runner = bench.load("runners", "serve_lfm2", "run").__globals__
    check, experts = runner["_check"](), runner["_expert_check"]()
    # the check's own sequences need few blocks; the cell's pool is not under test here
    steps, block = config["reference"]["decode_steps"], config["engine"]["kv_block_size"]
    need = sum(-(-(n + steps) // block) + 1 for n in check.sample_lengths(config["reference"]))
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=need + 3))
    engine = runner["build_engine"](config, seed, rehearse)
    if prepare is not None:
        prepare(engine)
    params, model = engine.params, config["model"]
    by_position = lambda a: [[round(float(e), 5) for e in row] for row in a]  # noqa: E731
    out = {"seed": seed}
    head8 = {"model": {"embedding_norm": params["model"]["embedding_norm"],
                       "embed_tokens": _rounded(params["model"]["embed_tokens"], F8)}}

    got = check.served_logits(engine, config, check.reference_sample(config, seed)[0])
    out["attention_impls"] = {str(k): v for k, v in engine.attention_impls.items()}

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
                out["margins"] = by_position(margins)
    finally:
        check.reference_moonlight = reference

    # the operators alone, on what the reference's saw of the longest sequence (the first pass)
    n_conv = model["layer_types"].count(reference.CONV)
    n_attn = model["layer_types"].count(reference.ATTENTION)
    bf16 = runner["bf16_values"]
    taps = [(bf16(x), y, tail) for x, y, tail in tapped.conv[:n_conv]]
    served = runner["served_conv_layer"]
    layers = {"program": lambda layer, x: served(engine, config, layer, x),
              "tails_dropped": lambda layer, x: served(engine, config, layer, x, drop_tails=True)}
    for name, read in layers.items():
        if name == "program" or control:
            errors, tails = runner["conv_layer_readings"](taps, read)
            out.setdefault(name, {})["conv_layer"] = dict(
                runner["summarize_conv_layer"](errors, tails, config["reference"]),
                tails=[float(t) for t in tails])

    taps = [(bf16(x), y) for x, y in tapped.attn[:n_attn]]
    served = runner["served_attention_layer"]
    layers = {"program": lambda layer, x: served(engine, config, layer, x)[0],
              "kv_float8": lambda layer, x: served(engine, config, layer, x, pool_dtype=F8)[0]}
    for name, read in layers.items():
        if name == "program" or control:
            errors = runner["attention_layer_errors"](taps, read)
            out.setdefault(name, {})["attention_layer"] = dict(
                runner["summarize_attention_layer"](errors, config["reference"]),
                min=float(errors.min()))

    # the expert feed-forwards alone, on what the reference's saw at the compared positions
    def control_layers(router):
        def read(x):
            return np.stack([np.asarray(reference.experts_at(
                params, l, jnp.asarray(x[l])[None], model, router=router)[0])[0]
                for l in range(x.shape[0])])
        return read

    layers = {"program": lambda x: runner["served_expert_layers"](engine, config, x),
              "pick_left_out": control_layers(router_pick_left_out)}
    inputs = tapped.inputs[:len(check.reference_sample(config, seed)[2])]
    for name, read in layers.items():
        if name == "program" or control:
            errors, held = experts.expert_layer_errors(params, config, inputs, read)
            out.setdefault(name, {})["expert_layer"] = dict(
                experts.summarize_expert_layer(errors, held, config["reference"]),
                min=float(errors.min()))
            out[name + "_expert_layer_by_position"] = by_position(errors)
    engine.destroy()
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="lfm2-24b-a2b-10l")
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
        with open(os.path.join(ROOT, "chiprun_out", f"control_lfm2.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps({k: v for k, v in got.items()
                          if not k.endswith("_by_position") and k != "margins"}), flush=True)


if __name__ == "__main__":
    main()
