#!/usr/bin/env python3
"""``control.py``'s recipe on the ``longcat-flash-omni-ep32`` configuration:
the program's reading and the controls', per seed, on the chip at the size
the cell runs:

    python3 benchmark/tests/control_longcat.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a
pool just large enough for the check's sequences, so that a half layer of
rounded weights fits beside the model) and prints per seed what
``correct`` reads - the served logits against the float32 reference
(``harness/reference_longcat.py``; ``runners/serve_moonlight.py``
``summarize``, which ``runners/serve_longcat.py`` uses) and the served
expert layer alone against the reference's (``serve_longcat.py``
``summarize_expert_layer``) - and, for the first ``--control`` seeds, of
the controls, each of which has to come out as not correct:

``float8``
    that reference with every matrix of a layer, the embedding rows, the
    head and the residual stream between double layers rounded to float8
    e4m3 with one scale a tensor, the arithmetic float32. It moves every
    position, and the logits fail it.
``held_left_out``, ``held_permuted``
    that reference with a fault only in **this share's held experts**: the
    held picks' part of ``M(x)`` left out, or held expert ``e`` weighted as
    its neighbour ``e + 1`` was picked (what a grouped matmul does that
    returns zeros for its rows, or another group's). Both go through the
    whole forward pass for the logits, which cannot tell them from the
    reference (the readings are printed: a held pick is ~0.06 of one
    expert in about one layer in five), and through the expert layer
    alone, which has to fail them.

Errors and margins by position are written to
``chiprun_out/control_longcat.<seed>.json`` (too long for the output's
end). A benchmark run never runs this; ``test_longcat_cell.py`` keeps it
at debug size.
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

from benchmark.harness import reference_longcat as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402


@functools.partial(jax.jit, static_argnames=("dtype",))
def _rounded_layer(layers, layer, dtype):
    return jax.tree.map(lambda x: _rounded(x[layer], dtype)[None], layers)


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_longcat.rows_at``'s rows in the next precision down."""
    attn, moe = reference.layer_kwargs(model)
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(params["model"]["embed_tokens"], ids), dtype)
        for l in range(int(model["num_layers"])):
            low = _rounded_layer(params["model"]["layers"], jnp.int32(l), dtype)
            h = _rounded(reference.double_layer(low, 0, h, attn, moe)[0], dtype)
    return jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)


def _held_columns(weights, kw):
    return weights[..., kw["first"]:kw["first"] + kw["held"]]


def router_held_left_out(x, router, **kw):
    """The reference's router with the held picks' weights zeroed."""
    weights, margin = reference._router(x, router, **kw)
    first = kw["first"]
    return weights.at[..., first:first + kw["held"]].set(0.0), margin


def router_held_permuted(x, router, **kw):
    """The reference's router with held expert ``e`` given the weight of
    held column ``e + 1``: every held pick goes to its neighbour."""
    weights, margin = reference._router(x, router, **kw)
    first = kw["first"]
    return (weights.at[..., first:first + kw["held"]].set(
        jnp.roll(_held_columns(weights, kw), -1, axis=-1)), margin)


SHARE_CONTROLS = {"held_left_out": router_held_left_out, "held_permuted": router_held_permuted}


def measure(bench, config, seed, rehearse, control=True, prepare=None):
    """→ what ``correct`` reads of the program and, with ``control``, of
    each control against the same reference and margins; errors and
    margins by position too. ``prepare(engine)``: a test's hook, before
    anything is read of the engine."""
    runner = bench.load("runners", "serve_longcat", "run").__globals__
    check = runner["_check"]()
    # the check's own sequences need few blocks; the cell's pool is not under test here
    steps, block = config["reference"]["decode_steps"], config["engine"]["kv_block_size"]
    need = sum(-(-(n + steps) // block) + 1 for n in check.sample_lengths(config["reference"]))
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=need + 1))
    engine = runner["build_engine"](config, seed, rehearse)
    if prepare is not None:
        prepare(engine)
    params, model = engine.params, config["model"]
    by_position = lambda a: [[round(float(e), 5) for e in row] for row in a]  # noqa: E731
    out = {"seed": seed,
           "attention_impls": {str(k): v for k, v in engine.attention_impls.items()}}
    head8 = {"model": {"norm": params["model"]["norm"]},
             "lm_head": {"kernel": _rounded(params["lm_head"]["kernel"], jnp.float8_e4m3fn)}}

    got = check.served_logits(engine, config, check.reference_sample(config, seed)[0])

    def program(first, ids, positions):
        return lambda i: got[first + i]

    def float8(first, ids, positions):
        rows = rows_rounded(params, ids, positions, model, jnp.float8_e4m3fn)
        return lambda i: reference.head_at(head8, rows[i:i + 1], model)[0]

    def faulty_share(router):
        def read(first, ids, positions):
            rows = reference.layers_at(params, ids, positions, model, router=router)[0]
            return lambda i: reference.head_at(params, rows[i:i + 1], model)[0]
        return read

    logits = {"program": program, "float8": float8,
              **{name: faulty_share(router) for name, router in SHARE_CONTROLS.items()}}
    check.reference_moonlight = tapped = runner["Tapped"]()
    try:
        for name, read in logits.items():
            if name == "program" or control:
                errors, margins, _ = check.reference_errors(params, config, seed, read)
                out[name] = dict(check.summarize(errors, margins, config["reference"]),
                                 min=float(errors.min()))
                out[name + "_by_position"] = by_position(errors)
                out["margins"] = by_position(margins)
    finally:
        check.reference_moonlight = reference

    # the expert layer alone, on what the reference's expert layers saw in the first pass
    def control_layers(router):
        def read(x):
            return np.stack([np.asarray(reference.experts_at(
                params, l, jnp.asarray(x[l])[None], model, router=router)[0])[0]
                for l in range(x.shape[0])])
        return read

    layers = {"program": lambda x: runner["served_expert_layers"](engine, config, x),
              **{name: control_layers(router) for name, router in SHARE_CONTROLS.items()}}
    inputs = tapped.inputs[:len(check.reference_sample(config, seed)[2])]
    for name, read in layers.items():
        if name == "program" or control:
            errors, held = runner["expert_layer_errors"](params, config, inputs, read)
            out[name]["expert_layer"] = runner["summarize_expert_layer"](
                errors, held, config["reference"])
            out[name + "_expert_layer_by_position"] = by_position(errors)
            out["held_by_position"] = [[int(h) for h in row] for row in held]
    engine.destroy()
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="longcat-flash-omni-ep32")
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
        with open(os.path.join(ROOT, "chiprun_out", f"control_longcat.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps({k: v for k, v in got.items()
                          if not k.endswith("_by_position") and k != "margins"}), flush=True)


if __name__ == "__main__":
    main()
