#!/usr/bin/env python3
"""``control.py``'s recipe on the ``nemotron3-super-ep4-11l`` configuration:
the program's reading and the controls', per seed, on the chip at the size
the cell runs:

    python3 benchmark/tests/control_nemotron.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a
pool just large enough for the check's sequences) and prints per seed what
``correct`` reads - the served logits against the float32 reference
(``harness/reference_nemotron_h.py``; ``runners/serve_moonlight.py``
``summarize``, which ``runners/serve_nemotron.py`` uses), every ``M``
layer alone (its output a row, the state and the convolution's tail it
leaves) and every ``E`` layer alone - and, for the first ``--control``
seeds, of the controls, each of which has to come out as not correct:

``float8``
    that reference with every matrix and vector of a layer, the embedding
    rows, the head and the residual stream between layers rounded to
    float8 e4m3 with one scale a tensor, the arithmetic float32. It moves
    every position, and the logits fail it.
``state_bf16``
    the served ``M`` layer with **its state carried in bfloat16**: what a
    slot pool of the stream's type would hold (rounded after every call,
    as a pool of that type rounds what is written to it). The ``M`` layer
    alone has to fail it, by the state it leaves.
``held_left_out``
    that reference's expert layer with **one held pick a token left out**
    (the largest-weighted one: what a grouped matmul does that drops a
    row). The ``E`` layer alone has to fail it.

Errors and margins by position are written to
``chiprun_out/control_nemotron.<seed>.json`` (too long for the output's
end). A benchmark run never runs this; ``test_nemotron_cell.py`` keeps it
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

from benchmark.harness import reference_nemotron_h as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402

F8 = jnp.float8_e4m3fn


@functools.partial(jax.jit, static_argnames=("dtype",))
def _rounded_layer(stack, layer, dtype):
    return jax.tree.map(lambda x: _rounded(x[layer], dtype)[None], stack)


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_nemotron_h.rows_at``'s rows in the next precision down."""
    mamba, attn, moe = reference.layer_kwargs(model)
    m = params["model"]
    seen = dict.fromkeys(reference.STACKS, 0)
    zero = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(m["embed_tokens"], ids), dtype)
        for letter in model["hybrid_override_pattern"]:
            low = _rounded_layer(m[reference.STACKS[letter]], jnp.int32(seen[letter]), dtype)
            if letter == reference.MAMBA:
                h = reference._mamba_layer(low, zero, h, **mamba)[0]
            elif letter == reference.ATTENTION:
                h = reference._attention_layer(low, zero, h, **attn)
            else:
                x = reference._norm(low, zero, h, eps=attn["eps"])
                h = h + reference._experts(low, zero, x, **moe)[0]
            h = jax.block_until_ready(_rounded(h, dtype))
            seen[letter] += 1
    return jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)


def router_held_left_out(x, router, **kw):
    """The reference's router with the largest-weighted held pick of every
    token given no weight."""
    weights, margin = reference._router(x, router, **kw)
    first, held = kw["first"], kw["held"]
    mine = weights[..., first:first + held]
    largest = jnp.argmax(mine, axis=-1)
    dropped = jnp.where(jnp.arange(held) == largest[..., None], 0.0, mine)
    return weights.at[..., first:first + held].set(dropped), margin


def measure(bench, config, seed, rehearse, control=True, prepare=None):
    """→ what ``correct`` reads of the program and, with ``control``, of
    each control against the same reference; errors and margins by
    position too. ``prepare(engine)``: a test's hook, before anything is
    read of the engine."""
    runner = bench.load("runners", "serve_nemotron", "run").__globals__
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
    out = {"seed": seed,
           "attention_impls": {str(k): v for k, v in engine.attention_impls.items()}}
    head8 = {"model": {"norm": params["model"]["norm"]},
             "lm_head": {"kernel": _rounded(params["lm_head"]["kernel"], F8)}}

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
                out["margins"] = by_position(margins)
    finally:
        check.reference_moonlight = reference

    # the M layers alone, on what the reference's saw of the longest sequence (the first pass)
    n_mamba = model["hybrid_override_pattern"].count(reference.MAMBA)
    taps = [(runner["bf16_values"](x), y, state, tail)
            for x, y, state, tail in tapped.mamba[:n_mamba]]
    served = runner["served_mamba_layer"]
    layers = {"program": lambda layer, x: served(engine, config, layer, x),
              "state_bf16": lambda layer, x: served(engine, config, layer, x,
                                                    state_dtype=jnp.bfloat16)}
    for name, read in layers.items():
        if name == "program" or control:
            errors, states, tails = runner["mamba_layer_readings"](taps, read)
            out.setdefault(name, {})["mamba_layer"] = dict(
                runner["summarize_mamba_layer"](errors, states, tails, config["reference"]),
                states=[float(s) for s in states], tails=[float(t) for t in tails])

    # the E layers alone, on what the reference's saw at the compared positions
    def control_layers(router):
        def read(x):
            return np.stack([np.asarray(reference.experts_at(
                params, l, jnp.asarray(x[l])[None], model, router=router)[0])[0]
                for l in range(x.shape[0])])
        return read

    layers = {"program": lambda x: runner["served_expert_layers"](engine, config, x),
              "held_left_out": control_layers(router_held_left_out)}
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
    parser.add_argument("--config", default="nemotron3-super-ep4-11l")
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
        with open(os.path.join(ROOT, "chiprun_out", f"control_nemotron.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps({k: v for k, v in got.items()
                          if not k.endswith("_by_position") and k != "margins"}), flush=True)


if __name__ == "__main__":
    main()
