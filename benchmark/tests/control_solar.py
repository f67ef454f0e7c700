#!/usr/bin/env python3
"""``control.py``'s recipe on the ``solar-open2-ep8-4l`` configuration: the
program's reading and the controls', per seed, on the chip at the size the
cell runs:

    python3 benchmark/tests/control_solar.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a pool
just large enough for the check's sequences) and prints per seed what
``correct`` reads - the served logits against the float32 reference
(``harness/reference_solar.py``; ``runners/serve_moonlight.py`` ``summarize``,
which ``runners/serve_solar.py`` uses), every KDA layer alone (its output a
row, the state and the convolutions' tail it leaves), the attention layer
alone and every routed feed-forward alone - and, for the first ``--control``
seeds, of the controls, each of which has to come out as not correct:

``float8``
    that reference with every matrix and vector of a layer, the embedding
    rows, the head and the residual stream between layers rounded to float8
    e4m3 with one scale a tensor, the arithmetic float32. It moves every
    position, and the logits fail it; and a KDA mixer of it alone
    (``float8``'s ``kda_layer``: the reference's mixer on float8 weights, on
    what the float32 reference's layer saw) leaves a state that fails the
    state's limit.
``state_bf16``
    the served KDA layer with **its state carried in bfloat16**: what a slot
    pool of the stream's type would hold (rounded after every call, as a
    pool of that type rounds what is written to it). The KDA layer alone
    has to fail it, by the state it leaves: that is what holds the
    configuration's float32 to its word.
``beta_clipped``
    the reference's KDA mixer with ``beta = sigmoid(.)`` in (0, 1) - what
    forgetting ``kda_allow_neg_eigval`` does: no eigenvalue of a token's
    transition is negative. Its rows have to fail the rows' limit.
``gateless``
    the reference's attention mixer without ``use_gqa_gate``'s sigmoid gate.
    Its rows have to fail the attention layer's limit.
``held_left_out``
    the reference's routed feed-forward with the largest-weighted held pick
    of every token given no weight: what a grouped matmul does that drops a
    row. The expert layer alone has to fail it.

Errors by position are written to ``chiprun_out/control_solar.<seed>.json``
(too long for the output's end). A benchmark run never runs this;
``test_solar_cell.py`` keeps it at debug size.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import reference_solar as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402
# a layer's leaves rounded op by op, not inside one program (PERF.md, PR 45)
from benchmark.tests.control_jamba import _rounded_layer  # noqa: E402

F8 = jnp.float8_e4m3fn
F32 = jnp.float32


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_solar.rows_at``'s rows in the next precision down."""
    kda, attn, moe = reference.layer_kwargs(model)
    m = params["model"]
    seen = dict.fromkeys(reference.STACKS, 0)
    zero = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(m["embed_tokens"], ids), dtype)
        for position, kind in enumerate(reference.layer_kinds(model)):
            low = _rounded_layer(m[reference.STACKS[kind]], jnp.int32(seen[kind]), dtype)
            if kind == reference.KDA:
                h = reference._kda_layer(low, zero, h, **kda)[0]
            else:
                h = reference._attention_layer(low, zero, h, **attn)[0]
            h = _rounded(h, dtype)
            low = _rounded_layer(m["moe"], jnp.int32(position), dtype)
            x = reference._norm(low, zero, h, eps=attn["eps"])
            h = h + reference._experts(low, zero, x, **moe)[0]
            h = jax.block_until_ready(_rounded(h, dtype))
            seen[kind] += 1
    return jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)


def kda_rounded(params, layer, x, model, dtype):
    """KDA layer ``layer``'s mixer of the reference on weights rounded to
    ``dtype``, alone, on the normalised x [S, D] from a zero start → (y [S,
    D], the state [H, d, d], the tail [K - 1, 3 I])."""
    low = {"model": {"kda_layers": _rounded_layer(params["model"]["kda_layers"],
                                                  jnp.int32(layer), dtype)}}
    return tuple(np.asarray(t) for t in reference.kda_at(low, 0, x, model))


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
    each control against the same reference; errors by position too.
    ``prepare(engine)``: a test's hook, before anything is read of the
    engine."""
    runner = bench.load("runners", "serve_solar", "run").__globals__
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
    n_kda = reference.layer_kinds(model).count(reference.KDA)
    n_attn = reference.layer_kinds(model).count(reference.GQA)
    bf16 = runner["bf16_values"]

    # the KDA layers alone, on what the reference's saw of the longest sequence (the first pass)
    taps = [(bf16(x), y, state, tail) for x, y, state, tail in tapped.kda[:n_kda]]
    served = runner["served_kda_layer"]
    layers = {"program": lambda layer, x: served(engine, config, layer, x),
              "state_bf16": lambda layer, x: served(engine, config, layer, x,
                                                    state_dtype=jnp.bfloat16),
              "float8": lambda layer, x: kda_rounded(params, layer, x, model, F8),
              "beta_clipped": lambda layer, x: tuple(np.asarray(t) for t in reference.kda_at(
                  params, layer, x, model, beta_scale=1.0))}
    for name, read in layers.items():
        if name == "program" or control:
            errors, states, tails = runner["kda_layer_readings"](taps, read)
            out.setdefault(name, {})["kda_layer"] = dict(
                runner["summarize_kda_layer"](errors, states, tails, config["reference"]),
                states=[float(s) for s in states], tails=[float(t) for t in tails],
                min=float(errors.min()))

    # the attention layers alone
    taps = [(bf16(x), y) for x, y in tapped.attn[:n_attn]]
    layers = {"program": lambda layer, x: runner["served_attention_layer"](engine, config, layer,
                                                                           x)[0],
              "gateless": lambda layer, x: np.asarray(reference.attention_at(
                  params, layer, x, model, gated=False))}
    for name, read in layers.items():
        if name == "program" or control:
            errors = runner["attention_layer_errors"](taps, read)
            out.setdefault(name, {})["attention_layer"] = dict(
                runner["summarize_attention_layer"](errors, config["reference"]),
                min=float(errors.min()))

    # the routed feed-forwards alone, on what the reference's saw at the compared positions
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
    parser.add_argument("--config", default="solar-open2-ep8-4l")
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
        with open(os.path.join(ROOT, "chiprun_out", f"control_solar.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps({k: v for k, v in got.items()
                          if not k.endswith("_by_position") and k != "margins"}), flush=True)


if __name__ == "__main__":
    main()
