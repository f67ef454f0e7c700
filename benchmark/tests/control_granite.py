#!/usr/bin/env python3
"""``control.py``'s recipe on the ``granite4-h-small-ep4-10l`` configuration:
the program's reading and the controls', per seed, on the chip at the size
the cell runs:

    python3 benchmark/tests/control_granite.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a pool
just large enough for the check's sequences) and prints per seed what
``correct`` reads - the served logits against the float32 reference
(``harness/reference_granite.py``), **a resumed turn**, every Mamba layer
alone and every feed-forward alone (``runners/serve_granite.py``) - and, for
the first ``--control`` seeds, of the controls, each of which has to come out
as not correct:

``float8``
    that reference with every matrix and vector of a layer, the embedding
    rows and the residual stream between layers rounded to float8 e4m3 with
    one scale a tensor, the arithmetic float32. The logits fail it.
``attention_root``
    that reference's attention mixer with the scores scaled by ``1 / sqrt(128)``
    in place of ``attention_multiplier`` = 1/128. The attention layer alone has
    to fail it (the logits barely move: it is one mixer of ten).
``state_bf16``
    the served Mamba layer with **its state carried in bfloat16** (rounded
    after every call, as a pool of that type rounds what is written to it).
    The Mamba layer alone has to fail it, by the state it leaves.
``resume_zero`` / ``resume_older``
    a resumed turn whose slot is **cleared** after the snapshot was restored
    (a resume with the snapshot dropped), or holds **the state of the block
    before** the snapshot's boundary. The resumed turn's check fails both.
``held_left_out``
    that reference's feed-forward with **one held pick a token left out**.
    The feed-forward alone has to fail it.
``softmax_all``
    that reference's router with **the softmax taken over all 72 columns
    before the top 10** (the picks' weights as they stand, not over their
    sum). The feed-forward alone has to fail it.

Errors and margins by position are written to
``chiprun_out/control_granite.<seed>.json``. A benchmark run never runs this;
``test_granite_cell.py`` keeps it at debug size.
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

from benchmark.harness import reference_granite as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402

F8 = jnp.float8_e4m3fn


@functools.partial(jax.jit, static_argnames=("dtype",))
def _rounded_layer(stack, layer, dtype):
    return jax.tree.map(lambda x: _rounded(x[layer], dtype)[None], stack)


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_granite.rows_at``'s rows in the next precision down."""
    mamba, attn, moe = reference.layer_kwargs(model)
    m = params["model"]
    r = mamba["residual"]
    seen = {reference.MAMBA: 0, reference.ATTENTION: 0}
    zero = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(m["embed_tokens"], ids,
                                      multiplier=float(model["embedding_multiplier"])), dtype)
        for position, kind in enumerate(model["layer_types"]):
            if kind == reference.MAMBA:
                low = _rounded_layer(m["mamba_layers"], jnp.int32(seen[kind]), dtype)
                h = reference._mamba_layer(low, zero, h, **mamba)[0]
            else:
                low = _rounded_layer(m["attn_layers"], jnp.int32(seen[kind]), dtype)
                h = reference._attention_layer(low, zero, h, **attn)[0]
            seen[kind] += 1
            h = _rounded(h, dtype)
            low = _rounded_layer(m["moe_layers"], jnp.int32(position), dtype)
            x = reference._norm(low, zero, h, eps=attn["eps"])
            h = h + r * reference._experts(low, zero, x, **moe)[0]
            h = jax.block_until_ready(_rounded(h, dtype))
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


def router_softmax_all(x, router, **kw):
    """The softmax over every column first, the top ``k`` of it as they
    stand: the picks' weights do not add up to one."""
    weights, margin = reference._router(x, router, **kw)
    probs = jax.nn.softmax(x @ router["weight"].astype(jnp.float32), axis=-1)
    return jnp.where(weights > 0, probs, 0.0), margin


def tamper_zero(engine, slot, older):
    engine.state_extra = {name: x.at[:, slot].set(0) if name in engine.kind.slot_state else x
                          for name, x in engine.state_extra.items()}


def tamper_older(engine, slot, older):
    engine.state_extra = {name: x.at[:, slot].set(jnp.asarray(older[name], x.dtype))
                          if name in engine.kind.slot_state else x
                          for name, x in engine.state_extra.items()}


def measure(bench, config, seed, rehearse, control=True, prepare=None):
    """→ what ``correct`` reads of the program and, with ``control``, of each
    control against the same reference; errors and margins by position too.
    ``prepare(engine)``: a test's hook, before anything is read of the engine."""
    runner = bench.load("runners", "serve_granite", "run").__globals__
    nem = runner["_nemotron"]()
    check, experts = nem._check(), nem._expert_check()
    # the check's own sequences need few blocks; the cell's pool is not under test here
    steps, block = config["reference"]["decode_steps"], config["engine"]["kv_block_size"]
    resume = config["reference"]["resume"]
    need = sum(-(-(n + steps) // block) + 1 for n in check.sample_lengths(config["reference"]))
    need += 6 * (-(-(sum(resume[k] for k in ("first_prompt", "first_steps", "more",
                                              "decode_steps"))) // block) + 1)
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=need + 3))
    engine = runner["build_engine"](config, seed, rehearse)
    if prepare is not None:
        prepare(engine)
    params, model = engine.params, config["model"]
    by_position = lambda a: [[round(float(e), 5) for e in row] for row in a]  # noqa: E731
    out = {"seed": seed,
           "attention_impls": {str(k): v for k, v in engine.attention_impls.items()}}
    low_head = {"model": {"norm": params["model"]["norm"],
                          "embed_tokens": _rounded(params["model"]["embed_tokens"], F8)}}

    got = check.served_logits(engine, config, check.reference_sample(config, seed)[0])

    def program(first, ids, positions):
        return lambda i: got[first + i]

    def float8(first, ids, positions):
        rows = rows_rounded(params, ids, positions, model, F8)
        return lambda i: reference.head_at(low_head, rows[i:i + 1], model)[0]

    tapped = runner["tapped"](nem.longest_sample(config["reference"]))
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

    # a resumed turn, and the same steps as one sequence that never retires
    twin = runner["resume_readings"](engine, config, seed, resumed=False)
    resumes = {"program": None, "resume_zero": tamper_zero, "resume_older": tamper_older}
    for name, tamper in resumes.items():
        if name == "program" or control:
            logits, held, said = runner["resume_readings"](engine, config, seed, tamper=tamper)
            errors, margins, states, tails = runner["resume_errors"](params, config, seed, logits,
                                                                     held)
            out.setdefault(name, {})["resume"] = dict(
                runner["summarize_resume"](errors, margins, states, tails,
                                           runner["twin_drift"](held, logits, twin), said,
                                           config["reference"]),
                min=float(errors.min()), states=[float(e) for e in states])
            out[name + "_resume_by_position"] = by_position(errors)

    # the Mamba layers alone, on what the reference's saw of the longest sequence
    n_mamba = list(model["layer_types"]).count(reference.MAMBA)
    taps = [(nem.bf16_values(x), y, state, tail) for x, y, state, tail in tapped.mamba[:n_mamba]]
    served = runner["served_mamba_layer"]
    layers = {"program": lambda layer, x: served(engine, config, layer, x),
              "state_bf16": lambda layer, x: served(engine, config, layer, x,
                                                    state_dtype=jnp.bfloat16)}
    for name, read in layers.items():
        if name == "program" or control:
            errors, states, tails = nem.mamba_layer_readings(taps, read)
            out.setdefault(name, {})["mamba_layer"] = dict(
                nem.summarize_mamba_layer(errors, states, tails, config["reference"]),
                states=[float(s) for s in states], tails=[float(t) for t in tails])

    # the attention layer alone
    root = (int(model["hidden_size"]) // int(model["num_attention_heads"])) ** -0.5
    taps = [(nem.bf16_values(x), y) for x, y in tapped.attention]
    layers = {"program": lambda layer, x: runner["served_attention_layer"](engine, config, layer, x),
              "attention_root": lambda layer, x: np.asarray(reference.attention_at(
                  params, layer, jnp.asarray(x), model, scale=root))}
    for name, read in layers.items():
        if name == "program" or control:
            errors = runner["attention_layer_readings"](taps, read)
            out.setdefault(name, {})["attention_layer"] = dict(
                runner["summarize_attention_layer"](errors, config["reference"]),
                min=float(errors.min()))

    # the feed-forwards alone, on what the reference's saw at the compared positions
    def control_layers(router):
        def read(x):
            return np.stack([np.asarray(reference.experts_at(
                params, l, jnp.asarray(x[l])[None], model, router=router)[0])[0]
                for l in range(x.shape[0])])
        return read

    layers = {"program": lambda x: runner["served_expert_layers"](engine, config, x),
              "held_left_out": control_layers(router_held_left_out),
              "softmax_all": control_layers(router_softmax_all)}
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
    parser.add_argument("--config", default="granite4-h-small-ep4-10l")
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
        with open(os.path.join(ROOT, "chiprun_out", f"control_granite.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps({k: v for k, v in got.items()
                          if not k.endswith("_by_position") and k != "margins"}), flush=True)


if __name__ == "__main__":
    main()
