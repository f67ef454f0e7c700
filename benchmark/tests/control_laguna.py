#!/usr/bin/env python3
"""``control.py``'s recipe on the ``laguna-xs2-ep8-20l`` configuration: the
program's reading and the controls', per seed, on the chip at the size the
cell runs:

    python3 benchmark/tests/control_laguna.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with pools
just large enough for the check's sequences) and prints per seed what
``correct`` reads - the served logits against the float32 reference
(``harness/reference_laguna.py``; ``runners/serve_moonlight.py`` ``summarize``,
which ``runners/serve_laguna.py`` uses), every window layer and every full
layer alone and every routed feed-forward alone - and, for the first
``--control`` seeds, of the controls, each of which has to come out as not
correct:

``float8``
    that reference with every matrix and vector of a layer, the embedding
    rows, the head and the residual stream between layers rounded to float8
    e4m3 with one scale a tensor, the arithmetic float32. It moves every
    position, and the logits fail it.
``unwindowed``
    the reference's window layers without their window's lower edge (what a
    layer does that reads a sequence's whole context). The window layer
    alone has to fail it at every row from ``sliding_window`` on
    (``from_window_min``), and reads exactly the reference before.
``window_short``
    the reference's window layers with a window one block short (what a call
    does that starts its walk a block late). The same rows have to fail.
``gateless``
    the reference's attention, of either kind, without the gate a head.
``full_rotary`` / ``no_attention_factor``
    the reference's full layers rotated over all 128 columns of a head /
    with cos and sin not multiplied by YaRN's attention factor. The full
    layer alone has to fail each.
``held_left_out``
    the reference's routed feed-forward with the largest-weighted held pick
    of every token given no weight: what a grouped matmul does that drops a
    row. The expert layer alone has to fail it.

Errors by position are written to ``chiprun_out/control_laguna.<seed>.json``
(too long for the output's end). A benchmark run never runs this;
``test_laguna_cell.py`` keeps it at debug size.
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

from benchmark.harness import reference_laguna as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402
# a layer's leaves rounded op by op, not inside one program (PERF.md, PR 45)
from benchmark.tests.control_jamba import _rounded_layer  # noqa: E402

F8 = jnp.float8_e4m3fn
FULL, WINDOW = reference.FULL, reference.WINDOW


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_laguna.rows_at``'s rows in the next precision down."""
    attn, moe = reference.layer_kwargs(model)
    m = params["model"]
    seen = dict.fromkeys(reference.STACKS, 0)
    dense = sparse = 0
    zero = jnp.int32(0)
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(m["embed_tokens"], ids), dtype)
        for kind, ffn in zip(model["layer_types"], model["mlp_layer_types"]):
            low = _rounded_layer(m[reference.STACKS[kind]], jnp.int32(seen[kind]), dtype)
            h = _rounded(reference._attention_layer(low, zero, h, **attn[kind])[0], dtype)
            seen[kind] += 1
            eps = attn[kind]["eps"]
            if ffn == "dense":
                low = _rounded_layer(m["dense_ffn"], jnp.int32(dense), dtype)
                h = h + reference._dense(low, zero, reference._norm(low, zero, h, eps=eps))
                dense += 1
            else:
                low = _rounded_layer(m["moe"], jnp.int32(sparse), dtype)
                x = reference._norm(low, zero, h, eps=eps)
                h = h + reference._experts(low, zero, x, **moe)[0]
                sparse += 1
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


def attention_controls(model, block):
    """What each control changes of the reference's attention
    (``reference_laguna.layer_kwargs``), and the kind of layer that has to
    fail it (None: either)."""
    return {"unwindowed": (WINDOW, {"window": None}),
            "window_short": (WINDOW, {"window": model["sliding_window"] - block}),
            "gateless": (None, {"gated": False}),
            "full_rotary": (FULL, {"rotated": model["head_dim"]}),
            "no_attention_factor": (FULL, {"factor": 1.0})}


def measure(bench, config, seed, rehearse, control=True, prepare=None):
    """→ what ``correct`` reads of the program and, with ``control``, of
    each control against the same reference; errors by position too.
    ``prepare(engine)``: a test's hook, before anything is read of the
    engine."""
    runner = bench.load("runners", "serve_laguna", "run").__globals__
    check, experts = runner["_check"](), runner["_expert_check"]()
    # the check's own sequences need few blocks; the cell's pools are not under test here
    ref, block = config["reference"], config["engine"]["kv_block_size"]
    need = sum(-(-(n + ref["decode_steps"]) // block) + 1 for n in check.sample_lengths(ref))
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=need + 3,
                                      num_window_blocks=ref["window_blocks_free"] + 3))
    engine = runner["build_engine"](config, seed, rehearse)
    if prepare is not None:
        prepare(engine)
    params, model = engine.params, config["model"]
    by_position = lambda a: [[round(float(e), 5) for e in row] for row in a]  # noqa: E731
    out = {"seed": seed}
    head8 = {"model": {"norm": params["model"]["norm"]},
             "lm_head": {"kernel": _rounded(params["lm_head"]["kernel"], F8)}}

    with runner["short_window_pool"](engine, ref["window_blocks_free"]) as pool:
        got = check.served_logits(engine, config, check.reference_sample(config, seed)[0])
    out["window_pool"] = pool
    out["attention_impls"] = {str(k): v for k, v in engine.attention_impls.items()}

    def program(first, ids, positions):
        return lambda i: got[first + i]

    def float8(first, ids, positions):
        rows = rows_rounded(params, ids, positions, model, F8)
        return lambda i: reference.head_at(head8, rows[i:i + 1], model)[0]

    tapped = runner["Tapped"](runner["longest_sample"](ref))
    check.reference_moonlight = tapped
    try:
        for name, read in (("program", program), ("float8", float8)):
            if name == "program" or control:
                errors, margins, _ = check.reference_errors(params, config, seed, read)
                out[name] = dict(check.summarize(errors, margins, ref), min=float(errors.min()))
                out[name + "_by_position"] = by_position(errors)
                out["margins"] = by_position(margins)
    finally:
        check.reference_moonlight = reference
    bf16 = runner["bf16_values"]

    # the attention layers alone, a kind at a time, on what the reference's saw of the longest
    # sequence (the first pass)
    controls, window = attention_controls(model, block), model["sliding_window"]
    for kind, name in runner["CHECKS"].items():
        taps = [(bf16(x), y) for x, y in tapped.attn[kind][:model["layer_types"].count(kind)]]
        layers = {"program": lambda layer, x, kind=kind: runner["served_attention_layer"](
            engine, config, kind, layer, x)[0]}
        if control:
            for cname, (of_kind, change) in controls.items():
                if of_kind in (None, kind):
                    layers[cname] = lambda layer, x, kind=kind, change=change: np.asarray(
                        reference.attention_at(params, kind, layer, x, model, control=change))
        for cname, read in layers.items():
            errors = runner["attention_layer_errors"](taps, read)
            got_ = dict(runner["summarize_attention_layer"](errors, ref[name]),
                        min=float(errors.min()))
            if kind == WINDOW and errors.shape[1] > window:
                over = (errors > ref[name]["tolerance"]).all(axis=0)
                under = np.flatnonzero(~over)
                got_.update(before_window_max=float(errors[:, :window - block].max()),
                            from_window_min=float(errors[:, window:].min()),
                            # the row from which every row of every layer reads over the limit
                            all_over_from=int(under.max()) + 1 if under.size else 0)
            if cname != "program":
                out[f"{cname}_{name}_by_row"] = [round(float(e), 5) for e in errors.max(axis=0)]
            out.setdefault(cname, {})[name] = got_

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
                experts.summarize_expert_layer(errors, held, ref), min=float(errors.min()))
            out[name + "_expert_layer_by_position"] = by_position(errors)
    engine.destroy()
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="laguna-xs2-ep8-20l")
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
        with open(os.path.join(ROOT, "chiprun_out", f"control_laguna.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps({k: v for k, v in got.items()
                          if not k.endswith(("_by_position", "_by_row")) and k != "margins"}),
              flush=True)


if __name__ == "__main__":
    main()
