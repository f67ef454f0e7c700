#!/usr/bin/env python3
"""``control.py``'s recipe on the ``moonlight-16b-a3b`` configuration: the
program's reading and the control's, per seed, on the chip at the size
the cell runs:

    python3 benchmark/tests/control_moonlight.py --seed 3000001201 [--seed ...] [--control 3]

builds the configuration's engine from each seed (one at a time, with a
pool just large enough for the check's sequences, so that a layer of
rounded weights fits beside the model) and prints per seed what
``correct`` reads of the served logits against the float32 reference
(``harness/reference_moonlight.py``; ``runners/serve_moonlight.py``
``summarize``) and, for the first ``--control`` seeds, of two controls
that have to come out as not correct: ``float8``, that reference with
every matrix of a layer, the embedding rows, the head and the residual
stream between layers rounded to float8 e4m3 with one scale a tensor, the
arithmetic float32 (it moves every position); and ``bf16_router``, that
reference with only the router's scores in bfloat16 (it moves no
position but those whose chosen experts it changes). A benchmark run
never runs this; ``test_moonlight_cell.py`` keeps it at debug size.
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

from benchmark.harness import reference_moonlight as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402


@functools.partial(jax.jit, static_argnames=("dtype",))
def _rounded_layer(layers, layer, dtype):
    return jax.tree.map(lambda x: _rounded(x[layer], dtype)[None], layers)


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_moonlight.rows_at``'s rows in the next precision down."""
    attn, moe = reference.layer_kwargs(model)
    n_dense = int(model["first_k_dense_replace"])
    groups = ((params["model"]["dense_layers"], n_dense, reference._dense_layer, attn),
              (params["model"]["layers"], int(model["num_hidden_layers"]) - n_dense,
               lambda *a, **kw: reference._expert_layer(*a, **kw)[0], moe))
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(params["model"]["embed_tokens"], ids), dtype)
        for layers, count, layer_fn, kw in groups:
            for i in range(count):
                h = _rounded(layer_fn(_rounded_layer(layers, jnp.int32(i), dtype),
                                      jnp.int32(0), h, **kw), dtype)
    return jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)


def _bf16_router(x, gate, top_k, scaling):
    """``reference_moonlight._router`` with the scores formed in bfloat16
    (input, matmul, sigmoid and the bias added), as a program would that
    did not keep the router in float32; the weights of the chosen from
    those scores, in float32 like the rest of the reference."""
    low = jnp.bfloat16
    scores = jax.nn.sigmoid(jnp.matmul(x.astype(low), gate["weight"].astype(low),
                                       preferred_element_type=low))
    biased = scores + gate["e_score_correction_bias"].astype(low)
    ranked, chosen = jax.lax.top_k(biased, top_k + 1)
    chosen = chosen[..., :top_k]
    picked = jnp.take_along_axis(scores.astype(reference.F32), chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) * scaling
    weights = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=reference.F32)
                      * picked[..., None], axis=-2)
    return weights, (ranked[..., top_k - 1] - ranked[..., top_k]).astype(reference.F32)


def rows_bf16_router(params, ids, positions, model):
    """``reference_moonlight.rows_at``'s rows with only the router in
    bfloat16: the one thing the configuration states as float32 whose loss
    moves no position but those it flips."""
    attn, moe = reference.layer_kwargs(model)
    n_dense = int(model["first_k_dense_replace"])
    with jax.default_matmul_precision("highest"):
        h = reference._embed(params["model"]["embed_tokens"], ids)
        for i in range(n_dense):
            h = reference._dense_layer(params["model"]["dense_layers"], jnp.int32(i), h, **attn)
        for i in range(int(model["num_hidden_layers"]) - n_dense):
            h, _ = reference._expert_layer(params["model"]["layers"], jnp.int32(i), h,
                                           router=_bf16_router, **moe)
    return jnp.take_along_axis(h, jnp.asarray(positions)[..., None], axis=1)


def measure(bench, config, seed, rehearse, control=True):
    """→ what ``correct`` reads of the program (``runners/serve_moonlight.py``
    ``summarize``) and, with ``control``, of the two controls against the
    same reference and margins; errors and margins by position too."""
    runner = bench.load("runners", "serve_moonlight", "run").__globals__
    # the check's own sequences need few blocks; the cell's pool is not under test here
    steps, block = config["reference"]["decode_steps"], config["engine"]["kv_block_size"]
    need = sum(-(-(n + steps) // block) + 1 for n in runner["sample_lengths"](config["reference"]))
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=need + 1))
    engine = runner["build_engine"](config, seed, rehearse)
    params, model = engine.params, config["model"]
    by_position = lambda a: [[round(float(e), 5) for e in row] for row in a]  # noqa: E731
    out = {"seed": seed,
           "attention_impls": {str(k): v for k, v in engine.attention_impls.items()}}
    head8 = {"model": {"norm": params["model"]["norm"]},
             "lm_head": {"kernel": _rounded(params["lm_head"]["kernel"], jnp.float8_e4m3fn)}}

    got = runner["served_logits"](engine, config, runner["reference_sample"](config, seed)[0])

    def program(first, ids, positions):
        return lambda i: got[first + i]

    def float8(first, ids, positions):
        rows = rows_rounded(params, ids, positions, model, jnp.float8_e4m3fn)
        return lambda i: reference.head_at(head8, rows[i:i + 1], model)[0]

    def bf16_router(first, ids, positions):
        rows = rows_bf16_router(params, ids, positions, model)
        return lambda i: reference.head_at(params, rows[i:i + 1], model)[0]

    for name, read in (("program", program), ("float8", float8), ("bf16_router", bf16_router)):
        if name == "program" or control:
            errors, margins, _ = runner["reference_errors"](params, config, seed, read)
            out[name] = dict(runner["summarize"](errors, margins, config["reference"]),
                             min=float(errors.min()))
            out[name + "_by_position"] = by_position(errors)
            out["margins"] = by_position(margins)
    engine.destroy()
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="moonlight-16b-a3b")
    parser.add_argument("--seed", type=int, action="append", required=True)
    parser.add_argument("--control", type=int, default=3,
                        help="run the float8 control for the first N seeds")
    args = parser.parse_args()
    from benchmark.harness import device, spec
    bench = spec.Benchmark(ROOT)
    device.require_devices(1)
    device.enable_compile_cache()
    for i, seed in enumerate(args.seed):
        print(json.dumps(measure(bench, bench.config(args.config), seed, False,
                                 control=i < args.control)), flush=True)


if __name__ == "__main__":
    main()
