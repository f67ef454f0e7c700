#!/usr/bin/env python3
"""The control of a serving cell's reference check, and the program's own
reading beside it:

    python3 benchmark/tests/control.py --config mistral-7b --seed 3000001201 [--seed ...]

builds the configuration's engine from each seed (on the chip, at the
size the cell runs; one engine at a time), and prints per seed the
largest relative error of the served logits against the float32
reference (what ``correct`` compares with ``reference.tolerance``) and of
the control: the reference itself in the next precision down
(``logits_rounded`` below, float8 e4m3 for a bfloat16 configuration), on
the same sequences. The control has to come out as
not correct: above the tolerance, with room. A benchmark run never runs
this; ``test_benchmark.py`` keeps it at debug size.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import reference  # noqa: E402

F32 = jnp.float32


def _rounded(x, dtype):
    """``x`` as ``dtype`` would hold it at best: scaled so that its largest
    magnitude is the type's largest, cast, and scaled back."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(F32))), 1e-30) / float(jnp.finfo(dtype).max)
    return ((x.astype(F32) / scale).astype(dtype).astype(F32) * scale).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _rounded_layer(layers, layer, dtype):
    return jax.tree.map(lambda x: _rounded(x[layer], dtype)[None], layers)


def logits_rounded(params, ids, model, dtype):
    """The control of the comparison that decides ``correct``: the plain
    reference (``harness/reference.py``: its layer, embedding and head) in
    the next precision down. Every matrix of a layer, the embedding rows,
    the head and the residual stream between layers are rounded to
    ``dtype`` with one scale a tensor, the arithmetic stays float32: what
    a lower-precision path loses at the least."""
    kw = dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"],
              eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
              top_k=int(model.get("num_experts_per_tok", 0)))
    with jax.default_matmul_precision("highest"):
        h = _rounded(reference._embed(params["model"]["embed_tokens"], ids), dtype)
        layers = params["model"]["layers"]
        for i in range(model["num_hidden_layers"]):
            h = _rounded(reference._layer(_rounded_layer(layers, jnp.int32(i), dtype),
                                          jnp.int32(0), h, **kw), dtype)
        head = {"model": {"norm": params["model"]["norm"]},
                "lm_head": {"kernel": _rounded(params["lm_head"]["kernel"], dtype)}}
        return reference._head(head, h, eps=float(model["rms_norm_eps"]))


def measure(bench, config, seed, rehearse):
    """→ {"program": largest rel. error of the served logits, "control":
    smallest rel. error of the control's, "tolerance"}."""
    serve = bench.load("runners", "serve", "run").__globals__
    engine = serve["build_engine"](config, seed, rehearse)
    errs, _ = serve["reference_check"](engine, config, seed)
    # the sequences reference_check drew, and the two rows of each it compares
    seqs, padded = serve["reference_sample"](config, seed)
    want = np.asarray(reference.logits(engine.params, jnp.asarray(padded), config["model"]))
    low = np.asarray(logits_rounded(engine.params, jnp.asarray(padded), config["model"],
                                    jnp.float8_e4m3fn))
    control = [serve["rel_err"](low[i, len(s) - j], want[i, len(s) - j])
               for i, s in enumerate(seqs) for j in (2, 1)]
    return {"seed": seed, "program": max(errs.values()), "control": min(control),
            "control_max": max(control), "tolerance": config["reference"]["tolerance"]}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, action="append", required=True)
    args = parser.parse_args()
    from benchmark.harness import device, spec
    bench = spec.Benchmark(ROOT)
    device.require_devices(1)
    device.enable_compile_cache()
    for seed in args.seed:
        print(json.dumps(measure(bench, bench.config(args.config), seed, False)), flush=True)


if __name__ == "__main__":
    main()
