#!/usr/bin/env python3
"""``control.py``'s recipe on the ``ouro-2.6b`` configuration: the program's
reading and the controls', per seed, on the chip at the size the cell runs:

    python3 benchmark/tests/control_ouro.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a pool
just large enough for the check's sequence) and prints per seed what
``correct`` reads (``runners/serve_ouro.py``: the served logits at every
compared position, the burst's regret, every pass's stream and gate against
``harness/reference_ouro.py``) and, for the first ``--control`` seeds, of the
controls, each of which has to come out as not correct by at least one of
the cell's limits:

``loops_share_cache``
    the **served** path with every pass reading and writing pool layers ``0
    .. L - 1`` (``OuroKind.pool_layers`` patched: the paper's cache-sharing
    approximation, which the published code does not make). The first
    chunk's logits still agree - a pass's rows are all its own there
    (``logits.first_chunk``) - and the second chunk, the decode rows and the
    burst have to fail.
``one_loop_fewer``
    the reference run ``R - 1`` times: what the head reads is ``x_{R-2}``.
``loop_norm_left_out``
    the reference with the model's norm once, after the last pass.
``sandwich_norms_left_out``
    the reference with each sublayer's output added as computed.
``float8``
    the reference with every matrix and vector of a layer, the embedding
    rows, the head and the stream after every layer rounded to float8 e4m3
    with one scale a tensor, op by op, the arithmetic float32.

and of one reading that is **no fault** and has to come out as correct by the
logits: ``bfloat16_stream``, the reference with its stream at bfloat16's
values after every layer (the arithmetic float32) - what four passes in a
row make of the configuration's own precision with these seeded weights.

A benchmark run never runs this; ``test_ouro_cell.py`` keeps it at debug size.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import reference_ouro as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402

F8 = jnp.float8_e4m3fn


def reference_controls(model):
    """What each control changes of ``reference_ouro.forward``."""
    return {"one_loop_fewer": {"passes": int(model["total_ut_steps"]) - 1},
            "loop_norm_left_out": {"leave_out": (reference.LOOP_NORM,)},
            "sandwich_norms_left_out": {"leave_out": (reference.SANDWICH_NORMS,)},
            "float8": {"lower": lambda x: _rounded(x, F8)},
            # no fault: the stream at bfloat16's values after every layer, the arithmetic
            # float32 - what the seeded weights make of the configuration's own precision over
            # four passes in a row (a difference grows ~2.6 times a pass), beside the program
            "bfloat16_stream": {"lower": lambda x: x.astype(jnp.bfloat16).astype(x.dtype)}}


def shared_cache(runner, engine, config, ids):
    """The served check with every pass on pool layers ``0 .. L - 1``: a
    second engine on the same weights whose programs are traced under the
    patch → what ``correct`` would read of it."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, model_runner
    kind = model_runner.OuroKind
    own = kind.pool_layers
    kind.pool_layers = staticmethod(lambda cfg, u: own(cfg, 0))
    try:
        shared = InferenceEngineV2(params=engine.params, model_config=engine.model_config,
                                   config=engine._config, dtype=engine.dtype)
        rows, full, burst = runner["served_sequence"](shared, config, ids)
        want = reference.forward(engine.params, jnp.asarray(full)[None], config["model"])
        x, g, _ = runner["served_passes"](shared, config, full, np.asarray(want["passes"])[:, 0])
    finally:
        kind.pool_layers = own
    return runner["summarize"](runner["readings"](want, config["reference"], rows, x, g, burst),
                               config["reference"])


def measure(bench, config, seed, rehearse, control=True, prepare=None):
    """→ what ``correct`` reads of the program and, with ``control``, of each
    control against the same reference. ``prepare(engine)``: a test's hook,
    before anything is read of the engine."""
    runner = bench.load("runners", "serve_ouro", "run").__globals__
    ref, block = config["reference"], config["engine"]["kv_block_size"]
    length = ref["prompt_tokens"] + ref["decode_rows"] + 1 + ref["burst"]
    # the check's own sequence needs few blocks; the cell's pool is not under test here
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=-(-length // block) + 4))
    engine = runner["build_engine"](config, seed, rehearse)
    if prepare is not None:
        prepare(engine)
    model, ids = config["model"], runner["check_tokens"](config, seed)
    rows, full, burst = runner["served_sequence"](engine, config, ids)
    want = reference.forward(engine.params, jnp.asarray(full)[None], model)
    x, g, impls = runner["served_passes"](engine, config, full, np.asarray(want["passes"])[:, 0])
    out = {"seed": seed, "attention_impls": {str(k): v for k, v in impls.items()},
           "program": runner["summarize"](runner["readings"](want, ref, rows, x, g, burst), ref)}
    if control:
        positions = runner["compared_positions"](ref)
        for name, change in reference_controls(model).items():
            low = reference.forward(engine.params, jnp.asarray(full)[None], model, **change)
            logits = np.asarray(low["logits"])
            out[name] = runner["summarize"](runner["readings"](
                want, ref, {p: logits[0, p] for p in positions}, np.asarray(low["passes"])[:, 0],
                np.asarray(low["gates"])[:, 0]), ref)
        out["loops_share_cache"] = shared_cache(runner, engine, config, ids)
    engine.destroy()
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="ouro-2.6b")
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
        with open(os.path.join(ROOT, "chiprun_out", f"control_ouro.{seed}.json"), "w") as f:
            json.dump(got, f)
        print(json.dumps(got), flush=True)


if __name__ == "__main__":
    main()
