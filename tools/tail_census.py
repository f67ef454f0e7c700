#!/usr/bin/env python3
"""``model_runner._conv_with_tail`` alone, by op, at ``jamba2-3b-chatloop``'s shape.

    chiprun -- python3 tools/tail_census.py [--parent _checkout/parent]

The function in a 26-layer scan with the tail pool donated - 512 rows: 227
decode rows and prompt chunks of 100 and 66, 229 live sequence rows of 257 -
run five times under the profiler, then each op's own time a call and its
calls (``benchmark/harness/trace.py``'s reduction). ``--parent DIR`` does the
same with the ``model_runner.py`` of another checkout and says whether the
two leave the same bits (``acc`` summed over the layers, the live slots).
About a minute on one chip; ``chiprun_out/tail_census.json`` (PERF.md, PR 46).
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
L, NS, K, C, T, S = 26, 257, 4, 5120, 512, 257
DECODE, CHUNKS, REPS = 227, (100, 66), 5


def runner_of(path):
    spec = importlib.util.spec_from_file_location("census_model_runner", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def slot_step(runner, batch):
    try:
        return runner._SlotStep(None, batch, NS)
    except TypeError:                       # before PR 46 it took no slot count
        return runner._SlotStep(None, batch)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--parent", default=None, metavar="DIR")
    args = parser.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import trace as tr
    if jax.default_backend() != "tpu":
        sys.exit("tail_census: no TPU - a time from another backend is no device number")

    rng = np.random.default_rng(0)
    seq, pos = np.full(T, S - 1, np.int32), np.zeros(T, np.int32)
    slots = rng.permutation(np.arange(1, NS)).astype(np.int32)
    state = np.zeros((S, 1), np.int32)
    runs = [(40 + s, 1) for s in range(DECODE)] + [(512 * (i == 0), n) for i, n in enumerate(CHUNKS)]
    at = 0
    for s, (first, n) in enumerate(runs):
        seq[at:at + n], pos[at:at + n], state[s, 0] = s, first + np.arange(n), slots[s]
        at += n
    batch = {"token_seq": jnp.asarray(seq), "token_pos": jnp.asarray(pos),
             "block_tables": jnp.zeros((S, 4), jnp.int32), "seq_state": jnp.asarray(state)}
    streams = jnp.asarray(rng.standard_normal((L, T, C)), jnp.bfloat16)
    kernels = jnp.asarray(rng.standard_normal((L, K, C)), jnp.bfloat16)
    biases = jnp.asarray(rng.standard_normal((L, C)), jnp.bfloat16)

    paths = {"tree": os.path.join(ROOT, "deepspeed_tpu/inference/v2/model_runner.py")}
    if args.parent:
        paths["parent"] = os.path.join(args.parent, "deepspeed_tpu/inference/v2/model_runner.py")
    out, bits = {}, {}
    for name, path in paths.items():
        runner = runner_of(path)

        def step(pool, batch, streams, kernels, biases, runner=runner):
            rows = slot_step(runner, batch)

            def layer(carry, x):
                pool, at, total = carry
                acc, pool = runner._conv_with_tail(*x, pool, at, rows)
                return (pool, at + 1, total + acc), None

            start = (pool, jnp.int32(0), jnp.zeros((T, C), jnp.float32))
            return jax.lax.scan(layer, start, (streams, kernels, biases))[0][::2]

        step = jax.jit(step, donate_argnums=0)
        pool = jnp.asarray(np.random.default_rng(7).standard_normal((L, NS, K - 1, C)), jnp.bfloat16)
        pool, total = step(pool, batch, streams, kernels, biases)
        live = np.asarray(state[:len(runs), 0])
        bits[name] = (np.asarray(pool.astype(jnp.float32))[:, live], np.asarray(total))
        capture = tr.Capture()
        capture.start(time.perf_counter)
        for _ in range(REPS):
            pool, total = step(pool, batch, streams, kernels, biases)
        jax.block_until_ready(pool)
        trace = capture.stop(time.perf_counter)
        own, calls = {}, {}
        for events in tr.ops_of(trace).values():
            for op, ns, _ in tr.self_times(events):
                own[op], calls[op] = own.get(op, 0) + ns, calls.get(op, 0) + 1
        ops = sorted(own.items(), key=lambda kv: -kv[1])[:24]
        out[name] = {"busy_ms_a_call": tr.busy_seconds(trace) * 1e3 / REPS,
                     "ops": [[op, ns / 1e3 / REPS, calls[op] // REPS] for op, ns in ops]}
        print(f"{name}: busy {out[name]['busy_ms_a_call']:.3f} ms a call of {L} layers", flush=True)
        for op, us, n in out[name]["ops"]:
            print(f"    {op[:72]:72s} {us:9.1f} us a call  x{n}", flush=True)
    if args.parent:
        out["same_bits"] = bool(all((a == b).all() for a, b in zip(bits["tree"], bits["parent"])))
        print("the same bits as the parent's:", out["same_bits"], flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "tail_census.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
