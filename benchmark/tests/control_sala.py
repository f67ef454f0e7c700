#!/usr/bin/env python3
"""``control.py``'s recipe on the ``minicpm-sala-16l`` configuration: the
program's readings and the controls', per seed, on the chip at the size
the cell runs:

    python3 benchmark/tests/control_sala.py --seed 3000001201 [--seed ...] [--control 2]

builds the configuration's engine from each seed (one at a time, with a
pool just large enough for the check's sequences) and prints per seed what
``correct`` reads — of the served logits against the float32 reference
(``harness/reference_sala.py``; ``runners/serve_moonlight.py``
``summarize``), and of the selection and the sparse layer alone
(``runners/serve_sala.py`` ``summarize_sparse_layer``) — and, for the
first ``--control`` seeds, of two controls that have to come out as not
correct: ``float8``, that reference with every matrix of a layer, the
embedding rows, the head and the residual stream between layers rounded to
float8 e4m3 with one scale a tensor, the arithmetic float32 (it moves
every logit row); and ``wrong_blocks``, the served sparse layer given a
selection that keeps the forced blocks and, in place of the blocks the
scores chose, reads the earliest blocks after the first — as many blocks,
ascending, the query's own last, so that nothing but *which* blocks
differs (it moves the mixer's output at every row that selects, and no
row reads the reference's blocks). A benchmark run never runs this;
``test_sala_cell.py`` keeps it at debug size.
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

from benchmark.harness import reference_sala as reference  # noqa: E402
from benchmark.tests.control import _rounded  # noqa: E402


def rows_rounded(params, ids, positions, model, dtype):
    """``reference_sala.rows_at``'s rows in the next precision down."""
    z = reference.sizes(model)
    sparse_fn, linear_fn = reference._layer_fns(tuple(sorted(z.items())))
    round_tree = jax.jit(lambda tree: jax.tree.map(lambda w: _rounded(w, dtype), tree))
    rows = []
    for i in range(ids.shape[0]):
        prompt = int(positions[i][0]) + 1
        sparse_from = jnp.int32(0 if prompt >= z["dense_len"] else z["dense_len"] - 1)
        h = _rounded(model["scale_emb"]
                     * params["model"]["embed_tokens"][ids[i]].astype(jnp.float32), dtype)
        for position, mixer in enumerate(model["mixer_types"]):
            lp = round_tree(reference.layer_tree(params, model, position))
            if mixer == reference.SPARSE:
                h = sparse_fn(lp, h, sparse_from)[0]
            else:
                h = linear_fn(lp, h, jnp.asarray(reference.log_decay(model, position)))
            h = _rounded(h, dtype)
        rows.append(h[jnp.asarray(positions[i])])
    return jnp.stack(rows)


def wrong_blocks(real, ctx, q, kb, layer_heads):
    """A stand-in for ``model_runner._sala_select``: the program's table
    with every block the scores chose replaced by the earliest blocks
    after the ``init_blocks`` first — the same count, ascending, the local
    window and the query's own block kept."""
    cfg = ctx.cfg
    tables = real(ctx, q, kb, layer_heads)                                # [T, Hkv, W]
    MB = ctx.batch["block_tables"].shape[1]
    blocks = jnp.arange(MB)[None, None, :]
    own = ctx.own[:, None, None]
    local = cfg.sparse_window_size // cfg.sparse_block_size
    forced = (blocks < cfg.sparse_init_blocks) | ((blocks <= own) & (blocks > own - local))
    n_forced = forced.sum(axis=-1, keepdims=True)
    others = ctx.counts[..., None] - n_forced                             # chosen by score
    member = forced | ((blocks >= cfg.sparse_init_blocks)
                       & (blocks < cfg.sparse_init_blocks + others) & (blocks <= own))
    wrong = jnp.sort(jnp.where(member, blocks, MB), axis=-1)[..., :tables.shape[-1]]
    return jnp.where(ctx.dense[:, None, None], tables, wrong).astype(tables.dtype)


def measure(bench, config, seed, rehearse, control=True):
    """→ what ``correct`` reads of the program and, with ``control``, of
    the two controls, against the same reference and margins."""
    runner = bench.load("runners", "serve_sala", "run").__globals__
    check = runner["_check"]()
    config = runner["with_sparse"](config)
    steps, block = config["reference"]["decode_steps"], config["engine"]["kv_block_size"]
    need = sum(-(-(n + steps) // block) + 1 for n in check.sample_lengths(config["reference"]))
    config = dict(config, engine=dict(config["engine"], num_kv_blocks=need + 1))
    engine = runner["build_engine"](config, seed, rehearse)
    params, model = engine.params, config["model"]
    out = {"seed": seed,
           "attention_impls": {str(k): v for k, v in engine.attention_impls.items()}}
    tapped = runner["Tapped"](model["mixer_types"].count(reference.SPARSE))
    check.reference_moonlight = tapped
    try:
        got = runner["served_logits"](engine, config, check.reference_sample(config, seed)[0])
        errors, margins, _ = check.reference_errors(
            params, config, seed, lambda first, ids, positions: lambda i: got[first + i])
    finally:
        check.reference_moonlight = reference
    finite = np.where(np.isfinite(margins), margins, 1e9)
    out["program"] = dict(check.summarize(errors, finite, config["reference"]),
                          min=float(errors.min()))
    out["program_by_position"] = [[round(float(e), 5) for e in row] for row in errors]
    out["margins"] = [[round(float(m), 7) for m in row] for row in finite]
    sparse_from = runner["first_sparse_from"](config)

    def alone(name, select):
        e, same, m = runner["sparse_layer_readings"](
            config, tapped.taps, lambda layer, x: runner["served_sparse_layer"](
                engine, config, layer, x, sparse_from, select=select))
        out[name] = dict(runner["summarize_sparse_layer"](e, same, m, config["reference"]),
                         min=float(e.min()),
                         min_selecting=float(e[np.isfinite(m)].min()),
                         median_selecting=float(np.median(e[np.isfinite(m)])),
                         max_not_selecting=float(e[~np.isfinite(m)].max())
                         if (~np.isfinite(m)).any() else None)
        # the errors of the rows that select, and the agreement, by the reference's margin
        sel = np.isfinite(m)
        edges = [0, 1e-6, 1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 1e9]
        out[name]["by_margin"] = [
            {"margin_from": lo, "rows": int(((m >= lo) & (m < hi) & sel).sum()),
             "same": float(same[(m >= lo) & (m < hi) & sel].mean()),
             "err_median": float(np.median(e[(m >= lo) & (m < hi) & sel])),
             "err_max": float(e[(m >= lo) & (m < hi) & sel].max())}
            for lo, hi in zip(edges[:-1], edges[1:]) if ((m >= lo) & (m < hi) & sel).any()]

    alone("program_sparse_layer", None)
    if control:
        alone("wrong_blocks_sparse_layer", wrong_blocks)
        head8 = {"model": {"norm": params["model"]["norm"]},
                 "lm_head": {"kernel": _rounded(params["lm_head"]["kernel"], jnp.float8_e4m3fn)}}

        def float8(first, ids, positions):
            rows = rows_rounded(params, ids, positions, model, jnp.float8_e4m3fn)
            return lambda i: reference.head_at(head8, rows[i:i + 1], model)[0]

        errors8, _, _ = check.reference_errors(params, config, seed, float8)
        out["float8"] = dict(check.summarize(errors8, finite, config["reference"]),
                             min=float(errors8.min()))
    engine.destroy()
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", default="minicpm-sala-16l")
    parser.add_argument("--seed", type=int, action="append", required=True)
    parser.add_argument("--control", type=int, default=2,
                        help="run the controls for the first N seeds")
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
