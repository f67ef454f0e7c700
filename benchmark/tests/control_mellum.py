#!/usr/bin/env python3
"""``control.py``'s recipe on the ``mellum2-12b-ep4-4l`` configuration: the
check's reading and the controls', per seed, on the chips at the size the cell
runs:

    python3 benchmark/tests/control_mellum.py --seed 3000005801 [--seed ...] [--no-grad-norm]

builds the cell's trainer from each seed, runs the first step (the state), and
prints per seed what ``correct`` reads of it (``runners/train_mellum.py``: the
per-position NLL by the loss's own path, step 2's loss and gradient norm, the
attention half of a sliding layer, of the full layer and one expert half each
alone, against ``harness/reference_mellum.py``) and then of the controls:
**the reference with one fault at a time**, each of which has to come out as
not correct by at least one of the cell's limits:

``window_as_full``      a sliding layer sees every key before it
``full_as_window``      the full layer sees its 1024 newest keys alone
``yarn_left_out``       the full layer rotates by the plain table, no attention factor
``topk_not_normalised`` the picks' probabilities as they are, not divided by their sum
``one_pick_fewer``      a token's eighth pick adds nothing
``one_rank_left_out``   the last quarter of the experts (one rank of four) adds nothing
``float8``              every matrix at float8 e4m3's values, one scale a tensor, op by
                        op, the arithmetic float32 (the nearest precision below the
                        configuration's bf16)

The program's side is read once a seed and compared with each; the streams
the halves are given are the published model's, never a control's. A
benchmark run never runs this; ``test_mellum_cell.py`` keeps it at debug size.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_mellum as reference  # noqa: E402


def measure(bench, cell_name, seed, rehearse, grad_norm=True, controls=reference.FAULTS,
            devices=None, log=print):
    """→ {"program": the check's table, "controls": {fault: table}} for one seed."""
    os.environ["DS_SEED"] = str(seed % (2 ** 31 - 1))
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.harness import device
    cell = bench.cell(cell_name)
    runner = bench.load("runners", cell["runner"], "run").__globals__

    class Ctx:
        pass
    ctx = Ctx()
    ctx.bench, ctx.cell, ctx.rehearse, ctx.seed = bench, cell, rehearse, seed
    ctx.config, ctx.traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    ctx.devices = devices or device.require_devices(cell["chips"], rehearse)
    config = ctx.config
    ids = bench.load("generators", ctx.traffic["kind"], "generate")(
        ctx.traffic, seed, 0.0, config["model"]["vocab_size"])["ids"]
    engine, model, cfg = runner["build"](ctx, ids)
    feed = (ids[None], ids[None])
    first = float(engine.train_batch(batch=feed))
    placed = jax.device_put(ids, NamedSharding(engine.mesh, P("expert")))
    streams = runner["given_streams"](engine.params, placed, config)
    system = runner["system_readings"](engine, model, cfg, ids, streams, seed)
    system["streams"] = streams
    limits = config["reference"]

    def read(faults):
        """One reading of the reference → (the check's table but for step 2's loss and
        gradient norm, the reference's own two); its arrays are let go at once."""
        ref = runner["reference_readings"](engine.params, placed, config, system,
                                           faults=frozenset(faults), gnorm=grad_norm)
        return runner["compare"](system, ref, limits)[0], ref["loss"], ref.get("grad_norm")

    tables = {"": read(())}
    for fault in controls:
        tables[fault] = read((fault,))
        log(f"[control] {fault} read")
    loss = float(engine.train_batch(batch=feed))
    gnorm = float(engine.global_grad_norm)
    out = {"seed": seed, "first_loss": first, "controls": {}}
    for fault, (table, ref_loss, ref_gnorm) in tables.items():
        table["loss_abs"] = [abs(loss - ref_loss), limits["tolerance"]]
        if ref_gnorm is not None:
            table["grad_norm_rel"] = [abs(gnorm - ref_gnorm) / ref_gnorm,
                                      limits["grad_norm_tolerance"]]
        entry = {"agrees": all(v < limit for v, limit in table.values()), "check": table,
                 "over": sorted(k for k, (v, limit) in table.items() if not v < limit)}
        if fault:
            out["controls"][fault] = entry
        else:
            out["program"] = entry
    engine.destroy()
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, action="append", required=True)
    parser.add_argument("--workload", default="mellum2-12b-moe8k-x4")
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--no-grad-norm", action="store_true",
                        help="leave the gradient's norm out (its backward is most of a control's time)")
    parser.add_argument("--control", action="append", choices=reference.FAULTS,
                        help="only these controls (default: every one)")
    parser.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    from benchmark.harness import spec
    bench = spec.Benchmark(args.root)
    ok = True
    for seed in args.seed:
        found = measure(bench, args.workload, seed, args.rehearse, not args.no_grad_norm,
                        tuple(args.control or reference.FAULTS),
                        log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps(found), flush=True)
        ok = ok and found["program"]["agrees"] and not any(
            c["agrees"] for c in found["controls"].values())
    print("every control fails and the program agrees" if ok
          else "NOT SO: a control agrees or the program does not")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
