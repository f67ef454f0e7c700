#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
and, traced, ``breakdown``. Progress goes to stderr. Without a TPU, or
with fewer chips than the cell asks for, or in a directory without the
program, it exits non-zero and prints no result.

``--rehearse`` (tests only) drives the same code on the CPU at the debug
size in ``benchmark/tests/data``; it reports no metric.
"""

import argparse
import json
import os
import sys
import time

_T_IMPORT = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def process_age_s():
    """Seconds since this process started (Linux: ``/proc``), so that
    set-up counts the interpreter's own start; 0.0 where that is unknown."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_IMPORT = process_age_s()


class Context:
    """What a runner is given."""

    def __init__(self, bench, cell_name, args):
        from benchmark.harness import device
        self.bench, self.cell_name = bench, cell_name
        self.cell = bench.cell(cell_name)
        self.config = bench.config(self.cell["config"])
        self.traffic = bench.traffic(self.cell["traffic"])
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse, self.keep_trace = bool(args.trace), args.rehearse, args.keep_trace
        self.devices = device.require_devices(self.cell["chips"], self.rehearse)
        if not self.rehearse:
            device.peaks_of(self.devices[0].device_kind)  # an unknown kind of chip is an error
        self.cache_dir = None if self.rehearse else device.enable_compile_cache()
        self.meter = device.CompileMeter()

    def generate(self, vocab):
        make = self.bench.load("generators", self.traffic["kind"], "generate")
        return make(self.traffic, self.seed, self.seconds, vocab)

    def age_at(self, t):
        """The process's age when ``time.perf_counter()`` read ``t``."""
        return _AGE_AT_IMPORT + (t - _T_IMPORT)

    def age(self):
        return self.age_at(time.perf_counter())

    def describe_device(self):
        from benchmark.harness import device
        return device.describe(self.devices)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--root", default=ROOT, help=argparse.SUPPRESS)
    parser.add_argument("--keep-trace", default=None, metavar="DIR",
                        help="copy the profiler's .xplane.pb of a traced run into DIR")
    args = parser.parse_args(argv)

    from benchmark.harness import device, spec, trace
    try:
        import deepspeed_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        sys.exit(f"benchmark: the program is not in this checkout ({e}) - nothing was run")
    bench = spec.Benchmark(args.root)
    if args.seconds is None:
        args.seconds = float(bench.run_seconds)
    ctx = Context(bench, args.workload, args)
    device.log(f"[run] {args.workload} seed {args.seed} seconds {args.seconds} trace {args.trace} "
               f"on {len(ctx.devices)} x {ctx.devices[0].device_kind}; cache {ctx.cache_dir}; "
               f"age {ctx.age():.1f}s")
    run = bench.load("runners", ctx.cell["runner"], "run")(ctx)

    which = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for name, entry in bench.metrics_of(args.workload, which).items():
        if which == "end_to_end":
            value = run["observed"].get(name)
        else:
            value = bench.reader(name)(run, bench.layer_metric(name))
        if value is not None:
            metrics[name] = {"value": float(value), "unit": entry["unit"]}
    result = {"correct": run["correct"], "attempted": run["attempted"], "failed": run["failed"],
              "metrics": {} if args.rehearse else metrics, "device": run["device"]}
    if args.rehearse:
        result["rehearsal"] = {"metrics": metrics}
    if args.trace and run.get("trace") is not None:
        result["device"]["busy_s"] = trace.busy_seconds(run["trace"])
        result["device"]["window_s"] = run["trace_window_s"]
        result["breakdown"] = trace.breakdown(run["trace"])
    result["facts"] = run.get("facts", {})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
