#!/usr/bin/env python3
"""One cell's set-up as a table, from the program's own records (PR 50).

    chiprun -- python3 tools/setup_table.py --workload solar-open2-reason --seed N [--seconds 10]
                                            [--root _checkout/parent] [--out chiprun_out/x.json]

Runs the cell in this process (``benchmark/run.py``'s ``main``) and then
reads ``deepspeed_tpu/utils/tracing.py``: the process's age at the engine's
constructor, its ``setup`` record by phase, every engine record up to the
window's opening by program (records, wall time, ``compile_ns``), the build
table (own trace / lower / backend time, cache hits and misses, the three
functions traced longest) with its ``outside`` row, beside the run's own log
ages (``engine built``, ``reference check``, ``warm``), the traffic's
pre-roll and the line's ``setup_s`` - and says what of ``setup_s`` the rows
leave unaccounted for. ``--root DIR`` runs another checkout (a parent: it has
no build table, and the table says so). A cell's usual run time.
"""

import argparse
import contextlib
import io
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", required=True)
    parser.add_argument("--seconds", default="10")
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--out", default=None)
    parser.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    root = os.path.abspath(args.root)
    path = os.path.abspath(args.out) if args.out else os.path.join(
        ROOT, "chiprun_out", f"setup_table.{args.workload}.{args.seed}.json")
    os.chdir(root)
    sys.path[:0] = [root, ROOT]     # the program is this tree's where --root has none
    from benchmark import run as bench_run
    from benchmark.harness import device
    said, tell = [], device.log

    def log(message):
        said.append(message)
        tell(message)

    device.log = log        # the runners take it when they are loaded
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench_run.main(["--workload", args.workload, "--seed", args.seed, "--seconds",
                        args.seconds, "--trace", "0", "--root", root]
                       + ["--rehearse"] * args.rehearse)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    from deepspeed_tpu.utils import tracing
    setup_s = (line.get("rehearsal") or line)["metrics"]["setup_s"]["value"]
    # the window's opening on the records' clock (run.py: age_at)
    open_ns = int((setup_s - bench_run._AGE_AT_IMPORT + bench_run._T_IMPORT) * 1e9)
    ages = {}
    for message in said:
        found = re.match(r"\[serve\] (engine built|reference check|warm)\b.* at ([0-9.]+)s;", message)
        if found:
            ages[found.group(1)] = float(found.group(2))
    snap = tracing.snapshot()
    counters = line["facts"].get("gateway_counters", {})
    table = {"workload": args.workload, "seed": int(args.seed), "root": root, "setup_s": setup_s,
             "compile_meter": line["facts"].get("compile_meter"), "log_ages_s": ages,
             "correct": line["correct"], "gateway_counters": counters,
             "metrics": {k: v["value"] for k, v in line["metrics"].items()}}
    table["builds"] = snap.get("builds")
    from benchmark.harness import spec
    bench = spec.Benchmark(root)
    table["preroll_s"] = bench.traffic(bench.cell(args.workload)["traffic"]).get("preroll_s", 0.0)
    # kept beside the ring, which a long window turns over
    setups = [r.as_dict() for r in getattr(tracing.RECORDER, "setups", ())]
    programs = [r for r in snap["steps"] if r["kind"] not in ("pump", "setup")]
    engine = setups[0]["engine"] if setups else programs[0]["engine"]
    before = setups + [r for r in programs if r["engine"] == engine and r["end_ns"] <= open_ns]
    by_program = {}
    for r in before:
        row = by_program.setdefault(f"{r['kind']}:{r['program']}",
                                    {"records": 0, "wall_s": 0.0, "compile_s": 0.0})
        row["records"] += 1
        row["wall_s"] += (r["end_ns"] - r["start_ns"]) / 1e9
        row["compile_s"] += r["compile_ns"] / 1e9
    table["records_before_window"] = by_program
    table["counted_once"] = {
        "sum_compile_s": sum(row["compile_s"] for row in by_program.values()),
        "sum_wall_s": sum(row["wall_s"] for row in by_program.values())}
    if setups:
        first = setups[0]
        age = (first["process_age_ns"] or 0) / 1e9
        wall = (first["end_ns"] - first["start_ns"]) / 1e9
        table["process_age_at_constructor_s"] = age
        table["setup_records"] = [
            {"program": r["program"], "wall_s": (r["end_ns"] - r["start_ns"]) / 1e9,
             "compile_s": r["compile_ns"] / 1e9,
             "phases_s": {name: (exit_ - enter) / 1e9 for name, enter, exit_ in r["phases"]}}
            for r in setups]
        table["counted_once"]["constructor_entry_to_window_s"] = (open_ns - first["start_ns"]) / 1e9
        table["summary"] = tracing.setup_summary(engine)
        if len(ages) == 3:
            # setup_s, piece by piece; the remainder is what no row names
            table["adds_up"] = {
                "process_age_at_constructor_s": age, "setup_record_s": wall,
                "constructor_to_engine_built_log_s": ages["engine built"] - age - wall,
                "reference_check_s": ages["reference check"] - ages["engine built"],
                "warm_up_s": ages["warm"] - ages["reference check"],
                "preroll_s": table["preroll_s"],
                "remainder_s": setup_s - ages["warm"] - table["preroll_s"]}
    else:
        table["note"] = "this checkout's recorder keeps no setup record and no build table"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(table, f, indent=1)
    print_table(table)
    return 0


def print_table(table):
    print(f"{table['workload']} seed {table['seed']} ({table['root']}): setup_s "
          f"{table['setup_s']:.2f}, meter {table['compile_meter']}, log ages {table['log_ages_s']}")
    if "setup_records" in table:
        print(f"  process age at the constructor {table['process_age_at_constructor_s']:.2f} s")
        for rec in table["setup_records"]:
            phases = " ".join(f"{name.rsplit('.', 1)[-1]} {s:.2f}"
                              for name, s in rec["phases_s"].items())
            print(f"  setup record {rec['program']}: {rec['wall_s']:.2f} s ({phases}; "
                  f"compiling {rec['compile_s']:.2f})")
    else:
        print("  " + table["note"])
    for name, row in table["records_before_window"].items():
        print(f"  records {name:24s} x{row['records']:<5d} wall {row['wall_s']:8.2f} s "
              f"compile_ns {row['compile_s']:7.2f} s")
    for row in table["builds"] or ():
        most = ", ".join(f"{name} x{times} {own / 1e9:.2f} (whole {whole / 1e9:.2f})"
                         for name, times, own, whole in row["functions"][:3])
        print(f"  build {row['engine']}:{row['kind']}:{row['program']:10s} builds {row['builds']} trace "
              f"{row['trace_ns'] / 1e9:6.2f} lower {row['lower_ns'] / 1e9:6.2f} backend "
              f"{row['backend_ns'] / 1e9:6.2f} compiles {row['compiles']} hits {row['hits']} "
              f"misses {row['misses']}; {most}")
    print(f"  counted once: {table['counted_once']}")
    print(f"  adds up: {table.get('adds_up')}")
    print(f"  counters: { {k: v for k, v in table['gateway_counters'].items() if 'setup' in k or 'compile' in k or k == 'programs_built'} }")


if __name__ == "__main__":
    sys.exit(main())
