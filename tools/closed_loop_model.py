#!/usr/bin/env python3
"""A model of a closed-loop serving cell, for the sandbox: no chip, no JAX.

    python3 tools/closed_loop_model.py [--traffic longdoc] [--seeds 48]
                                       [--mixed-ms 70.6] [--decode-ms 19.5]

It deals the benchmark generator's own decks (``benchmark/generators/
closed_loop.py``, the seeds the runs take) to a scheduler that plans as
``DynamicSplitFuseScheduler._plan`` does - decode rows first, then prompt
chunks in order of arrival, a decode burst when no prompt waits - with two
step times read on the chip, and lets a client count the window by
``runners/serve.py``'s rule (a prompt laid evenly between its sending and
its first token) and by ``runners/serve_sala.py``'s (between the first
token the client saw before the prompt's own and that one), beside what
the engine computed inside the window. It says what a seed's order of the
deck alone does to a cell's ``serve_tok_s``, which no chip run can
separate from the machine's noise: PERF.md section 6 (PR 34) has what it
read for ``minicpm-sala-longdoc`` and how close it came to the chip's
runs. It knows nothing of stalls, of the gate's queue beyond the block
commitment, or of a step's time changing with its rows: a count, never a
rate to report.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.generators import closed_loop  # noqa: E402


def simulate(params, engine, seed, seconds, mixed_s, decode_s, vocab=73448, max_burst=16):
    """One run → tokens a second inside the window by both rules and as
    computed, the requests that ended in it and the decode bursts."""
    traffic = closed_loop.generate(params, seed, seconds, vocab)
    deck = [(len(d["prompt"]), d["max_new"]) for d in traffic["deck"]]
    budget, max_seqs = engine["token_budget"], engine["max_ragged_sequence_count"]
    block, blocks = engine["kv_block_size"], engine["num_kv_blocks"] - 1
    open_at = traffic["preroll_s"]
    close_at = open_at + seconds
    deadline = close_at + traffic["tail_s"]
    now, dealt, committed, generated, computed, bursts = 0.0, 0, 0, 0, 0.0, 0
    queue, active, done = [], [], []

    def submit(client, max_new=None):
        nonlocal dealt
        prompt, answer = deck[dealt % len(deck)]
        dealt += 1
        queue.append({"prompt": prompt, "max_new": answer if max_new is None else max_new,
                      "sent": now, "first": None, "fed": 0, "out": 0, "client": client,
                      "ended": None})

    for c in range(traffic["clients"]):
        submit(c, traffic["first_max_new"][c])
    while True:
        while queue and len(active) < max_seqs:            # the gate: worst-case blocks
            need = -(-(queue[0]["prompt"] + queue[0]["max_new"]) // block)
            if committed + need > blocks:
                break
            committed += need
            queue[0]["need"] = need
            active.append(queue.pop(0))
        decoding = [r for r in active if r["fed"] >= r["prompt"]]
        waiting = [r for r in active if r["fed"] < r["prompt"]]
        chunks, emitted = [], []
        if not waiting:
            k = min(max_burst, min(r["max_new"] - r["out"] for r in decoding))
            k = 1 << (k.bit_length() - 1) if k >= 2 else 1
            bursts += k >= 2
            took = k * decode_s
            emitted = [(r, k) for r in decoding]
        else:
            room = budget - len(decoding)
            for r in waiting:
                if room <= 0 or len(decoding) + len(chunks) >= max_seqs:
                    break
                chunks.append((r, min(room, r["prompt"] - r["fed"])))
                room -= chunks[-1][1]
            rows = len(decoding) + sum(n for _, n in chunks)
            took = mixed_s if rows > max_seqs else decode_s
            emitted = [(r, 1) for r in decoding]
        began, now = now, now + took
        inside = max(0.0, min(now, close_at) - max(began, open_at)) / took
        computed += inside * sum(n for _, n in emitted)
        for r, n in chunks:
            r["fed"] += n
            computed += inside * n
            if r["fed"] >= r["prompt"]:
                emitted.append((r, 1))                     # the first token rides the last chunk
        for r, n in emitted:
            r["first"] = now if r["first"] is None else r["first"]
            r["out"] += n
            generated += n if open_at <= now < close_at else 0
            if r["out"] >= r["max_new"]:
                r["ended"] = now
        for r in [r for r in active if r["ended"] is not None]:
            active.remove(r)
            committed -= r["need"]
            done.append(r)
            submit(r["client"])
        if now >= close_at and (now >= deadline or all(
                r["first"] is not None for r in active + queue if r["sent"] < close_at)):
            break

    def laid(r, begun):
        if r["first"] <= begun:
            return r["prompt"] if open_at <= r["first"] < close_at else 0
        return r["prompt"] * max(0.0, min(r["first"], close_at) - max(begun, open_at)) \
            / (r["first"] - begun)

    seen = sorted((r for r in done + active if r["first"] is not None), key=lambda r: r["first"])
    by_wait = sum(laid(r, r["sent"]) for r in seen)
    by_turn = sum(laid(r, max(r["sent"], before["first"]) if before else r["sent"])
                  for before, r in zip([None] + seen, seen))
    return {"by_wait": (generated + by_wait) / seconds, "by_turn": (generated + by_turn) / seconds,
            "computed": computed / seconds, "bursts": bursts,
            "ended": sum(open_at <= r["ended"] < close_at for r in done)}


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--traffic", default="longdoc")
    parser.add_argument("--config", default="minicpm-sala-16l")
    parser.add_argument("--seeds", type=int, default=48)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--mixed-ms", type=float, default=70.6, help="a step that holds a prompt chunk")
    parser.add_argument("--decode-ms", type=float, default=19.5, help="a decode-only step")
    args = parser.parse_args()
    with open(os.path.join(ROOT, "benchmark", "traffic", f"{args.traffic}.json")) as f:
        params = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs", f"{args.config}.json")) as f:
        engine = json.load(f)["engine"]
    runs = [simulate(params, engine, 3000010000 + 101 * i, args.seconds,
                     args.mixed_ms / 1e3, args.decode_ms / 1e3) for i in range(args.seeds)]
    for key in ("by_wait", "by_turn", "computed"):
        values = [r[key] for r in runs]
        sets = [spread(values[i:i + 6]) for i in range(0, len(values) - 5, 6)]
        print(f"{key:9s} median {statistics.median(values):7.0f}  range {min(values):.0f}-{max(values):.0f}"
              f"  spread {spread(values):.3f}  sets of six {' '.join(f'{s:.3f}' for s in sets)}")
    print(f"requests ended a window {min(r['ended'] for r in runs)}-{max(r['ended'] for r in runs)}; "
          f"runs whose queue ran dry {sum(r['bursts'] > 0 for r in runs)} of {len(runs)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
