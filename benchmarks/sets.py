#!/usr/bin/env python3
"""Sets of runs of one cell, as the driver makes them, and the spread
a bound is set from.

    python3 benchmarks/sets.py --workload <cell> --seeds 11,12,13,14,15,16
                               [--sets 2] [--trace 0] [--out file.jsonl]

Each run is the benchmark's own command in a process of its own (this
parent never touches JAX, so the chip is free for each child in turn),
at `run_seconds`.  Every set uses the same seeds.  Prints each run's
result line, then per metric and set the median and the spread: the
distance between the first and third quartile (`statistics.quantiles`,
n=4) as a share of the median.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            cmd = bench["command"] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(args.trace)]
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                  text=True)
            lines = proc.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1])
            except (IndexError, ValueError):
                print(f"set {k} seed {seed}: rc {proc.returncode}, no "
                      f"result line; output ends:\n" + "\n".join(lines[-15:]),
                      flush=True)
                continue
            record = {"set": k, "seed": seed, "rc": proc.returncode,
                      **result}
            print(json.dumps(record), flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(record) + "\n")
            runs.append(result)
        sets.append(runs)
    names = sorted({n for runs in sets for r in runs for n in r["metrics"]})
    for name in names:
        for k, runs in enumerate(sets):
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r["metrics"]]
            if len(vals) >= 2:
                print(f"{args.workload} {name} set {k}: n {len(vals)} "
                      f"median {statistics.median(vals):.6g} spread "
                      f"{100 * spread(vals):.3f}% values "
                      f"{[round(v, 4) for v in vals]}", flush=True)
    wrong = [r for runs in sets for r in runs if not r["correct"]]
    print(f"{args.workload}: {sum(len(r) for r in sets)} runs, "
          f"{len(wrong)} not correct", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
