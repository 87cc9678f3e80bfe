#!/usr/bin/env python3
"""Many seeds of one cell in ONE process, with a short window: the
readings that `correct`'s limits are set from, and the control.

    python3 benchmarks/seeds.py --workload <cell> --seeds 1,2,3
                                --seconds 6 [--control]

One process pays the load of the device programs once.  Each seed is a
whole run of the cell (`cellrun.run_cell`: new network, new backlog,
new peer, window, comparison); one line per seed is printed, and the
last line sums them up.

`--control` computes the device programs in the nearest precision
below the one the program states (`Precision.HIGH`, three bf16 passes,
in place of `HIGHEST`, six: `ops/limbs9.set_precision_mode("high")`,
the program's own switch, which a later PR would be tempted by).  The
control has to come out as NOT correct on every seed.  It is a
different program and compiles anew; it never runs in the benchmark's
own runs.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchmarks.manifest import Cell
    cell = Cell(args.workload)
    from benchmarks.cellrun import find_chip, run_cell
    device = find_chip(cell.chips)
    if device is None:
        return 1
    from fabric_mod_tpu.ops.compilecache import enable_compile_cache
    enable_compile_cache()
    if args.control:
        from fabric_mod_tpu.ops import limbs9
        limbs9.set_precision_mode("high")

    verdicts = []
    for seed in seeds:
        t0 = time.perf_counter()
        try:
            result = run_cell(cell, seed, args.seconds, False, device,
                              print, t0, strict_warm=not args.control)
        except Exception as e:          # a control may crash: it failed
            print(json.dumps({"seed": seed, "correct": False,
                              "crashed": repr(e)}), flush=True)
            verdicts.append(False)
            continue
        off = {k: v["value"] for k, v in result["compared"].items()
               if v["value"] > v["limit"]}
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "attempted": result["attempted"], "over_limit": off,
            "metrics": {k: v["value"]
                        for k, v in result["metrics"].items()},
            "seconds": time.perf_counter() - t0}), flush=True)
        verdicts.append(result["correct"])
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "seeds": len(seeds), "correct": sum(verdicts),
                      "not_correct": len(verdicts) - sum(verdicts)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
