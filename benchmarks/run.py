#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n>
                              --seconds <s> --trace <0|1>

One process, JAX touched only here, no children.  It exits non-zero
at once, with no result line, unless JAX reports a TPU and as many
chips as the cell asks for.  Earlier lines are free text; the last
line of standard output is the result object.
"""
import time
T_START = time.perf_counter()          # set-up is counted from here

import argparse                        # noqa: E402
import json                            # noqa: E402
import os                              # noqa: E402
import sys                             # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def say(msg: str) -> None:
    print(msg, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()

    from benchmarks.manifest import Cell
    cell = Cell(args.workload)
    if args.trace:
        # the program's span ring (read at import) has to hold every
        # dispatch of the window for the counts taken from its spans
        os.environ.setdefault("FMT_TRACE_SPANS", "400000")

    from benchmarks.cellrun import find_chip, run_cell
    device = find_chip(cell.chips)
    if device is None:
        return 1

    from fabric_mod_tpu.ops.compilecache import enable_compile_cache
    say(f"{args.workload}: seed {args.seed}, {args.seconds}s, trace "
        f"{args.trace}, device {device}, compile cache "
        f"{enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, say, T_START)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
