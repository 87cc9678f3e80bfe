#!/usr/bin/env python3
"""One run of a benchmark cell, as `benchmarks/run.py` makes it, and
then the program's counters whose names start with one of the
prefixes given (comma-separated) and, after a traced run, how often
each value of the span attributes given (`span:attribute`,
comma-separated) was recorded and what the spans named with `--totals`
took in all.

    python3 scripts/cell_counters.py \\
        --counters fabric_ledger_mvcc,fabric_validator_body_decode \\
        [--spans mvcc_validate:path,rwset_extract:planes] \\
        [--totals rwset_extract,mvcc_validate] \\
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Chip only, like the run itself.  The result line is `run.py`'s; the
lines after it are `/metrics` samples, process-wide: a cell whose
traffic generator commits the chain on its software peer first (the
Smallbank rounds) reads both peers' counts, the software peer's and
the peer's under test.  The span lines are the traced window's and the
blocks' around it, of whichever peer recorded them:
`span mvcc_validate path=vector: 104`.  The totals are the recorder's
since the peer under test started (its warm-up blocks and the window's):
`total mvcc_validate: 1.234567 s in 53 spans`.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def take_option(name: str) -> list:
    if name not in sys.argv:
        return []
    at = sys.argv.index(name)
    values = sys.argv[at + 1].split(",")
    del sys.argv[at:at + 2]
    return values


def main() -> int:
    prefixes = tuple(take_option("--counters"))
    wanted = [pair.split(":") for pair in take_option("--spans")]
    totalled = take_option("--totals")
    from benchmarks import run
    rc = run.main()
    from fabric_mod_tpu.observability.metrics import default_provider
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith(prefixes):
            print(line, flush=True)
    from fabric_mod_tpu.observability import tracing
    totals = tracing.recorder().totals()
    for name in totalled:
        if name in totals:
            print(f"total {name}: {totals[name]['secs']:.6f} s in "
                  f"{totals[name]['count']} spans", flush=True)
    if wanted:
        from collections import Counter
        tally = Counter()
        for span in tracing.recorder().recent_spans(limit=1 << 30):
            for name, attr in wanted:
                if span["name"] == name and attr in span["attrs"]:
                    tally[name, attr, span["attrs"][attr]] += 1
        for (name, attr, value), n in sorted(tally.items(), key=str):
            print(f"span {name} {attr}={value}: {n}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
