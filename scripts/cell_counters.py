#!/usr/bin/env python3
"""One run of a benchmark cell, as `benchmarks/run.py` makes it, and
then the program's counters whose names start with one of the
prefixes given (comma-separated).

    python3 scripts/cell_counters.py \\
        --counters fabric_ledger_mvcc,fabric_validator_body_decode \\
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Chip only, like the run itself.  The result line is `run.py`'s; the
lines after it are `/metrics` samples, process-wide: a cell whose
traffic generator commits the chain on its software peer first (the
Smallbank rounds) reads both peers' counts, the software peer's and
the peer's under test.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    at = sys.argv.index("--counters")
    prefixes = tuple(sys.argv[at + 1].split(","))
    del sys.argv[at:at + 2]
    from benchmarks import run
    rc = run.main()
    from fabric_mod_tpu.observability.metrics import default_provider
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith(prefixes):
            print(line, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
