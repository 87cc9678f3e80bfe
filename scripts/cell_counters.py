#!/usr/bin/env python3
"""One run of a benchmark cell, as `benchmarks/run.py` makes it, and
then the program's counters whose names start with one of the
prefixes given (comma-separated) and, after a traced run, how often
each value of the span attributes given (`span:attribute`,
comma-separated) was recorded and what the spans named with `--totals`
took in all; and, after a traced run, every stretch of more than a
second between two commits (the ends of consecutive `ledger_write`
spans), with the collector's pauses (`gc_pause`) that lie in it.

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
blocks' around it, of whichever peer recorded them, with their
seconds and the longest: `span gc_pause generation=2: 27 (1.634512 s,
longest 0.081234 s)`; then the same of the spans that began between
t0 and t1 alone: `window span gc_pause generation=2: ...`.  The totals are the recorder's
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
    from benchmarks import run, cellrun
    stamps = []
    init = cellrun.Stamps.__init__

    def keep(self, *args, **kwargs):
        init(self, *args, **kwargs)
        stamps.append(self)
    cellrun.Stamps.__init__ = keep
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
    ring = tracing.recorder().recent_spans(limit=1 << 30)
    print_tally(ring, wanted, "")
    if wanted and stamps and stamps[0].snap1 is not None:
        w0, w1 = stamps[0].snap0[2], stamps[0].snap1[2]
        print_tally([sp for sp in ring if w0 < sp["ts"] <= w1], wanted,
                    "window ")
    print_commit_gaps(ring)
    return rc


def print_tally(ring, wanted, prefix: str) -> None:
    tally = {}
    for span in ring:
        for name, attr in wanted:
            if span["name"] == name and attr in span["attrs"]:
                tally.setdefault((name, attr, span["attrs"][attr]),
                                 []).append(span["dur"])
    for (name, attr, value), durs in sorted(tally.items(), key=str):
        print(f"{prefix}span {name} {attr}={value}: {len(durs)} "
              f"({sum(durs):.6f} s, longest {max(durs):.6f} s)",
              flush=True)


def print_commit_gaps(ring, over_s: float = 1.0) -> None:
    ends = sorted(sp["ts"] + sp["dur"] for sp in ring
                  if sp["name"] == "ledger_write")
    pauses = [sp for sp in ring if sp["name"] == "gc_pause"]
    for a, b in zip(ends, ends[1:]):
        if b - a <= over_s:
            continue
        inside = [sp for sp in pauses
                  if sp["ts"] < b and sp["ts"] + sp["dur"] > a]
        longest = max(inside, key=lambda sp: sp["dur"], default=None)
        print(f"commit gap {b - a:.6f} s from {a:.6f}: {len(inside)} "
              f"gc_pause in it, {sum(sp['dur'] for sp in inside):.6f} s"
              + (f", longest {longest['dur']:.6f} s (generation "
                 f"{longest['attrs']['generation']}, thread "
                 f"{longest['thread']})" if longest else ""), flush=True)


if __name__ == "__main__":
    sys.exit(main())
