"""The span-name registry: every tracing span, declared here.

Span names are the join key of the whole observability layer — the
per-block timelines, the ``fabric_trace_substage_seconds{stage}``
metric, the bench sub-span attribution that must explain the engine's
stage/await/commit buckets, and the Perfetto export all select spans
BY NAME.  A typo'd name in a new ``tracing.span("...")`` call would
silently fall out of every one of those views; the fmtlint
``span-names`` rule requires each literal to be declared here (and
each declaration to be used by a production seam), so the set of
stages is a reviewed, documented surface instead of an accident of
string literals.
"""
from __future__ import annotations

from typing import Set

# Keep sorted; the lint rule cross-checks both directions.
DECLARED_SPANS: Set[str] = {
    "body_decode",
    "broadcast.handle",
    "broadcast.stage",
    "broadcast.submit",
    "commit_wait_staged",
    "der_marshal",
    "device_dispatch",
    "device_enqueue",
    "dispatch_chunk",
    "fanout.materialize",
    "fingerprint",
    "gc_pause",
    "gossip.drain",
    "ledger_write",
    "mcs_verify",
    "mvcc",
    "mvcc_validate",
    "mvcc_vector",
    "policy_finish",
    "raft.replicate",
    "recv",
    "relay.push",
    "relay.repair",
    "rwset_extract",
    "shard.dispatch",
    "stage_wait_block",
    "stage_wait_slot",
    "submit_wait",
    "unpack",
    "verdict_await",
    "verify.flush",
    "verify.resolve",
    "wal.sync",
}

# The spans that are WAITING, not work: a thread parked on a queue, a
# condition or the device's verdict.  Whatever reads the spans to say
# what the host was doing (the benchmark's naming of the device's idle
# time) ranks these below every working span.
WAIT_SPANS: Set[str] = {
    "commit_wait_staged",
    "stage_wait_block",
    "stage_wait_slot",
    "submit_wait",
    "verdict_await",
}
assert WAIT_SPANS <= DECLARED_SPANS


def is_declared(name: str) -> bool:
    return name in DECLARED_SPANS
