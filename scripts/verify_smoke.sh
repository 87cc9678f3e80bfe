#!/usr/bin/env bash
# CPU smoke target for the verify + commit pipeline:
#   0. the FMT_RACECHECK=1 canary slice (concurrency guards armed
#      over every retrofitted threaded structure) + the
#      deterministic-clock raft elections + the fault-injection
#      scenario tier (deliver drop/failover, device-error sw
#      fallback + circuit breaker, leader-crash broadcast retry,
#      commit crash-resume) run with the race guards armed
#   1. the mixed-ladder verdict differential (incl. the fused-hash
#      raw-vs-digest check)
#   2. the fused hash->verify A/B
#   3. the commit-pipeline differential: pipelined-vs-sync committed
#      blocks with mixed barrier/non-barrier streams, asserting
#      per-block txflags + final state-hash identity (sw verifier so
#      no XLA compile — the identity assertion runs on every change);
#      since PR 9 the metric also runs a FMT_TRACE-armed arm whose
#      verdicts/fingerprints must match AND whose sub-span totals
#      must explain the stage/await/commit buckets within 10%
# all on the CPU backend with a small batch — a wheel-less container
# can run this in a few minutes, no TPU needed.
#
#   scripts/verify_smoke.sh              # defaults (batch 64)
#   SMOKE_BATCH=256 scripts/verify_smoke.sh
#
# Exit status is nonzero if any verdict differential or the commitpipe
# identity assertion fails (bench.py propagates per-metric rc).
set -euo pipefail
cd "$(dirname "$0")/.."
# 00. the static-analysis gate (fmtlint): the repo's runtime
#     disciplines — knob registry, fault points, span names,
#     registered threads/locks, injectable clocks, swallowed
#     exceptions, JAX hot-path purity, README knob-table drift —
#     checked over the whole package in seconds, BEFORE any test or
#     bench time is spent; any finding fails the smoke
JAX_PLATFORMS=cpu python -m fabric_mod_tpu.analysis
# 0. the race tier's canary slice under FMT_RACECHECK=1: every guard
#    of fabric_mod_tpu/concurrency armed over the retrofitted
#    structures (gossip comm senders, the verify-service flusher, the
#    commit pipeline, deliverclient, election, the gossip drain) plus
#    the deterministic-clock raft election suite — cheap (<1 min) and
#    run on EVERY change, so a reintroduced race or lock inversion
#    fails the smoke before it ever flakes in CI
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly \
    tests/test_racecheck.py tests/test_raft_fakeclock.py
# 0b. the fault/chaos slice, ALSO under FMT_RACECHECK=1 (the
#     permanently-armed lane): one deliver-drop -> typed disconnect +
#     resume, one device-error -> sw-fallback (verdicts bit-identical,
#     breaker open/probe/re-close), one raft leader crash -> broadcast
#     NOT_LEADER retry on ManualClock, plus the commit crash-resume
#     fingerprint differential — every retry/failover thread runs with
#     the race guards armed, so new fault-handling code is race-checked
#     the day it lands
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly \
    tests/test_faults.py
# 0c. the backpressure slice, same permanently-armed FMT_RACECHECK=1
#     lane: token-bucket/watermark units, the knobs-unset blocking-put
#     differential, RESOURCE_EXHAUSTED + retry-after over a real gRPC
#     socket, and the in-process mini broadcast storm (admitted =>
#     committed exactly once, sheds typed) — every admission thread
#     runs with the race guards armed from the day it lands
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_backpressure.py
# 0d. the soak slice, same permanently-armed FMT_RACECHECK=1 lane: a
#     short DETERMINISTIC churn-soak (fixed seed 8, ManualClock-
#     accelerated raft elections, <=60 s) running all six churn-event
#     kinds — peer join + anti-entropy catch-up, ACL revocation
#     cutting a live subscriber, batch reconfig, consenter add/remove,
#     leader kill — under continuous mixed x509+idemix traffic with
#     the background fault plan armed; fingerprint convergence,
#     admitted=>committed-exactly-once, and the thread-leak sweep all
#     gate, and a failure prints the seed + schedule to replay
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_soak.py
# 0e. the trace slice, ARMED (FMT_TRACE=1) on top of the race lane:
#     the span/timeline layer runs live over the commitpipe
#     differential — verdicts and state fingerprints must stay
#     identical with tracing on (tests/test_tracing.py pins the
#     armed-vs-unarmed differential, the cross-thread context
#     propagation, the flight-recorder ring bounds, and the Chrome
#     trace-event export schema), and test_commitpipe re-runs its
#     whole differential with every span seam armed
FMT_TRACE=1 FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_tracing.py tests/test_commitpipe.py
# 0f. the policy slice: the closure walk against a plain statement
#     of cauthdsl.go's rule over seeded trees (incl. the greedy
#     used-flag edge cases), and the batch spine-decode
#     value-identity + fuzz
JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_policy.py tests/test_protos.py
# 0g. the shard slice, FMT_RACECHECK=1 over 8 fake host devices (the
#     conftest forces xla_force_host_platform_device_count=8): slice
#     meshes carve the virtual device set and run the REAL
#     multi-device sharding path, the tagged cross-channel flusher
#     routes per-slice groups, and the sharded-vs-independent
#     differential (per-channel txflags + state fingerprints
#     bit-identical) plus both isolation contracts (injected fault /
#     tamper on channel A never perturbs B; a poisoned per-channel
#     pipe never wedges the shared flusher) run with every race
#     guard armed
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_sharding.py tests/test_parallel.py
# 0h. the staged-ingress slice, FMT_RACECHECK=1: the coalescing lane
#     engine (verdicts identical to the per-envelope path, typed
#     per-envelope NotLeaderError retry/shed, config-vs-staged
#     sequence semantics, per-envelope note_latency) and the
#     group-commit WAL crash contract (torn-tail crop + repair
#     rejoin, N->O(1) fsync collapse) with every race guard armed;
#     the raft suite re-runs with all three ISSUE 16 knobs hot so the
#     pipelined replication path is exercised under the guards too
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_stagedbroadcast.py tests/test_wal_groupcommit.py
FMT_RACECHECK=1 JAX_PLATFORMS=cpu \
    FABRIC_MOD_TPU_WAL_GROUP_COMMIT=1 FABRIC_MOD_TPU_RAFT_PIPELINE=4 \
    python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_raft.py tests/test_raft_fakeclock.py
# 0i. the deliver fan-out slice, FMT_RACECHECK=1: the shared-ring
#     byte-identity differentials (batch projection vs the per-tx
#     generic decoder, shared frames vs the per-stream sender, fuzzed
#     tx bodies), the CommitNotifier wake-exactness + cancellation
#     contracts (one notifier thread, zero tick wakeups), the batched
#     session-ACL once-per-(group, key) counting, the ring-overflow
#     fallback accounting, and the deliver.fanout kill seam — every
#     notifier/stream thread runs with the race guards armed, and the
#     event-service suite re-runs on the fanout-backed server
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_fanout.py tests/test_deliverevents.py
# 0j. the columnar-rwset slice, FMT_RACECHECK=1: the batch tx-body
#     decode identity + corruption fuzz (accepted rows bit-identical
#     to the generic decoder, corrupted rows COUNTED into the per-tx
#     fallback, never a differing verdict), the 60-block vectorized-
#     vs-generic MVCC differential with mixed columnar/materialized
#     routing, the end-to-end committer differential (planes handed
#     over / none staged), commit's routing by what stage handed it
#     (accepted rows never decoded again, refused and private-data
#     rows materialized, the source counter), the incremental-vs-full
#     state-fingerprint oracle, and the durable one-buffered-write
#     batch contract
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_vectormvcc.py
# 0k. the dissemination slice, FMT_RACECHECK=1: RelayTree determinism
#     + reparent-plan units, the 5-peer relay world's frame
#     byte-identity (relayed bytes == a direct orderer pull's) +
#     single-deliver-stream + state-fingerprint convergence, the
#     bounded per-child queue shedding counted-not-lost, gap repair
#     under an armed dissemination.push drop (repair prod ->
#     anti-entropy pull), and the leadership flap (old root torn
#     down, new root relays from its current height) — the relay
#     push thread and every forwarding peer run with the race guards
#     armed from the day the subsystem lands
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_dissemination.py
# 0l. the crash-recovery slice, FMT_RACECHECK=1: the deterministic
#     crash seams behind the soak's PR 20 churn kinds — the
#     peer.ledger.crash fault between blockstore append and state
#     apply (reopen replays statedb-behind-blockstore, incremental
#     fingerprint == full-rescan oracle, crashed peer == uncrashed
#     differential), the orderer.wal.crash fault (synced prefix
#     survives bit-exact, the never-acked in-buffer tail never
#     surfaces), and the physically-torn WAL tail (CRC crop +
#     truncate, post-restart appends land on a clean end)
FMT_RACECHECK=1 JAX_PLATFORMS=cpu python -m pytest -q \
    -p no:cacheprovider -p no:randomly -m 'not slow' \
    tests/test_crash_recovery.py
# CPU XLA compiles of the verify cores run multiple minutes each (the
# persistent compile cache is TPU-oriented); give the worker room.
export FABRIC_MOD_TPU_BENCH_TIMEOUT="${FABRIC_MOD_TPU_BENCH_TIMEOUT:-2400}"
# broadcaststorm: the ingress admission A/B (gated vs ungated 4x
# overload burst, consistency gate: zero admitted-then-lost, sheds
# typed) — host-only, small N, bounded wall time; --staged-batch adds
# the unthrottled staged-vs-unstaged pair on the sw verifier (the
# correctness/consistency gate of the staged engine at smoke scale —
# the batch-ECONOMICS curve needs the device verifier, on the chip)
# commitpipe: the pipelined/sync/depth1/traced differentials
# multichannel: the channel-sharded scale sweep on host-mode slices
# (sw verifiers, no XLA) — every point's per-channel txflags + state
# fingerprints gate bit-identical sharded-vs-N-independent-unsharded
# before any rate lands in the curve
# deliverfanout: the shared fan-out A/B at smoke scale (sweep up to
# 400 subscribers, host-only) — the byte-identity gate + the
# once-per-(block, form) and once-per-(group, key) assertions run on
# every change; the 10k-subscriber point is not a smoke-scale run
# statescale: the vectorized-MVCC state-scale differential at smoke
# sizes (top point 100k keys, host-only) — flags/fingerprint identity,
# the zero-fallback gate, and the stage+mvcc bucket reduction at the
# 100k point run on every change; the 1M point is not smoke-scale
exec python bench.py --cpu --batch "${SMOKE_BATCH:-64}" --reps 1 \
    --metric diffverify --metric hashverify \
    --metric commitpipe --commitpipe-verifier sw \
    --metric broadcaststorm --clients 4 --staged-batch 32 \
    --metric multichannel --multichannel-verifier sw --peers 8 \
    --metric deliverfanout --subscribers 400 \
    --metric statescale --state-keys 2000,20000,100000
