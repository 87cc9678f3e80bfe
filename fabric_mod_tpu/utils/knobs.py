"""The typed knob registry: every env tunable, declared once.

(reference: the role Viper + config structs play in the Go stack —
every tunable has a declared name, type, default, and doc, so a typo'd
override fails visibly instead of silently running defaults.  Our
knobs were stringly-typed `os.environ` reads scattered across 15+
modules; the fmtlint `knobs` rule now requires every
``FABRIC_MOD_TPU_*`` / ``FMT_*`` access to go through this registry,
and the README knob table is cross-checked against it so the docs
cannot drift.)

Reading an UNDECLARED knob raises ``KeyError`` at call time — the
static mirror is the fmtlint rule that flags undeclared knob literals
at lint time.  Parsing is built on :mod:`fabric_mod_tpu.utils.env`
(malformed values fall back to the default, never crash at import).

Usage::

    from fabric_mod_tpu.utils import knobs
    depth = knobs.get_int("FABRIC_MOD_TPU_INFLIGHT")      # registry default
    k     = knobs.get_int("FABRIC_MOD_TPU_BREAKER_K", 3)  # caller override
    if knobs.get_bool("FABRIC_MOD_TPU_FUSED_HASH"):
        ...

Boolean semantics are uniform: set-and-not-("", "0") is true.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Union

from fabric_mod_tpu.utils.env import env_float, env_int

Default = Union[int, float, str, bool, None]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared tunable: the registry row the README table and the
    fmtlint cross-checks are generated from."""
    name: str
    type: str                  # "int" | "float" | "str" | "bool"
    default: Default           # documented default (None = unset/off)
    doc: str


_REGISTRY: Dict[str, Knob] = {}


def declare(name: str, type: str, default: Default, doc: str) -> Knob:
    if type not in ("int", "float", "str", "bool"):
        raise ValueError(f"knob {name}: unknown type {type!r}")
    if name in _REGISTRY:
        raise ValueError(f"knob {name} declared twice")
    knob = Knob(name, type, default, doc)
    _REGISTRY[name] = knob
    return knob


def declared() -> Dict[str, Knob]:
    """Name -> Knob view of the registry (for the lint cross-checks
    and the generated README table)."""
    return dict(_REGISTRY)


def is_declared(name: str) -> bool:
    return name in _REGISTRY


def _lookup(name: str, want: str) -> Knob:
    knob = _REGISTRY.get(name)
    if knob is None:
        raise KeyError(
            f"undeclared knob {name!r}: declare it in "
            f"fabric_mod_tpu/utils/knobs.py (fmtlint rule 'knobs')")
    if knob.type != want:
        raise TypeError(
            f"knob {name} is declared {knob.type}, read as {want}")
    return knob


def get_int(name: str, default: Optional[int] = None) -> int:
    """Parse an int knob; `default` overrides the registry default for
    call sites whose fallback is computed at runtime."""
    knob = _lookup(name, "int")
    fallback = default if default is not None else knob.default
    return env_int(name, int(fallback if fallback is not None else 0))


def get_float(name: str, default: Optional[float] = None) -> float:
    knob = _lookup(name, "float")
    fallback = default if default is not None else knob.default
    return env_float(name, float(fallback if fallback is not None else 0.0))


def get_str(name: str, default: Optional[str] = None) -> str:
    knob = _lookup(name, "str")
    fallback = default if default is not None else (knob.default or "")
    return os.environ.get(name, str(fallback))


def get_bool(name: str) -> bool:
    """Uniform arming semantics: set and not in ("", "0")."""
    _lookup(name, "bool")
    return os.environ.get(name, "") not in ("", "0")


def knob_table() -> List[Knob]:
    """Rows for the generated README table, sorted by name."""
    return sorted(_REGISTRY.values(), key=lambda k: k.name)


# ---------------------------------------------------------------------------
# The registry.  One row per tunable; the README "Knob registry" table
# is GENERATED from these rows (`python -m fabric_mod_tpu.analysis
# --knob-table`) and the drift test fails when they diverge.
# ---------------------------------------------------------------------------

# -- framework arming gates (the FMT_* discipline layer) --------------------
declare("FMT_RACECHECK", "bool", None,
        "1 arms every concurrency guard process-wide (race tier); "
        "unset, each guard is one module-flag read")
declare("FMT_FAULTS", "str", None,
        "arm a fault plan process-wide, e.g. "
        "\"deliver.stream:error@n=3\"; unknown point names and "
        "malformed rules fail loudly at arm time")
declare("FMT_TRACE", "bool", None,
        "1 arms spans + timelines + flight recorder process-wide; "
        "unset is byte-identical behavior with zero span allocations")
declare("FMT_TRACE_RING", "int", 256,
        "flight-recorder ring: block timelines retained")
declare("FMT_TRACE_SPANS", "int", 2048,
        "span ring: finished spans retained for /trace + export")
declare("FMT_SLOW_TESTS", "bool", None,
        "1 enables the multi-minute eager-pairing differentials in "
        "the test suite (excluded from tier-1)")
declare("FMT_NO_COMPILE_CACHE", "bool", None,
        "1 disables the persistent XLA compilation cache the test "
        "harness keeps under .cache/jax (use to time cold compiles); "
        "unset, repeat suite runs skip every unchanged kernel compile")

# -- soak harness -----------------------------------------------------------
declare("FMT_SOAK_SEED", "int", 8,
        "churn schedule + rng seed (the replay handle)")
declare("FMT_SOAK_EVENTS", "int", 6, "churn events per run")
declare("FMT_SOAK_CHANNELS", "int", 2, "soak channels")
declare("FMT_SOAK_PEERS", "int", 2,
        "peers at start (join events add more)")
declare("FMT_SOAK_GAP_TXS", "str", "4:9",
        "\"lo:hi\" seeded range of txs between churn events")
declare("FMT_SOAK_WINDOW_S", "float", 45.0,
        "per-event recovery window (convergence deadline)")
declare("FMT_SOAK_RECOVERY_FRAC", "float", 0.05,
        "post/pre-event throughput floor")
declare("FMT_SOAK_X509_GAP_S", "float", 0.12,
        "x509 lane inter-tx gap (s)")
declare("FMT_SOAK_IDEMIX_GAP_S", "float", 1.0,
        "idemix lane inter-tx gap (s)")
declare("FMT_SOAK_FAULT_P", "float", 0.05,
        "background fault probability per injection-point pass")
declare("FMT_SOAK_SHARDED", "bool", None,
        "1 routes every soak peer's channels through a per-peer "
        "ChannelShardRouter (host-mode slices + the shared "
        "cross-channel verify service) so churn rides the sharding "
        "subsystem")
declare("FMT_SOAK_RELAY", "bool", None,
        "1 runs every soak peer's channels in relay mode "
        "(dissemination/ trees instead of epidemic push): churn "
        "exercises reparenting + anti-entropy repair, and leader_kill "
        "additionally flaps the relay root (recovery recorded as "
        "kind=relay_reparent)")
declare("FMT_SOAK_NO_CRASH", "bool", None,
        "1 drops the crash-shaped churn kinds (peer_crash_rejoin, "
        "orderer_restart, network_partition) from the default plan "
        "(they are in the pool by default since PR 20)")
declare("FMT_SOAK_PARTITION_S", "float", 2.0,
        "network_partition hold time (s): traffic keeps flowing on "
        "the majority side before the scheduled heal")
declare("FMT_SOAK_CRASH_HOLD_S", "float", 1.0,
        "peer_crash_rejoin / orderer_restart down window (s): traffic "
        "continues while the victim is gone, so its rejoin has a real "
        "tail to recover")

# -- device / kernel routing ------------------------------------------------
declare("FABRIC_MOD_TPU_MIXED_ADD", "bool", None,
        "1 routes bucket verifies through the affine-table "
        "mixed-addition ladder (RCB alg. 5); dark pending on-chip "
        "measurement")
declare("FABRIC_MOD_TPU_PALLAS", "bool", None,
        "1 selects the VMEM-fused Pallas ladder; composes with "
        "MIXED_ADD")
declare("FABRIC_MOD_TPU_FUSED_HASH", "bool", None,
        "1 makes msp identities emit raw-message verify items: "
        "SHA-256 on device in the same jitted program as the verify")
declare("FABRIC_MOD_TPU_UNROLL_LOW_CARRY", "bool", None,
        "1 defaults the unrolled low-carry lane on (bench A/B seam; "
        "set_unroll_low_carry overrides per thread)")
declare("FABRIC_MOD_TPU_SPLIT_FINALEXP", "str", None,
        "0/1 forces the split/fused idemix final-exponentiation "
        "program; unset = split on the CPU backend, fused on TPU")

# -- verify front-end -------------------------------------------------------
declare("FABRIC_MOD_TPU_VERDICT_CACHE", "int", 8192,
        "verdict memo-cache capacity, LRU over (digest, signature, "
        "pubkey); 0 disables")
declare("FABRIC_MOD_TPU_INFLIGHT", "int", 2,
        "in-flight dispatch window depth of BatchingVerifyService")
declare("FABRIC_MOD_TPU_VERIFY_DEADLINE", "float", 30.0,
        "whole-call deadline (s) of BatchingVerifyService.verify/"
        "verify_many; 0 = wait forever")
declare("FABRIC_MOD_TPU_BREAKER_K", "int", 3,
        "consecutive device failures that open the verify circuit; "
        "0 = never open (per-batch fallback only)")
declare("FABRIC_MOD_TPU_BREAKER_PROBE_S", "float", 5.0,
        "background probe period while the circuit is open; 0 "
        "disables the prober thread")

# -- channel sharding -------------------------------------------------------
declare("FABRIC_MOD_TPU_SHARDS", "int", 0,
        "mesh slices the channel-shard router carves (sharding/); "
        "0/unset = sharding disabled (single-slice behavior)")
declare("FABRIC_MOD_TPU_SHARD_HOSTS", "int", 1,
        "expected jax.distributed process count of the multi-host "
        "spec (sharding/multihost.py); >1 is specified but stubbed — "
        "initialize_multihost raises until the bring-up lands")

# -- ordering / ingress -----------------------------------------------------
declare("FABRIC_MOD_TPU_BROADCAST_RETRY_S", "float", 5.0,
        "how long Broadcast.submit retries NotLeaderError before "
        "surfacing it; 0 = no retry")
declare("FABRIC_MOD_TPU_SUBMIT_QUEUE", "int", 0,
        "consenter submit-queue bound + non-blocking puts; 0/unset = "
        "blocking 10k queue (pre-admission behavior)")
declare("FABRIC_MOD_TPU_INGRESS_RATE", "float", 0.0,
        "per-client sustained tokens/s; 0/unset disables the limiter")
declare("FABRIC_MOD_TPU_INGRESS_BURST", "float", None,
        "token-bucket capacity (burst size); default 2x rate, min 1")
declare("FABRIC_MOD_TPU_SHED_HIGH", "float", 0.9,
        "submit-queue occupancy fraction that opens the overload gate")
declare("FABRIC_MOD_TPU_SHED_LOW", "float", 0.6,
        "occupancy fraction that closes the gate (hysteresis band)")
declare("FABRIC_MOD_TPU_SHED_LAT_S", "float", 0.0,
        "admission-latency EWMA (s) that opens the gate even below "
        "the occupancy watermark; 0 = off")
declare("FABRIC_MOD_TPU_RAFT_QUEUE", "int", 8192,
        "raft FSM ingress queue bound; overflowed peer messages drop "
        "counted; 0 = unbounded")
declare("FABRIC_MOD_TPU_STAGED_BROADCAST", "int", 0,
        "staged broadcast ingress: max envelopes a per-channel "
        "drainer coalesces into ONE batched Writers-policy verify; "
        "0/unset = per-submission processing (pre-staging behavior)")
declare("FABRIC_MOD_TPU_RAFT_PIPELINE", "int", 0,
        "in-flight AppendEntries windows per follower (optimistic "
        "pipelining; replies repair the window on mismatch); "
        "0/unset = one outstanding round per follower")
declare("FABRIC_MOD_TPU_WAL_GROUP_COMMIT", "bool", None,
        "1 defers the raft WAL fsync to the group-commit barrier "
        "(one fsync covers every entry appended since the last "
        "barrier, still BEFORE any ack/commit); unset = fsync per "
        "append")

# -- peer deliver fan-out ---------------------------------------------------
declare("FABRIC_MOD_TPU_DELIVER_STREAMS", "int", 40,
        "peer event-deliver admission cap (streams per channel "
        "service); past it new streams get SERVICE_UNAVAILABLE")
declare("FABRIC_MOD_TPU_FANOUT_RING", "int", 128,
        "per-(channel, form) deliver fan-out ring depth: blocks kept "
        "as ready-to-send frames; subscribers lagging past the tail "
        "fall back to a counted per-stream ledger re-read")

# -- cross-peer dissemination ----------------------------------------------
declare("FABRIC_MOD_TPU_RELAY", "bool", None,
        "1 builds a RelayService into every GossipService: the "
        "elected leader keeps the sole orderer pull and pushes "
        "once-encoded frames down the deterministic relay tree; "
        "unset = the epidemic gossip_block push")
declare("FABRIC_MOD_TPU_RELAY_DEGREE", "int", 4,
        "relay-tree fan-out degree: children each member pushes to")
declare("FABRIC_MOD_TPU_RELAY_QUEUE", "int", 64,
        "per-child relay queue bound; a slow child sheds its own "
        "OLDEST frames, counted (anti-entropy repairs the gap)")

# -- retries / gossip -------------------------------------------------------
declare("FABRIC_MOD_TPU_RETRY_BASE_S", "float", 0.05,
        "default base of every Retrier backoff schedule")
declare("FABRIC_MOD_TPU_RETRY_MAX_S", "float", 5.0,
        "default cap of every Retrier backoff schedule")
declare("FABRIC_MOD_TPU_GOSSIP_SEND_RETRIES", "int", 2,
        "bounded per-message gossip send retries (fresh dial per "
        "attempt); 0 = drop on first failure")

# -- bench ------------------------------------------------------------------
declare("FABRIC_MOD_TPU_BENCH_TIMEOUT", "float", 1200.0,
        "bench.py --metric gossip: bound (s) on one device-verify "
        "wait of the storm (covers a cold compile)")
