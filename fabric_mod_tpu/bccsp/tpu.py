"""TPU batch crypto provider — the framework's north star.

The device-offload CSP the reference only gestures at with its PKCS#11
HSM binding (reference: bccsp/pkcs11/pkcs11.go:241 Verify — the
in-repo template for "send crypto to a device"): ECDSA-P256 verifies
are staged into fixed-size buckets, verified in one jitted program on
the TPU (ops/p256.py), and results are returned as futures so the
caller-facing API stays BCCSP-shaped.

Design notes (SURVEY.md §2.9, §7):
* The batch axis replaces the reference's goroutine-per-tx fan-out
  (core/committer/txvalidator/v20/validator.go:194-239).
* Buckets are padded to a small set of static sizes so XLA compiles a
  handful of programs, ever; a persistent compilation cache makes them
  survive process restarts.
* Latency-sensitive small batches are handled by a deadline-based
  flusher (default 2 ms), the device answer for the reference's
  assumption that a verify dispatch costs ~µs.
* Signing, key management and single hashes stay host-side (private
  keys never benefit from batch; reference keeps HSM signing
  device-side only because the key lives there).

The PIPELINED front-end (this layer's whole job is keeping the device
fed):

* **Vectorized marshalling** — DER decode + byte staging for a whole
  bucket is numpy array arithmetic (bccsp/der.py), not a 2048-pass
  python loop; see `marshal_items`.
* **Verdict memo-cache** — an LRU keyed by (digest, signature, public
  key) consulted BEFORE bucketing (`VerdictCache`); gossip
  redelivery, retried blocks, and the endorsement/commit
  dual-validation both repeat identical verifies, and a hit skips the
  device entirely (the role of the reference's msp cache layer,
  msp/cache).  Identical items within one call dedup to one device
  lane for the same reason.
* **Key tables** — signers repeat (a channel's endorsing peers, its
  orderer, the applications' enrolled identities), so the verifier
  keeps the fixed-base table of each public key seen lately on the
  device (`KeyTables`) and a lane whose key has one is verified by the
  table program (ops/p256.py: 129 additions and no doubling, against
  the ladder's 256 doublings and a table built in every call).
* **In-flight dispatch window** — `BatchingVerifyService` dispatches
  buckets via `verify_many_async` into a bounded in-flight queue
  (default depth 2, FABRIC_MOD_TPU_INFLIGHT) and a resolver thread
  completes Futures in dispatch order, so bucket k+1 marshals on the
  worker thread while bucket k executes on the device.
"""
from __future__ import annotations

import collections
import operator
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FutureTimeout
from typing import List, Optional, Sequence, Tuple

import numpy as np

from fabric_mod_tpu import faults
from fabric_mod_tpu.bccsp.api import BCCSP, Key, VerifyItem
from fabric_mod_tpu.bccsp.breaker import CircuitBreaker
from fabric_mod_tpu.bccsp import der as _der
from fabric_mod_tpu.bccsp import sw as _sw
from fabric_mod_tpu.concurrency import (GuardedQueue, RegisteredLock,
                                        RegisteredThread, assert_joined)
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.observability.metrics import (MetricOpts,
                                                  default_provider)
from fabric_mod_tpu.utils import knobs as _knobs

# Persistent XLA compilation cache: the ECDSA ladder costs tens of
# seconds to compile; cache it across processes.  (Shared helper —
# ops/fp256bn_dev.py puts the idemix pairing program on the same
# cache at its import.)
from fabric_mod_tpu.ops.compilecache import (  # noqa: E402
    enable_compile_cache as _enable_compile_cache)

_enable_compile_cache()

BUCKETS = (8, 64, 512, 2048)

# Low-S bound over the curve order defined alongside the device kernel,
# so the rule can't desynchronize from the math layer.
from fabric_mod_tpu.ops import p256 as _p256  # noqa: E402

_LOW_S_MAX = _p256.N // 2
# s is acceptable iff s < _LOW_S_MAX + 1, as a big-endian byte bound
# for the batched lexicographic compare.
_LOW_S_BOUND = (_LOW_S_MAX + 1).to_bytes(32, "big")


def _bucket(n: int, min_div: int = 1) -> int:
    """Smallest static bucket holding n that `min_div` divides (the
    mesh size must divide the sharded batch axis evenly); n must be
    <= max bucket (larger batches are chunked by the caller so the
    set of compiled program shapes stays fixed)."""
    for b in BUCKETS:
        if n <= b and b % min_div == 0:
            return b
    raise ValueError(
        f"no bucket >= {n} divisible by {min_div} (max {BUCKETS[-1]})")


def marshal_items(items: Sequence[VerifyItem], size: Optional[int] = None
                  ) -> Tuple[np.ndarray, ...]:
    """Whole-batch host marshalling: VerifyItems -> device byte planes.

    The vectorized replacement for the old per-item python loop
    (per-item DER decode, int.to_bytes, np.frombuffer): one batched
    DER parse, one packed copy per fixed-width field, and ONE low-S /
    length range check across the whole batch.  Returns
    (d, r, s, qx, qy, pre_ok, msg) — five (size, 32) uint8 planes
    padded to the bucket `size`, the (size,) host-side validity mask
    (False rows never contribute a True verdict, whatever the device
    says), and the fused-hash MESSAGE lane: None when no item carries
    a raw message, else (words, nblocks, has_msg) from the vectorized
    padder (der.pack_messages) — raw rows get their digest computed
    ON DEVICE (p256.batch_verify_raw), pre-digested rows keep the
    digest plane, one program either way.

    Fresh output arrays each call on purpose: jax's host->device
    transfer of a dispatched-but-unresolved batch may still be reading
    the source buffers, so reusing one staging buffer under the
    in-flight window would be a use-after-write hazard.
    """
    n = len(items)
    size = n if size is None else size
    msgs = [getattr(it, "message", None) for it in items]
    any_raw = any(m is not None for m in msgs)
    d, d_ok = _der.pack_fixed(
        list(map(operator.attrgetter("digest"), items)), 32, size)
    pub, pub_ok = _der.pack_fixed(
        list(map(operator.attrgetter("public_xy"), items)), 64, size)
    r, s, der_ok = _der.decode_der_batch(
        list(map(operator.attrgetter("signature"), items)), size)
    low_s = _der.lt_bytes(s, _LOW_S_BOUND)           # the low-S rule
    msg = None
    if any_raw:
        words, nblocks, msg_ok = _der.pack_messages(
            [m if m is not None else b"" for m in msgs], size,
            round_blocks_pow2=True)
        has_msg = np.zeros(size, bool)
        has_msg[:n] = [m is not None for m in msgs]
        # raw rows validate on the message, not the (empty) digest;
        # a raw item whose message is not bytes stays invalid rather
        # than silently falling back to a digest it did not carry
        d_ok = np.where(has_msg, msg_ok, d_ok)
        nblocks = np.where(has_msg, nblocks, 0).astype(np.int32)
        msg = (words, nblocks, has_msg)
    pre_ok = d_ok & pub_ok & der_ok & low_s
    qx = np.ascontiguousarray(pub[:, :32])
    qy = np.ascontiguousarray(pub[:, 32:])
    return d, r, s, qx, qy, pre_ok, msg


# ---------------------------------------------------------------------------
# Verdict memo-cache
# ---------------------------------------------------------------------------

_CACHE_HITS_OPTS = MetricOpts(
    "fabric", "bccsp", "verdict_cache_hits",
    help="Verify verdicts served from the memo-cache (device skipped).")
_CACHE_MISSES_OPTS = MetricOpts(
    "fabric", "bccsp", "verdict_cache_misses",
    help="Verify items that had to be dispatched to the device.")
_CACHE_EVICTIONS_OPTS = MetricOpts(
    "fabric", "bccsp", "verdict_cache_evictions",
    help="LRU evictions from the verdict memo-cache.")
_CACHE_SIZE_OPTS = MetricOpts(
    "fabric", "bccsp", "verdict_cache_size",
    help="Current number of memoized verify verdicts.")


class VerdictCache:
    """Bounded LRU of (digest, signature, public key) -> bool verdict.

    A verify is a pure function of that triple, so the verdict is
    memoizable forever; the LRU bound only caps memory.  Gossip
    redelivery, retried blocks, and the endorsement-then-commit
    dual validation (peer/txvalidator.py) all re-verify identical
    items — a hit skips DER decode, bucketing, and the device program
    entirely (the role the msp cache layer plays in the reference).

    Thread-safe; instrumented through observability/metrics.py via
    get-or-create so every instance shares one exposition row set.
    """

    def __init__(self, capacity: int, provider=None):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._od: "collections.OrderedDict[tuple, bool]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()  # fmtlint: allow[locks] -- leaf lock on the per-verify memo-cache path, never nested; C-level speed matters
        prov = provider or default_provider()
        self._hits = prov.counter(_CACHE_HITS_OPTS)
        self._misses = prov.counter(_CACHE_MISSES_OPTS)
        self._evictions = prov.counter(_CACHE_EVICTIONS_OPTS)
        self._size = prov.gauge(_CACHE_SIZE_OPTS)

    @staticmethod
    def key_of(item: VerifyItem) -> Optional[tuple]:
        """Hashable memo key, or None for items with non-bytes fields
        (bytearray coerces; anything else is uncacheable and must not
        raise — one weird item may never poison a coalesced batch).
        Raw-message items key on the message too: (digest, sig, key,
        message) is the full pure-function input of the fused path."""
        key = []
        for x in (item.digest, item.signature, item.public_xy):
            if type(x) is not bytes:
                if not isinstance(x, (bytes, bytearray, memoryview)):
                    return None
                x = bytes(x)
            key.append(x)
        msg = getattr(item, "message", None)
        if msg is not None and type(msg) is not bytes:
            if not isinstance(msg, (bytes, bytearray, memoryview)):
                return None
            msg = bytes(msg)
        key.append(msg)
        return tuple(key)

    def get_many(self, keys: Sequence[Optional[tuple]]
                 ) -> List[Optional[bool]]:
        """Probe many keys under one lock pass; hits refresh recency.
        None keys (uncacheable items) always miss."""
        out: List[Optional[bool]] = []
        hits = 0
        with self._lock:
            od = self._od
            for k in keys:
                got = od.get(k) if k is not None else None
                if got is not None:
                    od.move_to_end(k)
                    hits += 1
                out.append(got)
        self._hits.add(hits)
        self._misses.add(len(keys) - hits)
        return out

    def put_many(self, keys: Sequence[Optional[tuple]], verdicts) -> None:
        evicted = 0
        with self._lock:
            od = self._od
            before = len(od)
            for k, v in zip(keys, verdicts):
                if k is None:
                    continue
                od[k] = bool(v)
                od.move_to_end(k)
            while len(od) > self.capacity:
                od.popitem(last=False)
                evicted += 1
            delta = len(od) - before
        self._evictions.add(evicted)
        # delta, not set(): the exposition row is get-or-create-shared
        # across caches, so it reports the process-wide total of
        # memoized verdicts rather than last-writer-wins of one cache
        self._size.add(delta)

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)


def _cache_from_env() -> Optional[VerdictCache]:
    cap = _knobs.get_int("FABRIC_MOD_TPU_VERDICT_CACHE")
    return VerdictCache(cap) if cap > 0 else None


# ---------------------------------------------------------------------------
# Key tables
# ---------------------------------------------------------------------------

# Public keys whose fixed-base tables live on the device at once: 64
# slots x 64 positions x 16 entries x 3 planes x 30 limbs x 4 B = 23.6
# MB.  The array has ONE shape for the life of a verifier, so a new
# key never mints a program.
KEY_SLOTS = 64
# Tables built for the new keys of one batch, most lanes first; the
# lanes of the keys beyond it take the ladder.  A build is 11 ms of
# host under the tables' lock and the ladder call it saves every later
# batch 142.63 ms of device at the widest bucket (the chip, PERF.md
# section 5): thirteen builds cost one such call.  Sixteen (0.18 s a
# batch at most) is the least that lets a batch of sixteen keys never
# met, with slots free, run as ONE call of one program.
NEW_TABLES_PER_BATCH = 16
# Keys met while they could get no table, remembered so that their
# next batch can tell a signer that repeats from one that passes by.
SEEN_KEYS = 16 * KEY_SLOTS

_TABLE_LANES_OPTS = MetricOpts(
    "fabric", "bccsp", "key_table_lanes_total",
    help="Device verify lanes by the program that verified them: "
         "`table` (the key's fixed-base table was on the device) or "
         "`ladder`; added once per dispatch, pad lanes not counted.",
    label_names=("path",))
_TABLES_BUILT_OPTS = MetricOpts(
    "fabric", "bccsp", "key_tables_built_total",
    help="Fixed-base tables built for public keys seen for the first "
         "time (or again after their slot was taken).")


def _key_of(item: VerifyItem) -> Optional[bytes]:
    """The item's public key as the tables' lookup key, or None where
    it is not 64 bytes (such a lane is False whatever runs it)."""
    xy = item.public_xy
    if type(xy) is not bytes:
        if not isinstance(xy, (bytes, bytearray, memoryview)):
            return None
        xy = bytes(xy)
    return xy if len(xy) == 64 else None


class KeyTables:
    """The fixed-base tables (ops/p256.key_table) of the public keys
    seen lately, in one device array of `KEY_SLOTS` slots.

    Which new key gets a table, from the keys the batches carry and
    nothing else: while a slot is free, any, at once (most lanes of
    the batch first, `NEW_TABLES_PER_BATCH` a batch).  Once every slot
    is taken a table costs another key its own, so only a key met in
    an earlier batch takes the least recently used slot: a signer
    that passes by once (one of thousands of enrolled clients) costs
    no build and evicts nobody.

    The device array is immutable: when a slot changes, a fresh copy of
    the host master is put, and a call in flight keeps the array it was
    dispatched with (the master itself is never handed to a transfer —
    see `marshal_items`' note).  Thread-safe: several threads dispatch.
    """

    def __init__(self):
        self._lock = RegisteredLock("bccsp-key-tables")
        # key -> slot, least recently used first
        self._slot_of: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()
        self._free = list(range(KEY_SLOTS - 1, -1, -1))
        # keys that got no table in their batch, oldest first
        self._seen: "collections.OrderedDict[bytes, None]" = \
            collections.OrderedDict()
        # a slot whose key is no curve point holds no table: its lanes
        # are False
        self._ok = np.zeros(KEY_SLOTS, bool)
        self._host = _p256.empty_key_tables(KEY_SLOTS)
        # None: the host master has tables the device has not
        self._device = None
        self._built = default_provider().counter(_TABLES_BUILT_OPTS)

    def assign(self, keys: Sequence[Optional[bytes]]):
        """Slots for one batch's lanes.  A key with a slot keeps it;
        new keys get tables by the class's rule.  Returns (slot,
        slot_ok, tabled, tables): per lane its slot, whether that
        slot's key is a curve point, whether the lane has a slot at
        all (a lane without one takes the ladder; a lane with no
        usable key counts as tabled and False), and the device array
        to run against.

        All or nothing: the tables are built before anything is
        changed, and the device array is given up BEFORE the first
        slot changes hands, so a put that fails (the batch then
        degrades like any device error) is made again by the next
        batch, and no lane ever runs against an array that lacks its
        slot's table."""
        counts = collections.Counter(k for k in keys if k is not None)
        width = _p256.TABLE
        with self._lock:
            slot_of = self._slot_of
            new = []
            for key, _ in counts.most_common():
                if key in slot_of:
                    slot_of.move_to_end(key)
                else:
                    new.append(key)
            # the plan: (key, slot, the key that loses it)
            n_free = len(self._free)
            spare = (k for k in slot_of if k not in counts)
            plan = []
            for key in new:
                if len(plan) == NEW_TABLES_PER_BATCH:
                    break
                if len(plan) < n_free:
                    plan.append((key, self._free[-1 - len(plan)], None))
                elif key in self._seen:
                    loser = next(spare, None)
                    if loser is None:   # every slot's key is in the batch
                        break
                    plan.append((key, slot_of[loser], loser))
            tables = [_p256.key_table(int.from_bytes(key[:32], "big"),
                                      int.from_bytes(key[32:], "big"))
                      for key, _, _ in plan]
            built = sum(t is not None for t in tables)
            if built:
                self._device = None
            for (key, slot, loser), table in zip(plan, tables):
                if loser is not None:
                    del slot_of[loser]
                slot_of[key] = slot
                self._seen.pop(key, None)
                self._ok[slot] = table is not None
                if table is not None:
                    self._host[..., slot * width:(slot + 1) * width] = table
            del self._free[n_free - min(n_free, len(plan)):]
            for key in new:
                if key not in slot_of:
                    self._seen[key] = None
                    self._seen.move_to_end(key)
            while len(self._seen) > SEEN_KEYS:
                self._seen.popitem(last=False)
            self._built.add(built)
            if self._device is None:
                faults.point("bccsp.device.tables")
                self._device = _p256.place_key_tables(self._host)
            device = self._device
            # None is no key of `slot_of`: such a lane reads -1 too
            slot = np.array([slot_of.get(k, -1) for k in keys], np.int32)
            slot_ok = (slot >= 0) & self._ok[np.maximum(slot, 0)]
        tabled = (slot >= 0) | np.array([k is None for k in keys], bool)
        return np.maximum(slot, 0), slot_ok, tabled, device


# ---------------------------------------------------------------------------
# The device verifier
# ---------------------------------------------------------------------------

_DEVICE_ERRORS_OPTS = MetricOpts(
    "fabric", "bccsp", "device_errors_total",
    help="Device/XLA runtime errors on the verify path (each failed "
         "over per-batch to the sw verifier).")
_FALLBACK_OPTS = MetricOpts(
    "fabric", "bccsp", "sw_fallback_batches_total",
    help="Verify batches answered by the sw fallback instead of the "
         "device (device error, or circuit open).")
_DISPATCH_CHUNKS_OPTS = MetricOpts(
    "fabric", "bccsp", "dispatch_chunks_total",
    help="Device calls that batches wider than the widest bucket were "
         "cut into, by the bucket each call ran in; added once per "
         "chunked batch (a batch that fits one call adds nothing).",
    label_names=("bucket",))


def is_device_error(e: BaseException) -> bool:
    """Is `e` a device/XLA-runtime failure (vs. a host-side bug)?
    Device failures are operational — the sw verifier computes the
    identical verdict function, so they degrade instead of failing.
    Host exceptions (marshalling bugs, bad types, and jax's own
    TRACING errors like ConcretizationTypeError — those are program
    bugs, not outages) must keep raising: masking them behind the
    fallback would hide real defects, so only the RUNTIME error
    classes the XLA client raises for device/executor failures
    qualify."""
    if isinstance(e, faults.InjectedFault):
        return e.kind == "device"
    name = type(e).__name__
    return "XlaRuntimeError" in name or "JaxRuntimeError" in name


def _carry_lanes(resolve, parts):
    """Give a resolver the lane tallies of the resolvers it wraps:
    `table_lanes` / `ladder_lanes`, the device lanes by the program
    that verifies them (0 where a part never reached the device)."""
    for name in ("table_lanes", "ladder_lanes"):
        setattr(resolve, name, sum(getattr(p, name, 0) for p in parts))
    return resolve


class TpuVerifier:
    """Marshals VerifyItems to the device batch verifier.

    Separated from the CSP so the commit pipeline (and tests, via a
    fake with the same shape) can depend on just this seam — the
    equivalent of the reference's narrow per-consumer interfaces
    (SURVEY.md §4).

    Pass a `mesh` (parallel.data_mesh) to shard each bucket's batch
    axis across chips; bucket selection then skips buckets the mesh
    size does not divide, so the partition is always even.  The mesh
    size must divide the largest bucket (i.e. be a power of two
    <= 2048) — checked at construction.

    Which program a lane takes (`_device_dispatch`): the table program
    where its public key has a fixed-base table on the device
    (`KeyTables`: while a slot is free a new key gets one at once, up
    to `NEW_TABLES_PER_BATCH` a batch; once the slots are full, a key
    met in an earlier batch), the ladder otherwise, in a call of its
    own.  With a mesh every lane takes the ladder: the tables are one
    device's.  A process that serves warms BOTH programs (`warm`).

    `cache_size` bounds the verdict memo-cache (default from
    FABRIC_MOD_TPU_VERDICT_CACHE, 8192; 0 disables); pass a
    `VerdictCache` to share one across verifiers.  Identical items in
    one call always dedup to a single device lane, cache or not.
    """

    def __init__(self, mesh=None, cache: Optional[VerdictCache] = None,
                 cache_size: Optional[int] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 fallback=None):
        """`breaker`: circuit breaker guarding the device (None builds
        one from the FABRIC_MOD_TPU_BREAKER_K / _BREAKER_PROBE_S
        knobs; K=0 still fails over per-batch but never opens).
        `fallback(items) -> bool mask`: the degraded verifier — default
        is the sw provider's verify_batch, which enforces the same
        low-S/encoding rules as the device marshaller, so fallback
        verdicts are bit-identical to device verdicts."""
        self._mesh = mesh
        self._mesh_size = 1
        if mesh is not None:
            self._mesh_size = int(np.prod(mesh.devices.shape))
            if BUCKETS[-1] % self._mesh_size != 0:
                raise ValueError(
                    f"mesh size {self._mesh_size} must divide the max "
                    f"bucket {BUCKETS[-1]} (use a power-of-two mesh)")
        if cache is not None:
            self._cache = cache
        elif cache_size is not None:
            self._cache = (VerdictCache(cache_size) if cache_size > 0
                           else None)
        else:
            self._cache = _cache_from_env()
        self._fallback = fallback
        self._fallback_csp: Optional[_sw.SwCSP] = None
        self.breaker = breaker if breaker is not None else \
            CircuitBreaker(probe=self._probe_device)
        prov = default_provider()
        self._m_device_errors = prov.counter(_DEVICE_ERRORS_OPTS)
        self._m_fallback = prov.counter(_FALLBACK_OPTS)
        self._m_chunks = prov.counter(_DISPATCH_CHUNKS_OPTS)
        self._m_lanes = prov.counter(_TABLE_LANES_OPTS)
        self._tables = KeyTables() if mesh is None else None

    def close(self) -> None:
        """Tear down the breaker's background prober (if the circuit
        ever opened).  Verifiers are otherwise stateless; this exists
        so owners (BatchingVerifyService, tests) can guarantee no
        probe thread outlives the device it probes."""
        self.breaker.stop()

    def verify_many(self, items: Sequence[VerifyItem]) -> np.ndarray:
        return self.verify_many_async(items)()

    def warm(self, items: Sequence[VerifyItem]) -> None:
        """Load (or compile) both device programs at the bucket that
        holds `items`: the one the rule picks for them (a few fixture
        keys: the table program) and the ladder, which the lanes a
        batch has no tables for reach at any bucket.  For a process
        that serves before it knows its signers: a program loaded on
        the serving path holds its batch for minutes."""
        self.verify_many(items)
        if self._tables is not None and self.breaker.allow():
            try:
                self._device_call(items, None)()
            except Exception as e:
                self._degrade(e, items)

    def verify_many_async(self, items: Sequence[VerifyItem]):
        """Memo-probe + dedup + marshal + DISPATCH, returning a
        zero-arg resolver for the verdicts.  Between dispatch and
        resolution the device executes while the caller does host work
        for the next bucket — the commit pipeline's double buffer
        (SURVEY §2.9 row 2; reference analog: the payload buffer
        decoupling pull from commit at gossip/state/state.go:583)."""
        n = len(items)
        if n == 0:
            return lambda: np.zeros(0, bool)
        # Dedup FIRST, then memo-probe once per unique triple: the
        # cache hit/miss counters thereby count unique work units —
        # 2048 copies of one signature are one miss and one device
        # lane, not 2048 of either.
        slot_of: dict = {}
        uniq_items: List[VerifyItem] = []
        uniq_keys: List[tuple] = []
        lanes = np.empty(n, np.int64)
        for i, it in enumerate(items):
            k = VerdictCache.key_of(it)
            lane = slot_of.get(k) if k is not None else None
            if lane is None:
                lane = len(uniq_items)
                if k is not None:        # None: uncacheable, own lane
                    slot_of[k] = lane
                uniq_items.append(it)
                uniq_keys.append(k)
            lanes[i] = lane
        cache = self._cache
        cached = (cache.get_many(uniq_keys) if cache is not None
                  else [None] * len(uniq_keys))
        miss_lanes = [j for j, c in enumerate(cached) if c is None]
        vals = np.array([bool(c) for c in cached], bool)
        if not miss_lanes:
            out = vals[lanes]
            return lambda: out
        resolve = self._dispatch([uniq_items[j] for j in miss_lanes])
        miss_idx = np.asarray(miss_lanes)

        def finish() -> np.ndarray:
            mask = np.asarray(resolve(), bool)  # fmtlint: allow[jax-hot-path] -- THE sanctioned resolve seam: verdicts sync exactly once, in the commit stage, behind the in-flight window
            if cache is not None:
                cache.put_many([uniq_keys[j] for j in miss_lanes], mask)
            vals[miss_idx] = mask
            return vals[lanes]
        # device calls this batch became, for the caller's span
        finish.chunks = getattr(resolve, "chunks", 1)
        return _carry_lanes(finish, [resolve])

    def _dispatch(self, items: Sequence[VerifyItem]):
        """Marshal + dispatch unique items (no cache/dedup layer).
        Device/XLA runtime errors — at dispatch OR at resolution —
        fail over per-batch to the sw fallback (identical verdicts)
        and feed the circuit breaker; with the circuit open the device
        is skipped outright until a probe re-closes it."""
        n = len(items)
        if n > BUCKETS[-1]:
            # chunk through the fixed buckets — never mint new shapes
            chunks = [items[i:i + BUCKETS[-1]]
                      for i in range(0, n, BUCKETS[-1])]
            sizes = [_bucket(len(c), self._mesh_size) for c in chunks]
            parts = []
            for k, (chunk, size) in enumerate(zip(chunks, sizes)):
                # one part's marshal and enqueue: what the host does
                # while the parts before it run (or the chip waits)
                with tracing.span("dispatch_chunk", part=k, of=len(chunks),
                                  items=len(chunk), bucket=size):
                    parts.append(self._dispatch(chunk))
            for size, calls in collections.Counter(sizes).items():
                self._m_chunks.with_labels(str(size)).add(calls)

            def finish_parts() -> np.ndarray:
                return np.concatenate([p() for p in parts])
            finish_parts.chunks = len(parts)
            return _carry_lanes(finish_parts, parts)
        breaker = self.breaker
        if not breaker.allow():
            self._m_fallback.add(1)
            return lambda: self._fallback_verify(items)
        try:
            resolve = self._device_dispatch(items)
        except Exception as e:
            return self._degrade(e, items)

        def finish() -> np.ndarray:
            try:
                mask = resolve()
            except Exception as e:
                return self._degrade(e, items)()
            breaker.record_success()
            return mask
        return _carry_lanes(finish, [resolve])

    def _device_dispatch(self, items: Sequence[VerifyItem]):
        """The raw device path, and the rule that picks each lane's
        program from its public key and nothing else: the table
        program where the key has a table on the device, the ladder
        for the lanes left over, in a call of their own (a device call
        each: its own `der_marshal` and `device_enqueue`); the two
        masks are merged in lane order.  The returned resolver blocks
        on (and surfaces errors from) the device execution."""
        n = len(items)
        tabled = np.zeros(n, bool)           # a mesh: the ladder
        if self._tables is not None:
            slot, slot_ok, tabled, tables = self._tables.assign(
                [_key_of(it) for it in items])
        n_tabled = int(tabled.sum())
        self._m_lanes.with_labels("table").add(n_tabled)
        self._m_lanes.with_labels("ladder").add(n - n_tabled)
        if n_tabled == n:
            done = self._device_call(items, (slot, slot_ok, tables))
        elif n_tabled == 0:
            done = self._device_call(items, None)
        else:
            at_table, at_ladder = np.flatnonzero(tabled), \
                np.flatnonzero(~tabled)
            by_table = self._device_call(
                [items[i] for i in at_table],
                (slot[at_table], slot_ok[at_table], tables))
            by_ladder = self._device_call(
                [items[i] for i in at_ladder], None)

            def done() -> np.ndarray:
                mask = np.empty(n, bool)
                mask[at_table] = by_table()
                mask[at_ladder] = by_ladder()
                return mask
        done.table_lanes, done.ladder_lanes = n_tabled, n - n_tabled
        return done

    def _device_call(self, items: Sequence[VerifyItem], tabled):
        """Marshal + ONE program dispatch: the table program over
        `tabled` = (slot, slot_ok, tables) of `KeyTables.assign`, the
        ladder where it is None."""
        n = len(items)
        size = _bucket(n, self._mesh_size)
        with tracing.span("der_marshal", items=n, bucket=size):
            d, r, s, qx, qy, pre_ok, msg = marshal_items(items, size)
        faults.point("bccsp.device.dispatch")
        # a raw-message lane hashes on device in the SAME program as
        # the verify, either program: one dispatch, no host digest loop
        # (FABRIC_MOD_TPU_FUSED_HASH consumers)
        if tabled is not None:
            slot, slot_ok, tables = tabled
            pad = (0, size - n)
            dispatch = lambda: _p256.batch_verify_tables(
                d, r, s, np.pad(slot, pad), np.pad(slot_ok, pad), tables,
                msg=msg, lazy=True)
        elif msg is not None:
            words, nblocks, has_msg = msg
            dispatch = lambda: _p256.batch_verify_raw(
                words, nblocks, has_msg, d, r, s, qx, qy,
                mesh=self._mesh, lazy=True)
        else:
            dispatch = lambda: _p256.batch_verify(d, r, s, qx, qy,
                                                  mesh=self._mesh,
                                                  lazy=True)
        # the transfer and the enqueue; the program runs after it
        with tracing.span("device_enqueue", bucket=size):
            resolve = dispatch()

        def done() -> np.ndarray:
            faults.point("bccsp.device.resolve")
            return (resolve() & pre_ok)[:n]
        return done

    def _degrade(self, e: BaseException, items: Sequence[VerifyItem]):
        """Handle a dispatch/resolve failure: device errors fall back
        to the sw verifier (and count toward opening the circuit);
        anything else re-raises — it is a host bug, not an outage."""
        if not is_device_error(e):
            raise e
        self._m_device_errors.add(1)
        self._m_fallback.add(1)
        self.breaker.record_failure()
        return lambda: self._fallback_verify(items)

    def _fallback_verify(self, items: Sequence[VerifyItem]) -> np.ndarray:
        """The degraded path: host software, identical verdicts (the
        sw provider enforces the same low-S/encoding rules the device
        marshaller bakes into pre_ok)."""
        fb = self._fallback
        if fb is not None:
            return np.asarray(fb(items), bool)  # fmtlint: allow[jax-hot-path] -- degraded sw path: verdicts are host-computed by definition
        csp = self._fallback_csp
        if csp is None:
            csp = self._fallback_csp = _sw.SwCSP()
        return np.asarray(csp.verify_batch(items), bool)  # fmtlint: allow[jax-hot-path] -- degraded sw path: verdicts are host-computed by definition

    def _probe_device(self) -> bool:
        """Breaker probe: one minimal-bucket dispatch must execute
        without a device error (its verdict is irrelevant — the probe
        item is garbage by construction)."""
        try:
            faults.point("bccsp.device.probe")
            probe_item = VerifyItem(b"\x00" * 32, b"\x00" * 8,
                                    b"\x00" * 64)
            self._device_dispatch([probe_item])()
            return True
        except Exception as e:
            return not is_device_error(e)


class FakeBatchVerifier:
    """Deterministic CPU stand-in with the TpuVerifier seam (for tests
    and TPU-less deployments — the reference's fake-at-the-interface
    testing pattern, SURVEY.md §4)."""

    def __init__(self, csp: Optional[BCCSP] = None):
        self._csp = csp or _sw.SwCSP()

    def verify_many(self, items: Sequence[VerifyItem]) -> np.ndarray:
        return np.asarray(self._csp.verify_batch(items), bool)  # fmtlint: allow[jax-hot-path] -- FakeBatchVerifier is the host stand-in; no device in the loop

    def verify_many_async(self, items: Sequence[VerifyItem]):
        """Deferred-to-resolution stand-in for the device's async
        dispatch: the sw verify runs when the resolver is called (in
        the commit stage), preserving the pipeline's thread layout."""
        return lambda: self.verify_many(items)


# ---------------------------------------------------------------------------
# The batching front door
# ---------------------------------------------------------------------------

_SERVICE_BATCH_OPTS = MetricOpts(
    "fabric", "bccsp", "verify_batch_items",
    help="Items per dispatched verify batch (coalescing effectiveness).")
_SERVICE_INFLIGHT_OPTS = MetricOpts(
    "fabric", "bccsp", "verify_inflight_batches",
    help="Device batches dispatched but not yet resolved.")
_SERVICE_TIMEOUTS_OPTS = MetricOpts(
    "fabric", "bccsp", "verify_deadline_timeouts_total",
    help="Verify calls that hit the FABRIC_MOD_TPU_VERIFY_DEADLINE "
         "before their verdicts resolved.")


class VerifyDeadlineExceeded(TimeoutError):
    """The verify deadline expired before the verdict resolved.

    Typed so callers can tell a DEADLINE (device overloaded / stuck —
    the caller's timeout policy fired) from a device FAILURE (the
    batch errored — the breaker/fallback layer's business).  Straggler
    futures of a timed-out verify_many fail with this same error.
    """

    def __init__(self, msg: str, deadline_s: Optional[float] = None):
        super().__init__(msg)
        self.deadline_s = deadline_s


def verify_deadline_s() -> Optional[float]:
    """FABRIC_MOD_TPU_VERIFY_DEADLINE: whole-call deadline (seconds)
    shared by BatchingVerifyService.verify/verify_many; 0 or negative
    = no deadline."""
    got = _knobs.get_float("FABRIC_MOD_TPU_VERIFY_DEADLINE")
    return got if got > 0 else None


# callers distinguish "use the knob" (default) from an explicit
# timeout=None (wait forever)
_DEADLINE_KNOB = object()


def _complete(fut: Future, value=None, exc: Optional[BaseException] = None
              ) -> None:
    """Complete a Future that a deadline may have failed first: the
    straggler path and the resolver race, and the loser must not die
    on InvalidStateError (killing the resolver thread would hang every
    later caller)."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(value)
    except InvalidStateError:
        pass


class BatchingVerifyService:
    """Deadline/size-batched async verify front-end with a bounded
    in-flight dispatch window.

    A worker thread drains the submit queue into batches (flush on
    `max_batch` pending or the oldest item turning `deadline_s` old),
    marshals each batch, and DISPATCHES it via the verifier's
    `verify_many_async` — then immediately returns to accumulating the
    next batch while the device executes.  A separate resolver thread
    completes Futures in dispatch order.  The in-flight queue between
    them is bounded (`inflight_depth`, default 2 or
    FABRIC_MOD_TPU_INFLIGHT): when the device falls behind, the worker
    blocks on the queue — backpressure, not unbounded buffering.

    This is the latency/throughput trade-off knob (SURVEY.md §7 hard
    part #3) plus the host/device overlap the old blocking `_flush`
    forfeited: bucket k+1 marshals while bucket k executes.
    """

    _SENTINEL = None

    def __init__(self, verifier=None, max_batch: int = 2048,
                 deadline_s: float = 0.002,
                 inflight_depth: Optional[int] = None):
        # a verifier built HERE is owned here: close() must stop its
        # breaker prober (a caller-provided verifier may be shared, so
        # its lifecycle stays the caller's)
        self._owns_verifier = verifier is None
        self._verifier = verifier or TpuVerifier()
        self.max_batch = max_batch
        self.deadline_s = deadline_s
        if inflight_depth is None:
            inflight_depth = _knobs.get_int("FABRIC_MOD_TPU_INFLIGHT")
        self.inflight_depth = max(1, inflight_depth)
        # submit queue: many producers (any caller), ONE consumer (the
        # flusher worker); in-flight queue: strict SPSC worker ->
        # resolver.  Both contracts are machine-checked under
        # FMT_RACECHECK — the round-5 verdict named this flusher the
        # structure most likely to hide a real race.
        self._q: "GuardedQueue" = GuardedQueue(name="verify-submit")
        self._inflight: "GuardedQueue" = GuardedQueue(
            self.inflight_depth, name="verify-inflight",
            single_producer=True)
        self._stop = threading.Event()
        # serializes submit vs close; registry-fed for cycle detection
        self._lifecycle = RegisteredLock("verify-service-lifecycle")
        prov = default_provider()
        self._batch_hist = prov.histogram(
            _SERVICE_BATCH_OPTS, buckets=(1, 8, 64, 256, 512, 1024, 2048))
        self._inflight_gauge = prov.gauge(_SERVICE_INFLIGHT_OPTS)
        self._timeouts = prov.counter(_SERVICE_TIMEOUTS_OPTS)
        self._resolver = RegisteredThread(target=self._resolve_loop,
                                          name="verify-resolver",
                                          structure="BatchingVerifyService")
        self._resolver.start()
        self._worker = RegisteredThread(target=self._run,
                                        name="verify-flusher",
                                        structure="BatchingVerifyService")
        self._worker.start()

    def submit(self, item: VerifyItem, tag=None) -> Future:
        """`tag` rides the Future through the flusher untouched here;
        routing subclasses (sharding.CrossChannelVerifyService) read
        it in `_route_batch` to split one coalesced batch into
        per-slice dispatch groups.  It must be attached BEFORE the
        enqueue — the flusher may drain the item the instant the put
        lands."""
        fut: Future = Future()
        if tag is not None:
            fut._fmt_shard_tag = tag
        if tracing.armed():
            # the caller's trace context rides the Future through the
            # GuardedQueue handoff: the flusher/resolver threads link
            # their spans under the submitting span, so a tx's trace
            # survives the batch coalescing seam
            fut._fmt_trace_ctx = tracing.current_ctx()
        # Under the lock, either close() has not started (the item lands
        # before close()'s straggler drain) or it has finished setting
        # _stop (we reject here) — no orphaned Futures either way.
        with self._lifecycle:
            if self._stop.is_set():
                fut.set_exception(RuntimeError("verify service is closed"))
                return fut
            self._q.put((item, fut))
        return fut

    def verify_many(self, items: Sequence[VerifyItem],
                    timeout=_DEADLINE_KNOB, tag=None):
        """The policy-engine seam (same shape as TpuVerifier): submit
        each item and gather verdicts.  Concurrent callers' items
        coalesce into shared device batches — this is how ingress
        paths (broadcast filters, gossip-storm verifies) ride ONE
        deadline-batched dispatch across many independent requests
        (SURVEY §2.9 'admission control feeding fixed-size batches').
        `timeout` bounds the WHOLE call, not each item; default is the
        FABRIC_MOD_TPU_VERIFY_DEADLINE knob (explicit None waits
        forever).  On expiry every still-pending Future fails with
        VerifyDeadlineExceeded — typed, so callers can tell a deadline
        from a device failure — and the call raises it.  `tag` is the
        routing label (see `submit`)."""
        if timeout is _DEADLINE_KNOB:
            timeout = verify_deadline_s()
        futs = [self.submit(it, tag=tag) for it in items]
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        out = []
        for f in futs:
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            try:
                out.append(f.result(remaining))
            except FutureTimeout:
                raise self._fail_stragglers(futs, timeout) from None
        return out

    def _fail_stragglers(self, futs: Sequence[Future],
                         timeout: Optional[float]
                         ) -> "VerifyDeadlineExceeded":
        """Deadline expiry: fail every not-yet-resolved Future with the
        typed timeout error so no caller is left parked on a verdict
        the device may never produce.  (A resolver completing a future
        concurrently wins harmlessly — both sides complete through the
        InvalidStateError-tolerant `_complete`.)"""
        pending = [f for f in futs if not f.done()]
        err = VerifyDeadlineExceeded(
            f"verify deadline ({timeout}s) expired with "
            f"{len(pending)} verdict(s) outstanding", deadline_s=timeout)
        for f in pending:
            _complete(f, exc=err)
        self._timeouts.add(1)
        return err

    def verify(self, item: VerifyItem, timeout=_DEADLINE_KNOB) -> bool:
        """Single-item verify under the shared deadline knob (see
        verify_many for the timeout semantics)."""
        if timeout is _DEADLINE_KNOB:
            timeout = verify_deadline_s()
        fut = self.submit(item)
        try:
            return fut.result(timeout)
        except FutureTimeout:
            raise self._fail_stragglers([fut], timeout) from None

    def close(self) -> None:
        """Stop both threads, draining: everything already submitted
        (including batches still in flight on the device) gets a
        verdict — callers may be blocked on their Futures."""
        with self._lifecycle:
            self._stop.set()
        try:
            # leak-checked teardown: a worker/resolver that survives
            # the join is a race report, not a silent daemon park
            assert_joined((self._worker, self._resolver),
                          owner="BatchingVerifyService", timeout=30)
        finally:
            # A submit may have raced the worker's final drain; fail
            # any stragglers rather than leaving callers hung — even
            # when the join raised (a caller parked on a raced Future
            # must not block forever behind the race report).  When
            # the join raised the worker may still be ALIVE, so the
            # consumer pin must be released explicitly or the drain
            # itself would raise a second RaceError, mask the leak
            # report, and leave the stragglers unresolved.
            self._q.release_consumer()
            while True:
                try:
                    _, fut = self._q.get_nowait()
                except queue.Empty:
                    break
                _complete(fut, exc=RuntimeError(
                    "verify service is closed"))
            if self._owns_verifier:
                close = getattr(self._verifier, "close", None)
                if close is not None:
                    close()

    # -- worker side: accumulate + dispatch -------------------------------

    def _route_batch(self, batch):
        """Split one coalesced batch into dispatch groups
        ``[(verifier, subbatch)]``.  The base service is a single
        program: everything goes to the one verifier.  The sharding
        subsystem's cross-channel service overrides this to group by
        the submit tag's mesh slice — one flusher, per-slice fused
        dispatches."""
        return [(self._verifier, batch)]

    def _flush(self, batch) -> None:
        """Marshal + dispatch one batch, then hand it to the resolver.
        Marshalling failures fail the affected GROUP's Futures here
        (a routed batch dispatches group-by-group, and one channel's
        bad marshal must not fail another channel's riders); device
        failures surface on the resolver thread."""
        self._batch_hist.observe(len(batch))
        # stitch the flush span under the FIRST traced submitter (a
        # coalesced batch has many parents; one link beats none, and
        # the span's items attr says how many riders shared it)
        parent = None
        if tracing.armed():
            parent = next(
                (getattr(f, "_fmt_trace_ctx", None) for _, f in batch
                 if getattr(f, "_fmt_trace_ctx", None) is not None),
                None)
        flush_span = tracing.span("verify.flush", parent=parent,
                                  items=len(batch))
        dispatched = []
        with flush_span:
            # the span covers routing + marshal + dispatch ONLY — the
            # backpressure puts below may block on the in-flight
            # window, and that queue-wait is resolver backlog, not
            # flush cost (the PR 9 attribution reads this span)
            try:
                groups = self._route_batch(batch)
            except Exception as e:
                for _, fut in batch:
                    _complete(fut, exc=e)
                return
            for verifier, group in groups:
                items = [b[0] for b in group]
                try:
                    async_fn = getattr(verifier,
                                       "verify_many_async", None)
                    if async_fn is not None:
                        resolve = async_fn(items)
                    else:
                        mask = verifier.verify_many(items)
                        resolve = lambda m=mask: m   # noqa: E731
                except Exception as e:
                    for _, fut in group:
                        _complete(fut, exc=e)
                    continue
                dispatched.append((group, resolve))
        for group, resolve in dispatched:
            # Bounded in-flight window: blocks when `inflight_depth`
            # batches are already executing — backpressure on the
            # worker.  Gauge BEFORE put: the dispatched batch is in
            # flight even while the put blocks, and incrementing
            # after would race the resolver's decrement below zero.
            self._inflight_gauge.add(1)
            self._inflight.put((group, resolve, flush_span.ctx))

    def _run(self) -> None:
        pending: list[tuple[VerifyItem, Future]] = []
        first_ts = 0.0
        while not self._stop.is_set():
            timeout = None
            if pending:
                timeout = max(0.0, first_ts + self.deadline_s - time.time())
            try:
                item = self._q.get(timeout=timeout if pending else 0.05)
                if not pending:
                    first_ts = time.time()
                pending.append(item)
            except queue.Empty:
                pass
            if pending and (len(pending) >= self.max_batch
                            or time.time() - first_ts >= self.deadline_s):
                batch, pending = pending, []
                self._flush(batch)
        # Drain on close: anything submitted before close() still gets
        # a verdict rather than leaving callers hung on their Futures.
        while True:
            try:
                pending.append(self._q.get_nowait())
            except queue.Empty:
                break
        if pending:
            self._flush(pending)
        self._inflight.put(self._SENTINEL)   # resolver: drain then exit

    # -- resolver side: complete futures in dispatch order -----------------

    def _resolve_loop(self) -> None:
        while True:
            got = self._inflight.get()
            if got is self._SENTINEL:
                return
            batch, resolve, flush_ctx = got
            try:
                # the resolve span continues the flush span's trace —
                # the item's journey submit -> flusher -> device ->
                # resolver is one stitched parent chain
                with tracing.span("verify.resolve", parent=flush_ctx,
                                  items=len(batch)):
                    mask = resolve()
                # _complete, not set_result: a deadline-failed
                # straggler must not kill the resolver thread
                for (_, fut), ok in zip(batch, mask):
                    _complete(fut, bool(ok))
            except Exception as e:
                for _, fut in batch:
                    _complete(fut, exc=e)
            finally:
                self._inflight_gauge.add(-1)


class TpuCSP(BCCSP):
    """BCCSP whose Verify path runs on the TPU.

    Key management, hashing of single messages, signing, and symmetric
    crypto delegate to the software provider; `verify`/`verify_batch`
    go to the device.  `hash_many` exposes the device SHA-256 batch
    for pipelines that hash entire blocks.
    """

    def __init__(self, keystore_path: Optional[str] = None,
                 verifier=None, service: Optional[BatchingVerifyService] = None):
        self._sw = _sw.SwCSP(keystore_path)
        self._verifier = verifier or TpuVerifier()
        self._service = service

    # -- delegated host-side ops --
    def key_gen(self, algorithm: str = "P256", ephemeral: bool = True) -> Key:
        return self._sw.key_gen(algorithm, ephemeral)

    def key_import(self, raw: bytes, kind: str) -> Key:
        return self._sw.key_import(raw, kind)

    def get_key(self, ski: bytes) -> Optional[Key]:
        return self._sw.get_key(ski)

    def hash(self, msg: bytes, algorithm: str = "SHA256") -> bytes:
        return self._sw.hash(msg, algorithm)

    def hash_many(self, msgs: Sequence[bytes]) -> np.ndarray:
        from fabric_mod_tpu.ops import sha256
        return sha256.sha256_many(list(msgs))

    def sign(self, key: Key, digest: bytes) -> bytes:
        return self._sw.sign(key, digest)

    def encrypt(self, key: Key, plaintext: bytes) -> bytes:
        return self._sw.encrypt(key, plaintext)

    def decrypt(self, key: Key, ciphertext: bytes) -> bytes:
        return self._sw.decrypt(key, ciphertext)

    # -- device verify path --
    def verify(self, key: _sw.EcdsaKey, signature: bytes, digest: bytes) -> bool:
        if key.curve != "P256":
            return self._sw.verify(key, signature, digest)
        item = VerifyItem(digest, signature, key.public_xy())
        if self._service is not None:
            return self._service.verify(item)
        return bool(self._verifier.verify_many([item])[0])

    def verify_batch(self, items: Sequence[VerifyItem]) -> "list[bool]":
        return [bool(v) for v in self._verifier.verify_many(items)]
