"""The kv ledger: block store + versioned state + history, with
simulation, MVCC commit, and crash recovery.

(reference: core/ledger/kvledger/kv_ledger.go — `CommitLegacy` at
:457-541, recovery at :228-341 `recoverDBs`; the lock-based tx manager
in txmgmt/txmgr/lockbased_txmgr.go; query executors in
query_executor.go; history in kvledger/history/db.go.)

Commit pipeline stage order matches the reference: MVCC validate ->
append block (+flags in metadata) -> apply state batch -> history ->
snapshot/savepoint.  State and history are derivable from the block
store, so on open any gap between the state savepoint and the block
height is replayed — the ledger *is* the checkpoint (SURVEY.md §5.4).
"""
from __future__ import annotations

import hashlib
import os
import threading

from fabric_mod_tpu.utils.racecheck import OrderedLock
from typing import Dict, Iterator, List, Optional, Tuple

from fabric_mod_tpu import faults
from fabric_mod_tpu.ledger.blkstorage import BlockStore
from fabric_mod_tpu.ledger.mvcc import (
    COLUMNAR, validate_and_prepare_batch,
    validate_and_prepare_batch_vectorized)
from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder, parse_tx_rwset
from fabric_mod_tpu.ledger.statedb import UpdateBatch, VersionedDB
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.observability.metrics import (
    MetricOpts, default_provider)
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil

Version = Tuple[int, int]

# Per-block commit timing split (reference: kv_ledger.go:525-539's
# state_validation / block_and_pvtdata_commit / state_commit log line
# + metrics.go histograms)
_mp = default_provider()
H_STATE_VALIDATION = _mp.new_histogram(MetricOpts(
    "ledger", "", "block_processing_state_validation_seconds",
    "MVCC validation time per block"))
H_BLOCK_COMMIT = _mp.new_histogram(MetricOpts(
    "ledger", "", "block_commit_seconds",
    "Block store append time per block"))
H_STATE_COMMIT = _mp.new_histogram(MetricOpts(
    "ledger", "", "state_commit_seconds",
    "State+history apply time per block"))
G_HEIGHT = _mp.new_gauge(MetricOpts(
    "ledger", "", "blockchain_height", "Committed chain height",
    ("channel",)))
# what MVCC did, added once per block
C_MVCC_READS = _mp.new_counter(MetricOpts(
    "fabric", "ledger", "mvcc_reads_total",
    "Recorded reads of the transactions that reached the MVCC check "
    "(those that signature and policy validation left VALID)"))
C_MVCC_INVALID = _mp.new_counter(MetricOpts(
    "fabric", "ledger", "mvcc_invalid_total",
    "Transactions the MVCC check invalidated, by validation code",
    ("code",)))
C_MVCC_RWSET_SOURCE = _mp.new_counter(MetricOpts(
    "fabric", "ledger", "mvcc_rwset_source_total",
    "Transactions by where commit took their read-write set from: "
    "the planes stage's columnar decoder handed over, or a decode of "
    "the envelope on the commit thread", ("source",)))
_MVCC_CODES = (
    (m.TxValidationCode.MVCC_READ_CONFLICT, "MVCC_READ_CONFLICT"),
    (m.TxValidationCode.PHANTOM_READ_CONFLICT, "PHANTOM_READ_CONFLICT"))


class LedgerError(Exception):
    pass


class QueryExecutor:
    """Read-only state access (reference: query_executor.go)."""

    def __init__(self, db: VersionedDB):
        self._db = db

    def get_state(self, ns: str, key: str) -> Optional[bytes]:
        got = self._db.get_state(ns, key)
        return got[0] if got else None

    def get_state_range(self, ns: str, start: str, end: str):
        for key, value, _ in self._db.get_state_range(ns, start, end):
            yield key, value

    def get_private_data(self, ns: str, collection: str,
                         key: str) -> Optional[bytes]:
        from fabric_mod_tpu.ledger.pvtdata import pvt_namespace
        got = self._db.get_state(pvt_namespace(ns, collection), key)
        return got[0] if got else None

    def _execute_query_versioned(self, ns: str, query):
        """Shared rich-query core: ([(key, doc, version)], bookmark).
        A bookmark bounds the scan start (execute skips the boundary
        key itself), so each page costs what remains, not the whole
        namespace."""
        from fabric_mod_tpu.ledger import richquery
        q = richquery.RichQuery.parse(query)
        start = q.bookmark if (q.bookmark and not q.sort) else ""
        rows = self._db.get_state_range(ns, start, "")
        return richquery.execute(rows, q)

    def execute_query(self, ns: str, query):
        """Rich JSON-selector query over a namespace (reference:
        statecouchdb.go:1230 ExecuteQuery).  Returns
        ([(key, doc)], bookmark)."""
        matches, bookmark = self._execute_query_versioned(ns, query)
        return [(k, doc) for k, doc, _ver in matches], bookmark


class TxSimulator(QueryExecutor):
    """Records reads/writes into an RWSetBuilder
    (reference: lockbased_txmgr.go NewTxSimulator + rwset_builder)."""

    def __init__(self, db: VersionedDB, txid: str):
        super().__init__(db)
        self.txid = txid
        self._rw = RWSetBuilder()
        self._writes: Dict[Tuple[str, str], Optional[bytes]] = {}

    def get_state(self, ns: str, key: str) -> Optional[bytes]:
        if (ns, key) in self._writes:       # read-your-writes
            return self._writes[(ns, key)]
        got = self._db.get_state(ns, key)
        self._rw.add_read(ns, key, got[1] if got else None)
        return got[0] if got else None

    def get_state_range(self, ns: str, start: str, end: str):
        """Range over committed state merged with this simulation's own
        writes (read-your-writes, consistent with get_state).  The
        phantom fingerprint records committed results only: at
        validation time the re-executed range sees earlier txs' writes
        but never this tx's own."""
        results = []
        merged = {}
        for key, value, ver in self._db.get_state_range(ns, start, end):
            results.append((key, ver))
            merged[key] = value
        self._rw.add_range_query(ns, start, end, True, results)
        for (wns, key), value in self._writes.items():
            if wns != ns or not (start <= key and (not end or key < end)):
                continue
            if value is None:
                merged.pop(key, None)
            else:
                merged[key] = value
        return iter(sorted(merged.items()))

    def execute_query(self, ns: str, query):
        """Rich query during simulation: each returned key joins the
        read set, but — exactly like the reference — the query itself
        is NOT re-executed at validation (no phantom protection for
        rich queries; statecouchdb documents the same limitation)."""
        matches, bookmark = self._execute_query_versioned(ns, query)
        out = []
        for key, doc, ver in matches:
            self._rw.add_read(ns, key, ver)
            out.append((key, doc))
        return out, bookmark

    def set_state(self, ns: str, key: str, value: bytes) -> None:
        self._writes[(ns, key)] = value
        self._rw.add_write(ns, key, value)

    def delete_state(self, ns: str, key: str) -> None:
        self._writes[(ns, key)] = None
        self._rw.add_write(ns, key, None)

    def set_state_metadata(self, ns: str, key: str, name: str,
                           value: bytes) -> None:
        """Key metadata write — e.g. the VALIDATION_PARAMETER
        endorsement override key-level validation reads (reference:
        the shim's PutStateMetadata -> rwset metadata writes)."""
        self._rw.add_metadata_write(ns, key, name, value)

    # -- private data (reference: the shim's PutPrivateData path) -----
    def set_private_data(self, ns: str, collection: str, key: str,
                         value: bytes) -> None:
        from fabric_mod_tpu.ledger.pvtdata import pvt_namespace
        self._writes[(pvt_namespace(ns, collection), key)] = value
        self._rw.add_pvt_write(ns, collection, key, value)

    def delete_private_data(self, ns: str, collection: str,
                            key: str) -> None:
        from fabric_mod_tpu.ledger.pvtdata import pvt_namespace
        self._writes[(pvt_namespace(ns, collection), key)] = None
        self._rw.add_pvt_write(ns, collection, key, None)

    def get_private_data(self, ns: str, collection: str,
                         key: str) -> Optional[bytes]:
        from fabric_mod_tpu.ledger.pvtdata import pvt_namespace
        pns = pvt_namespace(ns, collection)
        if (pns, key) in self._writes:      # read-your-writes
            return self._writes[(pns, key)]
        got = self._db.get_state(pns, key)
        # private reads are NOT recorded in the public read set (the
        # reference keys hashed reads; omitted — write-only MVCC here)
        return got[0] if got else None

    def done(self) -> m.TxReadWriteSet:
        return self._rw.build()

    def done_pvt(self) -> Optional[m.TxPvtReadWriteSet]:
        """The plaintext private write-sets for transient staging."""
        return self._rw.build_pvt()


class HistoryDB:
    """(ns, key) -> [(block, tx), ...] — rebuildable from blocks
    (reference: kvledger/history/db.go)."""

    def __init__(self):
        self._hist: Dict[Tuple[str, str], List[Version]] = {}

    def commit(self, block_num: int,
               tx_writes: List[Tuple[int, str, str]]) -> None:
        for tx_num, ns, key in tx_writes:
            self._hist.setdefault((ns, key), []).append((block_num, tx_num))

    def get_history_for_key(self, ns: str, key: str) -> List[Version]:
        return list(self._hist.get((ns, key), []))


def tx_rwset_from_envelope(env: m.Envelope) -> Optional[m.TxReadWriteSet]:
    """Envelope -> TxReadWriteSet of its (first) endorser action, or
    None when absent/malformed (reference: rwsetutil on the
    ChaincodeAction.results path)."""
    try:
        payload = protoutil.unmarshal_envelope_payload(env)
        tx = protoutil.extract_endorser_tx(payload)
        cca, _prp, _ends = protoutil.tx_rwset_and_endorsements(tx.actions[0])
        return m.TxReadWriteSet.decode(cca.results)
    except Exception:
        return None


class KvLedger:
    """One channel's ledger (reference: kv_ledger.go kvLedger)."""

    SNAPSHOT_EVERY = 64
    TRANSIENT_RETENTION_BLOCKS = 100

    def __init__(self, ledger_dir: str, ledger_id: str = "ch",
                 durable: bool = True):
        self.ledger_id = ledger_id
        self.dir = ledger_dir
        self._durable = durable
        os.makedirs(ledger_dir, exist_ok=True)
        # rank 10 in the lock hierarchy (utils/racecheck.py): the
        # commit path nests transient (20) / pvt (30) store locks
        # inside this one; an inversion anywhere raises instead of
        # deadlocking (the -race analog, SURVEY Â§5.2)
        self._lock = OrderedLock(10, "kvledger")
        # commit notification for event deliver streams (reference:
        # the ledger's CommitNotifier consumed by deliverevents.go)
        self.height_changed = threading.Condition()
        self.blockstore = BlockStore(os.path.join(ledger_dir, "chains"))
        self._state_path = os.path.join(ledger_dir, "state.snap")
        if durable:
            # log-structured disk stores: O(delta) recovery, values on
            # disk (reference contract: stateleveldb.go:379 + history/db.go)
            from fabric_mod_tpu.ledger.durable import (
                DurableHistoryDB, DurableStateDB)
            self.state = DurableStateDB(os.path.join(ledger_dir, "state"))
            self.history = DurableHistoryDB(
                os.path.join(ledger_dir, "history"))
        else:
            self.state = VersionedDB.load(self._state_path)
            self.history = HistoryDB()
        # private data machinery (attach_pvt wires the live stores;
        # absent, hashed collections commit without plaintext — the
        # reference's "missing pvt data, reconcile later" stance)
        self._transient = None
        self._pvtstore = None
        self._btl_fn = None
        # cached state-fingerprint accumulator (XOR of per-entry
        # hashes): None until the first fingerprint seeds it with a
        # full scan, then maintained incrementally by every state
        # mutation through _apply_state_updates
        self._fp_acc: Optional[int] = None
        # lifecycle deploy events + historical collection configs
        # (reference: cceventmgmt + confighistory) — file-backed, fed
        # by both commit and recovery replay below
        from fabric_mod_tpu.ledger.confighistory import (
            ConfigHistoryManager)
        self.confighistory = ConfigHistoryManager(
            os.path.join(ledger_dir, "confighistory.jsonl"))
        self._recover()

    def attach_pvt(self, transient_store, pvtdata_store,
                   btl_fn=None) -> None:
        """Wire the transient + pvt stores (reference: the coordinator
        binding of gossip/privdata/coordinator.go:498)."""
        self._transient = transient_store
        self._pvtstore = pvtdata_store
        self._btl_fn = btl_fn or (lambda ns, coll: 0)

    def _reset_state_db(self):
        """State ran ahead of a cropped block store: rebuild from
        genesis (reference: kv_ledger.go recovery edge)."""
        if self._durable:
            import shutil
            from fabric_mod_tpu.ledger.durable import DurableStateDB
            self.state.close()
            shutil.rmtree(os.path.join(self.dir, "state"))
            self.state = DurableStateDB(os.path.join(self.dir, "state"))
        else:
            self.state = VersionedDB()
        self._fp_acc = None

    # -- recovery --------------------------------------------------------
    def _recover(self) -> None:
        """Replay blocks past the savepoints (reference:
        kv_ledger.go:239 syncStateAndHistoryDBWithBlockstore).  With
        durable stores both state and history resume from their own
        savepoints — O(delta), not O(chain) (VERDICT r2 weak #6)."""
        height = self.blockstore.height
        if self.state.savepoint >= height:
            self._reset_state_db()
        hist_sp = getattr(self.history, "savepoint", -1)
        if hist_sp >= height and self._durable:
            import shutil
            from fabric_mod_tpu.ledger.durable import DurableHistoryDB
            self.history.close()
            shutil.rmtree(os.path.join(self.dir, "history"))
            self.history = DurableHistoryDB(
                os.path.join(self.dir, "history"))
            hist_sp = -1
        # confighistory writes AFTER state commit, so its savepoint can
        # trail state's by one block after a crash: include it in the
        # replay floor (commit/replay are idempotent per store)
        start = min(self.state.savepoint, hist_sp,
                    self.confighistory.savepoint) + 1
        for block in self.blockstore.iter_blocks(max(0, start)):
            num = block.header.number
            replay_state = num > self.state.savepoint
            self._apply_block_effects(block, replay_state=replay_state)

    def _apply_block_effects(self, block: m.Block,
                             replay_state: bool) -> None:
        """Re-derive state/history updates of a committed block from
        its stored txflags (no re-validation on replay)."""
        flags = protoutil.block_txflags(block)
        num = block.header.number
        batch = UpdateBatch()
        hist: List[Tuple[int, str, str]] = []
        for tx_num, env in enumerate(protoutil.get_envelopes(block)):
            if flags[tx_num] != m.TxValidationCode.VALID:
                continue
            rwset = tx_rwset_from_envelope(env)
            if rwset is None:
                continue
            for ns, kv in parse_tx_rwset(rwset):
                for w in kv.writes:
                    if w.is_delete:
                        batch.delete(ns, w.key, (num, tx_num))
                    else:
                        batch.put(ns, w.key, w.value, (num, tx_num))
                    hist.append((tx_num, ns, w.key))
                for mw in kv.metadata_writes:
                    batch.put_metadata(
                        ns, mw.key,
                        {e.name: e.value for e in mw.entries},
                        (num, tx_num))
        if replay_state:
            self._apply_state_updates(batch, num)
        self.history.commit(num, hist)
        self.confighistory.handle_block_writes(
            num, [(ns, key, value)
                  for (ns, key), (value, _v) in batch.updates.items()])

    # -- simulation ------------------------------------------------------
    def new_tx_simulator(self, txid: str) -> TxSimulator:
        return TxSimulator(self.state, txid)

    def new_query_executor(self) -> QueryExecutor:
        return QueryExecutor(self.state)

    # -- commit ----------------------------------------------------------
    def commit_block(self, block: m.Block,
                     incoming_flags: Optional[List[int]] = None,
                     rwsets=None) -> List[int]:
        """MVCC-validate + commit a block whose signature/policy
        verdicts are `incoming_flags` (defaults to the flags already in
        the block metadata, e.g. from the validator).  Returns final
        flags.  `rwsets` (batchdecode.BlockRWSets | None) is the
        validator's stage-time columnar body decode riding the
        staged→commit handoff: header facts (txid/type) are reused
        instead of re-decoded, and every row the decoder accepted
        goes to the vectorized MVCC as the COLUMNAR sentinel and is
        read from the planes, never decoded again here (bit-identical
        flags, one bulk statedb call).  Without planes (a block under
        batchdecode.COLUMNAR_MIN_ROWS, a caller that has none) every
        row is decoded from its envelope and takes the serial MVCC.
        (reference: kv_ledger.go:457 CommitLegacy)"""
        with self._lock:
            num = block.header.number
            if num != self.blockstore.height:
                raise LedgerError(
                    f"commit out of order: {num} at height "
                    f"{self.blockstore.height}")
            envs = protoutil.get_envelopes(block)
            if incoming_flags is None:
                # fail closed: absent metadata flags decode to
                # NOT_VALIDATED, never to VALID
                incoming_flags = list(protoutil.block_txflags(block))
            elif len(incoming_flags) != len(envs):
                raise LedgerError(
                    f"flags length {len(incoming_flags)} != "
                    f"{len(envs)} txs")
            # "mvcc" covers the commit-side host unpack (span
            # `rwset_extract`) + the version compares (span
            # `mvcc_validate`) — together the conflict-detection cost
            # the vectorized-MVCC roadmap item targets.
            #
            # Where a row's rwset comes from follows from what stage
            # handed over: a row of `rwsets` the scanner accepted is
            # read from the planes; a scanner-refused row, a block
            # staged without planes, a non-endorser row and a
            # private-data row (while a transient store is wired)
            # are decoded from the envelope.  One commit of a block of
            # 2.2 KB blind writes / of Smallbank-shaped rows (1.81
            # reads, ~1.5 writes), planes against rwsets=None, median
            # ms a block (scripts/commit_crossover.py; PR 35):
            #
            #   rows a block            96     120     250     500
            #   chip machine's host
            #     blind, planes       6.48    7.96   13.33   23.29
            #     blind, envelope    14.06   17.51   32.32   62.21
            #     smallbank, planes   6.99    8.25   13.78   24.57
            #     smallbank, envel.  16.08   18.80   37.01   69.59
            #   sandbox (CPU, a shared machine)
            #     blind, planes       4.71    8.79    9.35   23.14
            #     blind, envelope    10.42   22.53   29.09   56.65
            #     smallbank, planes   4.92    6.69   10.94   21.25
            #     smallbank, envel.  12.24   15.89   28.19   62.47
            #
            # Planes win by x2.2-2.8 at every size from the one at
            # which stage starts to make them, so there is no row
            # count here beside COLUMNAR_MIN_ROWS.
            bodies = rwsets.bodies if rwsets is not None else None
            with tracing.span("mvcc", block=num):
                with tracing.span("rwset_extract", block=num) as ex:
                    txs = []
                    n_planes = n_decoded = 0
                    for tx_num, (env, flag) in enumerate(
                            zip(envs, incoming_flags)):
                        if rwsets is not None and \
                                rwsets.txids[tx_num] is not None:
                            # stage-time spine facts, value-identical
                            # to the generic header decode below
                            txid = rwsets.txids[tx_num]
                            ch_type = rwsets.types[tx_num]
                        else:
                            try:
                                ch = protoutil.envelope_channel_header(
                                    env)
                                txid, ch_type = ch.tx_id, ch.type
                            except Exception:
                                txs.append(
                                    ("", None,
                                     m.TxValidationCode.BAD_PAYLOAD))
                                continue
                        if ch_type != m.HeaderType.ENDORSER_TRANSACTION:
                            # config/control txs carry no rwset; they
                            # commit with no state effects (their
                            # effect is the bundle swap done by the
                            # channel machinery upstream)
                            txs.append((txid, m.TxReadWriteSet(), flag))
                        elif bodies is not None \
                                and bodies[tx_num] is not None \
                                and (self._transient is None
                                     or not bodies[tx_num].has_pvt):
                            # pvt-bearing txs keep the materialized
                            # rwset when a transient store is wired —
                            # _commit_pvt walks its collection hashes
                            txs.append((txid, COLUMNAR, flag))
                            n_planes += 1
                        else:
                            txs.append(
                                (txid, tx_rwset_from_envelope(env), flag))
                            n_decoded += 1
                    ex.set(planes=n_planes, decoded=n_decoded)
                stats = {}
                with tracing.span(
                        "mvcc_validate", block=num, txs=len(txs),
                        path="vector" if n_planes else "serial") as sp, \
                        H_STATE_VALIDATION.time():
                    if n_planes:
                        flags, batch, tx_writes = \
                            validate_and_prepare_batch_vectorized(
                                txs, self.state, num, rwsets, stats)
                    else:
                        flags, batch, tx_writes = \
                            validate_and_prepare_batch(
                                txs, self.state, num, stats)
                    invalid = [(name, flags.count(code))
                               for code, name in _MVCC_CODES]
                    sp.set(reads=stats["reads"],
                           conflicts=sum(n for _, n in invalid))
            C_MVCC_READS.add(stats["reads"])
            if n_planes:
                C_MVCC_RWSET_SOURCE.with_labels("planes").add(n_planes)
            if n_decoded:
                C_MVCC_RWSET_SOURCE.with_labels("envelope").add(n_decoded)
            for name, n in invalid:
                if n:
                    C_MVCC_INVALID.with_labels(name).add(n)
            protoutil.set_block_txflags(block, bytes(flags))
            with tracing.span("ledger_write", block=num):
                with H_BLOCK_COMMIT.time():
                    self.blockstore.add_block(block)
                # the crash seam of the recovery contract: an armed
                # error-mode rule kills the commit AFTER the block is
                # durable in the block store but BEFORE any statedb /
                # history / pvt effect — exactly the statedb-behind-
                # blockstore window _recover() must replay on reopen
                faults.point("peer.ledger.crash")
                with H_STATE_COMMIT.time():
                    self._apply_state_updates(batch, num)
                    # per-tx writes (not the deduped batch) so commit
                    # and recovery replay record identical history
                    self.history.commit(num, tx_writes)
                    self._commit_pvt(num, txs, flags)
                    self.confighistory.handle_block_writes(
                        num, [(ns, key, value)
                              for (ns, key), (value, _v)
                              in batch.updates.items()])
            G_HEIGHT.with_labels(self.ledger_id).set(
                self.blockstore.height)
            if not self._durable and (num + 1) % self.SNAPSHOT_EVERY == 0:
                self.state.snapshot(self._state_path)
        with self.height_changed:
            self.height_changed.notify_all()
        return flags

    def _commit_pvt(self, num: int, txs, flags) -> None:
        """Apply plaintext private writes for VALID txs whose hashes
        the block carries, pulled from the transient store and
        hash-verified; then run BTL purges (reference:
        coordinator.go:498 StoreBlock + pvtstatepurgemgmt)."""
        if self._transient is None:
            return
        from fabric_mod_tpu.ledger.pvtdata import (
            PvtDataMismatchError, pvt_namespace, verify_pvt_against_hashes)
        batch = UpdateBatch()
        consumed = []
        for tx_num, (txid, rwset, _flag) in enumerate(txs):
            if flags[tx_num] != m.TxValidationCode.VALID or rwset is None:
                continue
            if rwset is COLUMNAR:
                # columnar rows are only taken for bodies without
                # collection hashes — same as the empty-`hashed` skip
                continue
            hashed = {}                    # (ns, coll) -> HashedRWSet
            for ns_entry in rwset.ns_rwset:
                for ch in ns_entry.collection_hashed_rwset:
                    hashed[(ns_entry.namespace, ch.collection_name)] = \
                        m.HashedRWSet.decode(ch.hashed_rwset)
            if not hashed:
                continue
            candidates = self._transient.get_by_txid(txid)
            for (ns, coll), hset in hashed.items():
                kv = self._find_matching_pvt(candidates, ns, coll, hset)
                if kv is None:
                    # missing: record the digest so the reconciler can
                    # pull it from an eligible peer later
                    self._pvtstore.report_missing(num, tx_num, ns, coll)
                    continue
                for w in kv.writes:
                    pns = pvt_namespace(ns, coll)
                    if w.is_delete:
                        batch.delete(pns, w.key, (num, tx_num))
                    else:
                        batch.put(pns, w.key, w.value, (num, tx_num))
                self._pvtstore.commit(num, tx_num, ns, coll, kv,
                                      self._btl_fn(ns, coll))
            consumed.append(txid)
        if len(batch):
            self._apply_state_updates(batch, num)
        # purge ALL txids this block carried (valid or not — an
        # invalidated private tx would otherwise leak its plaintext in
        # the transient store forever), plus endorsement leftovers
        # older than the retention window (reference: the commit-path
        # PurgeBelowHeight)
        self._transient.purge_by_txids(
            [txid for txid, _r, _f in txs if txid])
        self._transient.purge_below_height(
            max(0, num - self.TRANSIENT_RETENTION_BLOCKS))
        # BTL expiry: delete only keys whose committed version still
        # IS the expiring write — a later rewrite has its own expiry
        # (reference: pvtstatepurgemgmt's version-matched purge)
        purge_batch = UpdateBatch()
        for bn, tn, ns, coll, keys in self._pvtstore.expiring_at(num):
            pns = pvt_namespace(ns, coll)
            for key in keys:
                if self.state.get_version(pns, key) == (bn, tn):
                    purge_batch.delete(pns, key, (num, 0))
        if len(purge_batch):
            self._apply_state_updates(purge_batch, num)
        self._pvtstore.purge(num)
        # ONE durability barrier for the whole block's private data —
        # per-collection fsyncs would multiply commit latency by the
        # number of collections (the blockstore also syncs per block)
        if hasattr(self._pvtstore, "sync"):
            self._pvtstore.sync()

    # -- reconciliation (reference: gossip/privdata/reconcile.go:339) ----
    def get_pvt(self, block_num: int, tx_num: int):
        """Committed plaintext private write-sets for one tx:
        [(ns, collection, KVRWSet)] — the public surface reconciliation
        responders serve from."""
        if self._pvtstore is None:
            return []
        return self._pvtstore.get(block_num, tx_num)

    def missing_pvt_count(self) -> int:
        """Total reconciliation backlog (exported as a gauge by the
        gossip reconciler — the 'is the queue draining?' signal)."""
        if self._pvtstore is None or not hasattr(self._pvtstore,
                                                 "missing_count"):
            return 0
        return self._pvtstore.missing_count()

    def missing_pvt(self, limit: int = 50):
        """Unreconciled (block, tx, ns, collection) digests, dropping
        any whose BTL already lapsed (no longer needed or wanted)."""
        if self._pvtstore is None:
            return []
        out = []
        for bn, tn, ns, coll in self._pvtstore.missing(limit):
            if self._pvt_expired(bn, ns, coll):
                self._pvtstore.drop_missing(bn, tn, ns, coll)
                continue
            out.append((bn, tn, ns, coll))
        return out

    def _pvt_expired(self, block_num: int, ns: str, coll: str) -> bool:
        """BTL lapse check aligned with the purge schedule: data from
        `block_num` is purged while committing block block_num+btl+1,
        i.e. it is dead once height ≥ block_num+btl+2 — before that,
        eligible peers still serve it and backfills are welcome."""
        btl = self._btl_fn(ns, coll)
        return btl > 0 and block_num + btl + 2 <= self.height

    def reconcile_pvt(self, block_num: int, tx_num: int, ns: str,
                      coll: str, kv: m.KVRWSet) -> bool:
        """Backfill a previously-missing private write-set obtained
        from a peer: re-verify it against the hashes the committed
        block carries, then apply writes version-aware (a key already
        rewritten by a LATER block keeps the newer value).  Returns
        True when the digest was resolved."""
        from fabric_mod_tpu.ledger.pvtdata import (
            PvtDataMismatchError, pvt_namespace, verify_pvt_against_hashes)
        with self._lock:
            if self._pvtstore is None or \
                    not self._pvtstore.is_missing(block_num, tx_num,
                                                  ns, coll):
                return False
            if self._pvt_expired(block_num, ns, coll):
                self._pvtstore.drop_missing(block_num, tx_num, ns, coll)
                return False               # expired while missing
            block = self.blockstore.get_block_by_number(block_num)
            if block is None:
                return False
            flags = protoutil.block_txflags(block)
            envs = protoutil.get_envelopes(block)
            if tx_num >= len(envs) or \
                    flags[tx_num] != m.TxValidationCode.VALID:
                self._pvtstore.drop_missing(block_num, tx_num, ns, coll)
                return False
            rwset = tx_rwset_from_envelope(envs[tx_num])
            hset = None
            if rwset is not None:
                for ns_entry in rwset.ns_rwset:
                    if ns_entry.namespace != ns:
                        continue
                    for ch in ns_entry.collection_hashed_rwset:
                        if ch.collection_name == coll:
                            hset = m.HashedRWSet.decode(ch.hashed_rwset)
            if hset is None:
                self._pvtstore.drop_missing(block_num, tx_num, ns, coll)
                return False               # block never hashed this coll
            try:
                verify_pvt_against_hashes(hset, kv)
            except PvtDataMismatchError:
                return False               # forged response; keep waiting
            batch = UpdateBatch()
            pns = pvt_namespace(ns, coll)
            later_keys = self._pvtstore.later_written_keys(
                block_num, tx_num, ns, coll)
            for w in kv.writes:
                cur = self.state.get_version(pns, w.key)
                if cur is not None and cur >= (block_num, tx_num):
                    continue               # a later tx already wrote it
                if w.key in later_keys:
                    continue               # later delete left no version
                if w.is_delete:
                    batch.delete(pns, w.key, (block_num, tx_num))
                else:
                    batch.put(pns, w.key, w.value, (block_num, tx_num))
            if len(batch):
                # keep the savepoint where it is: this backfills an old
                # block, it does not advance commit progress
                self._apply_state_updates(batch, self.state.savepoint)
            self._pvtstore.commit(block_num, tx_num, ns, coll, kv,
                                  self._btl_fn(ns, coll))
            return True

    @staticmethod
    def _find_matching_pvt(candidates, ns, coll, hset):
        from fabric_mod_tpu.ledger.pvtdata import (
            PvtDataMismatchError, verify_pvt_against_hashes)
        for cand in candidates:
            for ns_pvt in cand.ns_pvt_rwset:
                if ns_pvt.namespace != ns:
                    continue
                for cp in ns_pvt.collection_pvt_rwset:
                    if cp.collection_name != coll:
                        continue
                    kv = m.KVRWSet.decode(cp.rwset)
                    try:
                        verify_pvt_against_hashes(hset, kv)
                        return kv
                    except PvtDataMismatchError:
                        continue           # forged/stale candidate
        return None

    # -- state fingerprint -----------------------------------------------
    # The digest is height ‖ an XOR of independent per-entry hashes
    # (one per state row, one per key's metadata dict).  XOR is the
    # point: it makes the accumulator ORDER-FREE and INVERTIBLE, so a
    # commit folds its UpdateBatch in O(batch) — remove the old
    # entry's hash, add the new one — instead of re-scanning a
    # million-key state per block.  Each entry hash is an injective
    # length-prefixed encoding under a domain tag ("S" rows, "M"
    # metadata), so colliding entries would need a sha256 collision.

    @staticmethod
    def _fp_entry(tag: bytes, ns: str, key: str, tail: bytes) -> int:
        h = hashlib.sha256(tag)
        for part in (ns.encode(), key.encode()):
            h.update(len(part).to_bytes(4, "big"))
            h.update(part)
        h.update(tail)
        return int.from_bytes(h.digest(), "big")

    @classmethod
    def _fp_row(cls, ns: str, key: str, value: bytes,
                ver: Version) -> int:
        tail = (len(value).to_bytes(4, "big") + value
                + ver[0].to_bytes(8, "big") + ver[1].to_bytes(8, "big"))
        return cls._fp_entry(b"S", ns, key, tail)

    @classmethod
    def _fp_meta(cls, ns: str, key: str,
                 entries: Dict[str, bytes]) -> int:
        parts = [len(entries).to_bytes(4, "big")]
        for name in sorted(entries):
            for part in (name.encode(), entries[name]):
                parts.append(len(part).to_bytes(4, "big"))
                parts.append(part)
        return cls._fp_entry(b"M", ns, key, b"".join(parts))

    def _fp_scan_acc(self) -> int:
        acc = 0
        for ns, key, value, ver in self.state.iter_state():
            acc ^= self._fp_row(ns, key, value, ver)
        for ns, key, entries in self.state.iter_metadata():
            acc ^= self._fp_meta(ns, key, entries)
        return acc

    def _fp_fold(self, batch: UpdateBatch) -> None:
        """Fold one UpdateBatch into the cached accumulator — the
        exact delta statedb.apply_updates is about to make (put keeps
        metadata, delete drops it, metadata writes bump the row
        version and skip rows absent after the value pass).  Called
        BEFORE the apply so the old entries are still readable."""
        acc = self._fp_acc
        state = self.state
        for (ns, key), (value, version) in batch.updates.items():
            old = state.get_state(ns, key)
            if old is not None:
                acc ^= self._fp_row(ns, key, old[0], old[1])
                if value is None:
                    oldm = state.get_metadata(ns, key)
                    if oldm:
                        acc ^= self._fp_meta(ns, key, oldm)
            if value is not None:
                acc ^= self._fp_row(ns, key, value, version)
        for (ns, key), (entries, version) in batch.meta_updates.items():
            upd = batch.updates.get((ns, key))
            if upd is not None:
                value, ver = upd
                if value is None:
                    continue          # row gone after the value pass
            else:
                got = state.get_state(ns, key)
                if got is None:
                    continue          # metadata without a key: no-op
                value, ver = got
            acc ^= self._fp_row(ns, key, value, ver)
            acc ^= self._fp_row(ns, key, value, version)
            oldm = state.get_metadata(ns, key)
            if oldm:
                acc ^= self._fp_meta(ns, key, oldm)
            if entries:
                acc ^= self._fp_meta(ns, key, dict(entries))
        self._fp_acc = acc

    def _apply_state_updates(self, batch: UpdateBatch,
                             height: int) -> None:
        """EVERY state mutation funnels through here (commit, pvt
        plaintext, BTL purge, reconciliation backfill, recovery
        replay) so the fingerprint accumulator can never silently
        drift from the statedb it summarizes."""
        if self._fp_acc is not None and len(batch):
            self._fp_fold(batch)
        self.state.apply_updates(batch, height)

    # -- queries ---------------------------------------------------------
    def state_fingerprint(self) -> str:
        """Deterministic digest of the ENTIRE committed state: every
        (ns, key, value, version) row plus every key-metadata entry
        (VALIDATION_PARAMETER included) plus the chain height.  Two
        ledgers that committed the same blocks with the same verdicts
        agree bit-for-bit — the commit-pipeline differential's
        equality oracle (bench.py --metric commitpipe/statescale,
        tests/test_commitpipe.py).  The first call full-scans to seed
        the accumulator; later calls are O(1) because every commit
        folded its own delta (state_fingerprint_full stays as the
        scan-from-scratch oracle).

        Taken under the COMMIT lock: commit_block advances the block
        store before applying state, so an unlocked scan racing an
        in-flight commit would hash height N+1 with block N's writes
        missing — a phantom divergence that is pure read timing (the
        soak harness's convergence checker hit exactly this on the
        freshest block of whichever peer committed last)."""
        with tracing.span("fingerprint", channel=self.ledger_id):
            with self._lock:
                if self._fp_acc is None:
                    self._fp_acc = self._fp_scan_acc()
                h = hashlib.sha256(self.height.to_bytes(8, "big"))
                h.update(self._fp_acc.to_bytes(32, "big"))
                return h.hexdigest()

    def state_fingerprint_full(self) -> str:
        """Scan-from-scratch recompute, bypassing the cached
        accumulator — the incremental path's differential oracle
        (tests assert it equals state_fingerprint after arbitrary
        commit/pvt/reconcile histories)."""
        with self._lock:
            h = hashlib.sha256(self.height.to_bytes(8, "big"))
            h.update(self._fp_scan_acc().to_bytes(32, "big"))
            return h.hexdigest()

    @property
    def height(self) -> int:
        return self.blockstore.height

    def get_block_by_number(self, num: int) -> Optional[m.Block]:
        return self.blockstore.get_block_by_number(num)

    def get_transaction_by_id(self, txid: str) -> Optional[m.ProcessedTransaction]:
        loc = self.blockstore.get_tx_loc(txid)
        if loc is None:
            return None
        block = self.blockstore.get_block_by_number(loc[0])
        if block is None:
            return None                    # known txid, pruned block
        flags = protoutil.block_txflags(block)
        return m.ProcessedTransaction(
            transaction_envelope=protoutil.get_envelopes(block)[loc[1]],
            validation_code=flags[loc[1]])

    def tx_id_exists(self, txid: str) -> bool:
        return self.blockstore.get_tx_loc(txid) is not None

    def snapshot_to(self, out_dir: str) -> dict:
        """Consistent snapshot export: ledger/snapshot.generate_snapshot
        under the commit lock, so no block lands mid-iteration of the
        state it seals."""
        from fabric_mod_tpu.ledger.snapshot import generate_snapshot
        with self._lock:
            return generate_snapshot(self, out_dir)

    def close(self) -> None:
        with self._lock:
            if self._durable:
                self.state.close()
                self.history.close()
            else:
                self.state.snapshot(self._state_path)
            # attached pvt/transient stores may hold open op-logs
            for store in (self._transient, self._pvtstore):
                if store is not None and hasattr(store, "close"):
                    store.close()
            self.blockstore.close()


class LedgerManager:
    """Open/create ledgers by id (reference: ledgermgmt/ledger_mgmt.go)."""

    def __init__(self, root_dir: str):
        self.root = root_dir
        os.makedirs(root_dir, exist_ok=True)
        self._ledgers: Dict[str, KvLedger] = {}

    def create_or_open(self, ledger_id: str) -> KvLedger:
        if ledger_id not in self._ledgers:
            self._ledgers[ledger_id] = KvLedger(
                os.path.join(self.root, ledger_id), ledger_id)
        return self._ledgers[ledger_id]

    def ledger_ids(self) -> List[str]:
        existing = set(self._ledgers)
        if os.path.isdir(self.root):
            existing.update(os.listdir(self.root))
        return sorted(existing)

    def close(self) -> None:
        for led in self._ledgers.values():
            led.close()
