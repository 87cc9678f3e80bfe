"""Block validation: the north-star hot path, one device batch per block.

(reference: core/committer/txvalidator/v20/validator.go:182-267
`TxValidator.Validate` + `ValidateTx` at :300-455,
core/common/validation/msgvalidation.go:248 `ValidateTransaction`,
the plugin dispatcher at plugindispatcher/dispatcher.go:102, the
default VSCC at handlers/validation/builtin/v20/validation_logic.go:185,
and the endorsement signature-set construction at
statebased/validator_keylevel.go:245-258.)

Where the reference fans out one goroutine per transaction behind a
semaphore and verifies each ECDSA signature as it reaches it, this
validator makes the data flow explicit and device-shaped:

  pass 1 (host)   unpack every tx (a block of batchdecode.
                  COLUMNAR_MIN_ROWS rows or more: two columnar
                  pre-passes, then per-tx staging over their rows; a
                  smaller one: the generic per-tx decode chain alone);
                  syntactic checks; creator identity
                  validation; stage creator signature + every
                  endorsement signature of every tx into ONE
                  BatchCollector (the policy engine's two-phase
                  prepare handles dedup/principal logic)
  pass 2 (device) verifier.verify_many(collector.items) — a single
                  jitted dispatch for the whole block
  pass 3 (host)   resolve creator verdicts, finish each endorsement-
                  policy decision against the mask, mark duplicate
                  tx ids, write the txflags bitmap

MVCC and commit stay in the ledger (kvledger.commit_block).
"""
from __future__ import annotations

import functools

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.observability.metrics import (MetricOpts,
                                                  default_provider)
from fabric_mod_tpu.peer.mcs import signatures_unsatisfied
from fabric_mod_tpu.policy import ApplicationPolicyEvaluator, BatchCollector
from fabric_mod_tpu.protos import batchdecode
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from fabric_mod_tpu.protos.protoutil import SignedData

V = m.TxValidationCode

_STAGED_ITEMS_OPTS = MetricOpts(
    "fabric", "validator", "staged_verify_items",
    help="Unique verify items staged per block (the device batch size).")
_DEDUP_SAVED_OPTS = MetricOpts(
    "fabric", "validator", "dedup_saved_items",
    help="Verify requests answered by within-block dedup instead of a "
         "device lane (key-level candidates and repeated signature "
         "sets re-stage identical items; an implicit meta policy's "
         "leaves share one staging and no longer count here: see "
         "fabric_policy_meta_shared_resolutions_total).")
_META_SHARED_OPTS = MetricOpts(
    "fabric", "policy", "meta_shared_resolutions_total",
    help="Identity resolutions (deserialize, validate, stage) an "
         "implicit meta policy's sub-policies took from one shared "
         "pass instead of resolving again: (leaves - 1) x distinct "
         "identities per evaluation; added once per block.")
_RAW_ITEMS_OPTS = MetricOpts(
    "fabric", "validator", "staged_raw_message_items",
    help="Staged items carrying raw messages instead of host digests "
         "(FABRIC_MOD_TPU_FUSED_HASH: e = H(m) computed on device in "
         "the same program as the verify).")
_POLICY_EVALS_OPTS = MetricOpts(
    "fabric", "policy", "signature_evals_total",
    help="Endorsement-policy evaluations the validator finished "
         "(chaincode-wide and key-level), by result; added once per "
         "block.",
    label_names=("result",))
_BODY_FALLBACK_OPTS = MetricOpts(
    "fabric", "validator", "body_decode_fallbacks",
    help="Endorser-tx bodies the columnar batch decoder could not "
         "prove clean — staged through the generic per-tx decode "
         "instead (identical outcome, serial speed).")
_DECODE_PATH_OPTS = MetricOpts(
    "fabric", "validator", "decode_path_blocks_total",
    help="Blocks staged, by the decode path their row count chose "
         "(protos/batchdecode.COLUMNAR_MIN_ROWS): generic = the "
         "per-tx decode chain, columnar = the two batch pre-passes.",
    label_names=("path",))


@functools.lru_cache(maxsize=None)
def _stage_metrics():
    prov = default_provider()
    return (prov.histogram(_STAGED_ITEMS_OPTS,
                           buckets=(1, 8, 64, 256, 512, 1024, 2048)),
            prov.counter(_DEDUP_SAVED_OPTS),
            prov.counter(_RAW_ITEMS_OPTS),
            prov.counter(_BODY_FALLBACK_OPTS),
            prov.counter(_META_SHARED_OPTS))


@functools.lru_cache(maxsize=None)
def _decode_path_metrics():
    blocks = default_provider().counter(_DECODE_PATH_OPTS)
    return {path: blocks.with_labels(path)
            for path in ("generic", "columnar")}


@functools.lru_cache(maxsize=None)
def _policy_eval_metrics():
    evals = default_provider().counter(_POLICY_EVALS_OPTS)
    return (evals.with_labels("satisfied"),
            evals.with_labels("unsatisfied"))


class ValidationInfoProvider:
    """Resolves a chaincode namespace to its validation plugin and
    endorsement policy — the lifecycle's job in the reference
    (plugindispatcher dispatcher.go:102 + lifecycle ValidationInfo).
    A static map with a default stands in until the lifecycle SCC
    lands; the seam is the same.
    """

    def __init__(self, default_policy: bytes,
                 per_namespace: Optional[Dict[str, bytes]] = None):
        self._default = default_policy
        self._per_ns = dict(per_namespace or {})

    def validation_info(self, ns: str) -> Tuple[str, bytes]:
        return "vscc", self._per_ns.get(ns, self._default)

    def set_policy(self, ns: str, policy_bytes: bytes) -> None:
        self._per_ns[ns] = policy_bytes


VALIDATION_PARAMETER = "VALIDATION_PARAMETER"


class _KeyEval:
    """One written key's endorsement-policy resolution candidates.

    (reference: statebased/validator_keylevel.go:243-271 — a key with a
    VALIDATION_PARAMETER metadata override validates against it; which
    override is in force can depend on EARLIER txs in the same block,
    so every candidate's signature checks are staged in pass 1 and the
    choice is resolved sequentially in pass 3.)
    """

    __slots__ = ("ns", "key", "committed", "inblock")

    def __init__(self, ns: str, key: str, committed, inblock):
        self.ns = ns
        self.key = key
        self.committed = committed        # PendingEval | None
        self.inblock = inblock            # [(tx_idx, PendingEval)]


class _ActionEval:
    __slots__ = ("cc_pending", "key_evals")

    def __init__(self, cc_pending, key_evals):
        self.cc_pending = cc_pending      # chaincode-wide policy
        self.key_evals = key_evals        # [_KeyEval]


class _TxWork:
    """Per-tx staging between the host pass and the device verdict."""

    __slots__ = ("flag", "txid", "creator_slot", "actions", "is_config",
                 "env", "vp_writes", "written_ns")

    def __init__(self):
        self.flag = V.NOT_VALIDATED
        self.txid = ""
        self.creator_slot = None          # (batch_idx | None, host_ok)
        self.actions = []                 # [_ActionEval]
        self.is_config = False
        self.env = None                   # kept only for config txs
        self.vp_writes = []               # [(ns, key, policy_bytes)]
        self.written_ns = set()           # namespaces this tx writes


class StagedBlock:
    """A block after passes 1+2: host staging done, device batch
    dispatched, verdicts pending (resolved by TxValidator.finish).

    `trace_timeline` (FMT_TRACE armed only, else None) is the block's
    flight-recorder timeline riding the stage→commit handoff: the
    engine that staged this block attaches it, the committing side
    resumes it — context propagation by carrying the context.

    `gate` (None unless the block was staged with its block
    signatures, as the deliver client's blocks are) is the pending
    BlockValidation evaluation of the orderer's signature set: its
    items rode this block's own batch, and `finish` reads its verdict
    before anything else."""

    __slots__ = ("block", "validator", "works", "mask_fn", "_mask",
                 "trace_timeline", "rwsets", "gate")

    def __init__(self, block, validator, works, mask_fn, rwsets=None,
                 gate=None):
        self.block = block
        self.validator = validator
        self.works = works
        self.mask_fn = mask_fn
        self._mask = None
        self.trace_timeline = None
        # the stage-time columnar rwset planes (batchdecode.
        # BlockRWSets; None for a block under COLUMNAR_MIN_ROWS) —
        # commit_block's vectorized MVCC consumes them so the block's
        # tx bodies are decoded ONCE
        self.rwsets = rwsets
        self.gate = gate

    def resolve_mask(self):
        """Await the device verdicts (idempotent).  The commit
        pipeline calls this under its own await-latency histogram;
        `finish` then reads the cached mask for free."""
        if self._mask is None:
            # the single choke point both the pipelined and the
            # synchronous path pass through — the verdict_await
            # sub-stage is attributed HERE so neither path can hide it
            with tracing.span("verdict_await",
                              block=self.block.header.number):
                self._mask = np.asarray(self.mask_fn(), bool)
        return self._mask

    @property
    def needs_barrier(self) -> bool:
        """True when the NEXT block's staging must wait for this
        block's commit: config txs swap the bundle/MSPs, VALIDATION_
        PARAMETER writes change key-level policies, and lifecycle-
        namespace writes change validation info — all state that
        pass 1 reads (reference: the key-level validator's wait at
        validator_keylevel.go + the config serialization in
        validator.go:400)."""
        from fabric_mod_tpu.peer.lifecycle import LIFECYCLE_NS
        for w in self.works:
            if w.is_config or w.vp_writes or LIFECYCLE_NS in w.written_ns:
                return True
        return False


class TxValidator:
    """(reference: txvalidator/v20/validator.go TxValidator)"""

    def __init__(self, channel_id: str, msp_mgr,
                 policy_eval: ApplicationPolicyEvaluator,
                 verifier,
                 vinfo: ValidationInfoProvider,
                 tx_id_exists: Optional[Callable[[str], bool]] = None,
                 config_apply: Optional[Callable[[m.Envelope], None]] = None,
                 state_metadata: Optional[Callable[[str, str],
                                                   Optional[bytes]]] = None,
                 plugin_registry=None):
        self.channel_id = channel_id
        self._msp_mgr = msp_mgr
        self._policy_eval = policy_eval
        self._verifier = verifier
        self._vinfo = vinfo
        # named validation plugins (reference: handlers/library
        # registry.go:79); definitions naming an unknown plugin fail
        # closed in _stage_tx
        if plugin_registry is None:
            from fabric_mod_tpu.peer.plugins import PluginRegistry
            plugin_registry = PluginRegistry()
        self._plugins = plugin_registry
        self._tx_id_exists = tx_id_exists or (lambda _txid: False)
        # CONFIG txs: validated + applied through the channel config
        # machinery (reference: txvalidator/v20/validator.go:400-421 —
        # config txs are governance, not a signature check).  Fail
        # closed when no applier is wired.
        self._config_apply = config_apply
        # Committed VALIDATION_PARAMETER reader for key-level policies
        # (reference: the key-level validator's policy fetcher over the
        # state DB) — returns ApplicationPolicy bytes or None.
        self._state_metadata = state_metadata

    # -- pass 1: host unpack + staging -----------------------------------
    def _stage_tx(self, env: m.Envelope, work: _TxWork,
                  collector: BatchCollector, inblock_vp,
                  spine=None, body=None) -> None:
        """Syntactic validation + creator/endorsement staging for one
        tx.  Sets work.flag on terminal failure, else leaves VALID
        pending the device verdicts.  `spine` (protos/batchdecode) is
        the batch pre-pass's already-decoded envelope/payload/header
        layers — value-identical to the generic decode below, which
        is the path of EVERY row of a block under batchdecode.
        COLUMNAR_MIN_ROWS (stage runs no pre-pass there) and the
        per-tx fallback for rows the scanner rejected in a larger
        one.  `body` (batchdecode.TxBody) is the columnar batch
        decoder's staged endorser-tx body for this row: the exact ns /
        prp / endorsement / written-key values the generic decode
        chain below would produce, already validated transitively —
        rows it could not prove take the generic chain (counted).
        (reference: msgvalidation.go:248 ValidateTransaction)"""
        if not env.payload:
            work.flag = V.NIL_ENVELOPE
            return
        if spine is not None:
            payload, ch, sh = spine.payload, spine.ch, spine.sh
        else:
            try:
                payload = protoutil.unmarshal_envelope_payload(env)
                ch = m.ChannelHeader.decode(payload.header.channel_header)
                sh = m.SignatureHeader.decode(
                    payload.header.signature_header)
            except Exception:
                work.flag = V.BAD_PAYLOAD
                return
        if not ch.channel_id or ch.channel_id != self.channel_id:
            work.flag = V.BAD_CHANNEL_HEADER
            return
        work.txid = ch.tx_id

        # creator signature (reference: msgvalidation.go:26
        # checkSignatureFromCreator — Validate() then Verify())
        if not sh.creator or not env.signature:
            work.flag = V.BAD_CREATOR_SIGNATURE
            return
        try:
            creator = self._msp_mgr.deserialize_identity(sh.creator)
            self._msp_mgr.validate(creator)
        except Exception:
            work.flag = V.BAD_CREATOR_SIGNATURE
            return
        item = creator.verify_item(env.payload, env.signature)
        if item is not None:
            work.creator_slot = (collector.add(item), False)
        else:
            work.creator_slot = (
                None, creator.verify(env.payload, env.signature))

        if ch.type == m.HeaderType.CONFIG:
            work.is_config = True
            work.env = env                # finish_tx re-validates+applies
            return                        # config txs skip endorsement
        if ch.type != m.HeaderType.ENDORSER_TRANSACTION:
            work.flag = V.UNKNOWN_TX_TYPE
            return

        # tx id binding (reference: utils.CheckTxID in msgvalidation)
        expected = protoutil.compute_tx_id(sh.nonce, sh.creator)
        if ch.tx_id != expected:
            work.flag = V.BAD_PROPOSAL_TXID
            return
        # NOTE: the committed-store duplicate-txid check runs in pass 3
        # (_finish_tx callers), not here — staging may run ahead of the
        # previous block's commit in the pipelined path, and only at
        # finish time is the committed store guaranteed current.

        if body is not None:
            # columnar fast path: the batch decoder already produced
            # this tx's staged body view (single action — the scanner
            # rejects multi-action txs into the fallback), so staging
            # reads fields instead of re-decoding six proto layers
            self._stage_body(body, work, collector, inblock_vp)
            return

        # endorsement policy per action (reference: VSCC v20
        # validation_logic.go:185 + validator_keylevel.go:245-258:
        # data = proposal-response-payload ‖ endorser-identity)
        try:
            tx = protoutil.extract_endorser_tx(payload)
            if not tx.actions:
                work.flag = V.NIL_TXACTION
                return
            for action in tx.actions:
                cca, prp_bytes, endorsements = \
                    protoutil.tx_rwset_and_endorsements(action)
                if not endorsements:
                    work.flag = V.ENDORSEMENT_POLICY_FAILURE
                    return
                ns = (cca.chaincode_id.name
                      if cca.chaincode_id is not None else "")
                # ONE rwset decode per action, shared by validation-
                # info resolution and key-level policy staging (these
                # used to each decode cca.results themselves)
                try:
                    rwset = m.TxReadWriteSet.decode(cca.results)
                except Exception:
                    rwset = None
                plugin_name, policy_bytes = self._resolve_vinfo(ns, rwset)
                evaluator = self._plugins.resolve(plugin_name,
                                                  self._policy_eval)
                if evaluator is None:
                    # definition names a plugin this peer does not
                    # have: fail closed (reference: plugindispatcher's
                    # missing-plugin error -> invalid tx)
                    work.flag = V.INVALID_OTHER_REASON
                    return
                sds = [SignedData(data=prp_bytes + e.endorser,
                                  identity=e.endorser,
                                  signature=e.signature)
                       for e in endorsements]
                cc_pending = evaluator.prepare(policy_bytes, sds, collector)
                key_evals = self._stage_key_policies(
                    rwset, sds, collector, inblock_vp, work)
                work.actions.append(_ActionEval(cc_pending, key_evals))
        except Exception:
            work.flag = V.INVALID_ENDORSER_TRANSACTION
            return

    def _stage_body(self, body, work, collector, inblock_vp) -> None:
        """Stage one scanner-accepted endorser-tx body — the columnar
        twin of _stage_tx's generic action loop, consuming the values
        batchdecode already proved instead of re-decoding them.  Every
        flag it can set is one the generic chain sets on the same
        bytes (the decoder's soundness gate)."""
        try:
            if body.no_action:
                work.flag = V.NIL_TXACTION
                return
            if not body.endorsements:
                work.flag = V.ENDORSEMENT_POLICY_FAILURE
                return
            ns = body.ns
            plugin_name, policy_bytes = self._resolve_vinfo(
                ns, None, keys=body.lifecycle_write_keys(ns))
            evaluator = self._plugins.resolve(plugin_name,
                                              self._policy_eval)
            if evaluator is None:
                work.flag = V.INVALID_OTHER_REASON
                return
            sds = [SignedData(data=body.prp + endorser,
                              identity=endorser,
                              signature=signature)
                   for endorser, signature in body.endorsements]
            cc_pending = evaluator.prepare(policy_bytes, sds, collector)
            key_evals = self._stage_key_policies_columnar(
                body, sds, collector, inblock_vp, work)
            work.actions.append(_ActionEval(cc_pending, key_evals))
        except Exception:
            work.flag = V.INVALID_ENDORSER_TRANSACTION
            return

    def _resolve_vinfo(self, ns: str, rwset, keys=None):
        """Validation info for one action; `_lifecycle` writes are
        resolved write-aware when the provider supports it (org-local
        approval txs validate against that org's Endorsement policy —
        see peer/lifecycle.py).  `rwset` is the action's decoded
        TxReadWriteSet (None when cca.results was malformed — fall
        back to tx-level resolution; decode errors are surfaced by
        validation itself).  `keys` short-circuits the inner decode
        when the columnar body already carries this ns's write keys."""
        from fabric_mod_tpu.peer.lifecycle import LIFECYCLE_NS
        write_aware = getattr(self._vinfo, "validation_info_for_writes",
                              None)
        if write_aware is not None and ns == LIFECYCLE_NS and \
                (rwset is not None or keys is not None):
            try:
                if keys is None:
                    keys = [w.key
                            for nsrw in rwset.ns_rwset
                            if nsrw.namespace == ns
                            for w in m.KVRWSet.decode(nsrw.rwset).writes]
                return write_aware(ns, keys)
            except Exception:  # fmtlint: allow[swallowed-exceptions] -- malformed inner rwset: fall back to tx-level VP resolution; decode errors are surfaced by validation itself
                pass
        return self._vinfo.validation_info(ns)

    def _stage_key_policies(self, rwset, sds, collector, inblock_vp, work):
        """Stage every candidate key-level endorsement policy of this
        action's written keys (reference: validator_keylevel.go — the
        committed VALIDATION_PARAMETER plus any same-block overrides
        whose applicability pass 3 resolves in order).  `rwset` is the
        action's already-decoded TxReadWriteSet (None = malformed ->
        no key evals, the historical behavior)."""
        key_evals = []
        if rwset is None:
            return key_evals
        from fabric_mod_tpu.ledger.rwsetutil import parse_tx_rwset
        for ns, kv in parse_tx_rwset(rwset):
            if kv.writes or kv.metadata_writes:
                work.written_ns.add(ns)
            written = dict.fromkeys(
                [w.key for w in kv.writes]
                + [mw.key for mw in kv.metadata_writes])
            for key in written:
                committed_pending = None
                if self._state_metadata is not None:
                    vp = self._state_metadata(ns, key)
                    if vp:
                        committed_pending = self._policy_eval.prepare(
                            vp, sds, collector)
                cands = inblock_vp.get((ns, key), ())
                inblock = [
                    (idx, self._policy_eval.prepare(vp, sds, collector))
                    for idx, vp in cands]
                # EVERY written key gets an eval entry: keys without an
                # effective VP resolve to None in pass 3 and force the
                # cc-wide policy — otherwise a tx satisfying one key's
                # narrow VP could smuggle writes to other keys past the
                # chaincode policy (fail-closed, like the reference's
                # per-key fallback to the default policy)
                key_evals.append(
                    _KeyEval(ns, key, committed_pending, inblock))
            # register this tx's own VALIDATION_PARAMETER writes for
            # later txs in the block (applied only if this tx is VALID)
            for mw in kv.metadata_writes:
                for e in mw.entries:
                    if e.name == VALIDATION_PARAMETER:
                        work.vp_writes.append((ns, mw.key, e.value))
        return key_evals

    def _stage_key_policies_columnar(self, body, sds, collector,
                                     inblock_vp, work):
        """_stage_key_policies over a columnar TxBody: `body.groups`
        is the per-ns-occurrence written view the generic path derives
        from parse_tx_rwset — same occurrence order, same per-
        occurrence key dedup, same eval/vp-write sequence."""
        key_evals = []
        for ns, wkeys, metas in body.groups:
            if wkeys or metas:
                work.written_ns.add(ns)
            written = dict.fromkeys(
                list(wkeys) + [mkey for mkey, _entries in metas])
            for key in written:
                committed_pending = None
                if self._state_metadata is not None:
                    vp = self._state_metadata(ns, key)
                    if vp:
                        committed_pending = self._policy_eval.prepare(
                            vp, sds, collector)
                cands = inblock_vp.get((ns, key), ())
                inblock = [
                    (idx, self._policy_eval.prepare(vp, sds, collector))
                    for idx, vp in cands]
                key_evals.append(
                    _KeyEval(ns, key, committed_pending, inblock))
            for mkey, entries in metas:
                for name, value in entries:
                    if name == VALIDATION_PARAMETER:
                        work.vp_writes.append((ns, mkey, value))
        return key_evals

    def _decode_columnar(self, block: m.Block):
        """The two batch pre-passes of a block at or above
        batchdecode.COLUMNAR_MIN_ROWS: (spines, rwsets) for `stage`'s
        per-tx loop.  Rows either decoder could not prove clean come
        back None and take the generic per-tx decode there (identical
        outcomes)."""
        datas = block.data.data
        # batch pre-pass: the whole block's envelope/payload/header
        # spine in one vectorized scan
        spines = batchdecode.decode_block_spine(datas)
        # batch body pre-pass: every spine-accepted endorser tx's
        # payload.data goes through ONE columnar rwset decode
        # (protos/batchdecode.decode_block_rwsets); accepted bodies
        # are shared by VP resolution, key-level policy staging, and
        # — vectorized — MVCC at commit
        with tracing.span("body_decode", block=block.header.number,
                          txs=len(datas)):
            body_datas: List[Optional[bytes]] = [None] * len(datas)
            for idx, spine in enumerate(spines):
                if spine is not None and spine.ch.type == \
                        m.HeaderType.ENDORSER_TRANSACTION:
                    body_datas[idx] = spine.payload.data
            rwsets = batchdecode.decode_block_rwsets(body_datas)
        if rwsets is not None:
            # header facts ride along: value-identical to the generic
            # envelope_channel_header decode commit would otherwise
            # repeat per tx
            for idx, spine in enumerate(spines):
                if spine is not None:
                    rwsets.txids[idx] = spine.ch.tx_id
                    rwsets.types[idx] = spine.ch.type
            _stage_metrics()[3].add(rwsets.fallbacks)
        return spines, rwsets

    # -- the three passes -------------------------------------------------
    def stage(self, block: m.Block,
              block_gate: Optional[tuple] = None) -> "StagedBlock":
        """Passes 1+2: host unpack/staging, then DISPATCH the device
        batch without awaiting it.  The returned StagedBlock carries
        the pending verdicts; `finish` resolves them.  Staging block
        N+1 while block N commits is the commit pipeline's double
        buffer — legal exactly when block N sets no state the staging
        reads (see StagedBlock.needs_barrier).

        The decode path follows the block's row count: at or above
        batchdecode.COLUMNAR_MIN_ROWS the two columnar pre-passes run
        first (`_decode_columnar`) and `_stage_tx` reads their rows;
        under it none runs, every row takes `_stage_tx`'s generic
        chain and `StagedBlock.rwsets` is None (commit decodes the
        rwsets itself).  The batch's lanes, every `_TxWork` and the
        flags are the same either way; the `unpack` span's `decoder`
        attribute and fabric_validator_decode_path_blocks_total{path}
        say which ran.

        `block_gate` is (policy, signed_datas): the orderer's block
        signatures (peer/mcs.check_block) and the BlockValidation
        policy in force now.  Their items join this block's batch
        AFTER the transactions' (a batch past the widest bucket chunks
        as before; the extra lane lands in the last chunk), and
        `finish` refuses the block unless the policy is satisfied."""
        works: List[_TxWork] = []
        collector = BatchCollector()
        # (ns, key) -> [(tx_idx, ApplicationPolicy bytes)]: the
        # VALIDATION_PARAMETER writes of EARLIER txs in this block —
        # the intra-block dependency structure of validator_keylevel.go
        inblock_vp: Dict[tuple, list] = {}
        datas = block.data.data
        decoder = ("columnar"
                   if len(datas) >= batchdecode.COLUMNAR_MIN_ROWS
                   else "generic")
        with tracing.span("unpack", block=block.header.number,
                          txs=len(datas), decoder=decoder):
            if decoder == "columnar":
                spines, rwsets = self._decode_columnar(block)
            else:
                # a small block: the pre-passes' fixed cost (19
                # scan_message passes, each a chain of numpy calls)
                # is more than its rows' own generic decode
                spines, rwsets = [None] * len(datas), None
            for idx, data in enumerate(datas):
                work = _TxWork()
                works.append(work)
                spine = spines[idx]
                if spine is not None:
                    env = spine.env
                else:
                    try:
                        env = m.Envelope.decode(data)
                    except Exception:
                        work.flag = V.BAD_PAYLOAD
                        continue
                body = rwsets.bodies[idx] if rwsets is not None else None
                self._stage_tx(env, work, collector, inblock_vp, spine, body)
                for ns, key, vp in work.vp_writes:
                    inblock_vp.setdefault((ns, key), []).append((idx, vp))
        gate = None
        n_tx_items = len(collector.items)
        if block_gate is not None:
            policy, signed_datas = block_gate
            gate = policy.prepare(signed_datas, collector)

        # pass 2: dispatch the device batch (async when the verifier
        # supports it; the resolver blocks only when called).  Repeats
        # across blocks — gossip redelivery, the endorsement/commit
        # dual validation — are the verifier-level memo-cache's job
        # (bccsp/tpu.VerdictCache); within-block repeats never reach
        # it thanks to the collector's dedup, and both effects are
        # exported so coalescing stays observable.
        staged_hist, dedup_ctr, raw_ctr, _fb_ctr, shared_ctr = \
            _stage_metrics()
        staged_hist.observe(len(collector.items))
        dedup_ctr.add(collector.requests - len(collector.items))
        shared_ctr.add(collector.shared_resolutions)
        _decode_path_metrics()[decoder].add(1)
        # Raw-message items (identities emit them under FABRIC_MOD_
        # TPU_FUSED_HASH) flow through the same collector/dedup into
        # p256.batch_verify_raw — counted so the fused rollout is
        # observable per block.
        raw_ctr.add(sum(1 for it in collector.items
                        if getattr(it, "message", None) is not None))
        with tracing.span("device_dispatch",
                          block=block.header.number,
                          items=len(collector.items)) as dispatch_span:
            async_fn = getattr(self._verifier, "verify_many_async", None)
            if async_fn is not None:
                mask_fn = async_fn(collector.items)
            else:
                items = collector.items
                mask_fn = lambda: self._verifier.verify_many(items)
            # device calls the batch became, where the verifier says
            chunks = getattr(mask_fn, "chunks", None)
            if chunks is not None:
                dispatch_span.set(chunks=chunks)
            # and its device lanes by the program that verifies them
            table_lanes = getattr(mask_fn, "table_lanes", None)
            if table_lanes is not None:
                dispatch_span.set(table_lanes=table_lanes,
                                  ladder_lanes=mask_fn.ladder_lanes)
            if gate is not None:
                # block-signature items that rode this batch
                dispatch_span.set(
                    block_sigs=len(collector.items) - n_tx_items)
        return StagedBlock(block, self, works, mask_fn, rwsets, gate)

    def finish(self, staged: "StagedBlock") -> List[int]:
        """Pass 3: await the device verdicts, then sequential flag
        resolution — duplicate marking and key-level override
        application happen in block order so later txs see exactly the
        effects of earlier VALID ones."""
        block, works = staged.block, staged.works
        mask = staged.resolve_mask()
        if staged.gate is not None and not staged.gate.finish(mask):
            # the MCS gate, deferred: nothing of this block has been
            # flagged, applied or committed, and the caller's pipeline
            # commits nothing staged after it
            raise signatures_unsatisfied(block.header.number)
        flags: List[int] = []
        seen_txids = set()
        applied_vp: Dict[tuple, int] = {}   # (ns, key) -> writer tx_idx
        # evaluations satisfied and unsatisfied: tallied per block,
        # never per (identity, principal) pair
        tally = [0, 0]
        with tracing.span("policy_finish",
                          block=block.header.number) as finish_span:
            for idx, work in enumerate(works):
                flag = self._finish_tx(work, mask, applied_vp, tally)
                if flag == V.VALID and work.txid:
                    if work.txid in seen_txids or \
                            self._tx_id_exists(work.txid):
                        flag = V.DUPLICATE_TXID
                    else:
                        seen_txids.add(work.txid)
                if flag == V.VALID:
                    for ns, key, _vp in work.vp_writes:
                        applied_vp[(ns, key)] = idx
                flags.append(flag)
            protoutil.set_block_txflags(block, bytes(flags))
            finish_span.set(evals=tally[0] + tally[1])
        satisfied, unsatisfied = _policy_eval_metrics()
        satisfied.add(tally[0])
        unsatisfied.add(tally[1])
        return flags

    def validate(self, block: m.Block) -> List[int]:
        """Validate every tx of `block`; ONE device dispatch total.
        Writes the txflags bitmap into the block metadata and returns
        the flags (reference: validator.go:182-267)."""
        return self.finish(self.stage(block))

    @staticmethod
    def _finish_eval(pending, mask, tally) -> bool:
        ok = pending.finish(mask)
        tally[0 if ok else 1] += 1
        return ok

    def _finish_tx(self, work: _TxWork, mask, applied_vp, tally) -> int:
        if work.flag != V.NOT_VALIDATED:
            return work.flag
        bidx, host_ok = work.creator_slot
        creator_ok = bool(mask[bidx]) if bidx is not None else host_ok
        if not creator_ok:
            return V.BAD_CREATOR_SIGNATURE
        if work.is_config:
            # (reference: validator.go:400-421 — the config envelope is
            # re-validated against the current bundle's mod policies and
            # applied; anything short of that is INVALID, fail-closed)
            if self._config_apply is None:
                return V.INVALID_CONFIG_TRANSACTION
            try:
                self._config_apply(work.env)
            except Exception:
                return V.INVALID_CONFIG_TRANSACTION
            return V.VALID
        for action in work.actions:
            uncovered = not action.key_evals
            for ke in action.key_evals:
                writer = applied_vp.get((ke.ns, ke.key))
                pending = None
                if writer is not None:
                    for tx_idx, cand in ke.inblock:
                        if tx_idx == writer:
                            pending = cand
                            break
                if pending is None:
                    pending = ke.committed
                if pending is None:
                    uncovered = True        # falls to the cc-wide policy
                    continue
                if not self._finish_eval(pending, mask, tally):
                    return V.ENDORSEMENT_POLICY_FAILURE
            if uncovered and not self._finish_eval(
                    action.cc_pending, mask, tally):
                return V.ENDORSEMENT_POLICY_FAILURE
        return V.VALID


class Committer:
    """Validate + MVCC + commit, the peer's StoreBlock composition
    (reference: gossip/state/state.go:817 commitBlock ->
    coordinator StoreBlock -> validator -> kvledger CommitLegacy).

    Strictly serial: block N+1's staging starts only after block N's
    commit returns.  peer/commitpipe.PipelinedCommitter is the
    overlapped version of this composition (and collapses to exactly
    this behavior at depth=1)."""

    def __init__(self, validator: TxValidator, ledger):
        self.validator = validator
        self.ledger = ledger

    def store_block(self, block: m.Block) -> List[int]:
        # the synchronous path records the SAME per-block timeline the
        # pipelined engine does, so /flight and the bench attribution
        # see both arms through one lens
        tl = tracing.start_timeline("sync", block.header.number)
        try:
            with tracing.timeline_scope(tl):
                staged = self.validator.stage(block)
                flags = self.validator.finish(staged)
                return self.ledger.commit_block(block, flags,
                                                rwsets=staged.rwsets)
        finally:
            tracing.finish_timeline(tl)
