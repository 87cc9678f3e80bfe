"""Per-channel peer wiring: bundle + validator + committer + deliver.

(reference: core/peer/peer.go:248 `createChannel` — the function that
assembles validator, committer, gossip state and config callbacks for
one channel — plus the channelconfig bundle-swap pattern of
common/channelconfig/bundlesource.go:103.)

The Channel owns the mutable piece (the current Bundle) and rebuilds
the per-bundle objects (policy evaluator, validator) atomically when a
CONFIG tx commits.  Everything downstream reads through `bundle()` /
`validator()` accessors so a block always validates under exactly one
config snapshot.
"""
from __future__ import annotations

import threading
from typing import Callable, List, Optional

from fabric_mod_tpu.channelconfig import (
    Bundle, ConfigTxError, extract_config_update, propose_config_update)
from fabric_mod_tpu.channelconfig.configtx import config_from_block
from fabric_mod_tpu.peer.mcs import (MessageCryptoService,
                                     block_validation_policy)
from fabric_mod_tpu.peer.txvalidator import (
    Committer, TxValidator, ValidationInfoProvider)
from fabric_mod_tpu.policy import ApplicationPolicyEvaluator
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from fabric_mod_tpu.concurrency.locks import RegisteredLock

# Default endorsement policy reference when the namespace has none
# (reference: lifecycle's default /Channel/Application/Endorsement)
DEFAULT_ENDORSEMENT_REF = "/Channel/Application/Endorsement"


class Channel:
    """One channel on one peer (reference: core/peer/peer.go Channel)."""

    def __init__(self, channel_id: str, ledger, verifier, bundle: Bundle,
                 csp, vinfo: Optional[ValidationInfoProvider] = None,
                 plugin_registry=None):
        self.channel_id = channel_id
        self.ledger = ledger
        self.verifier = verifier
        self._verifier = verifier
        self._csp = csp
        self._plugin_registry = plugin_registry
        self._lock = RegisteredLock("peer.channel._lock")
        self._shard_router = None          # set via use_shard_router()
        if vinfo is None:
            # lifecycle-backed: committed chaincode definitions resolve
            # each namespace's endorsement policy (peer/lifecycle.py)
            from fabric_mod_tpu.peer.lifecycle import LifecycleValidationInfo

            def state_get(ns: str, key: str):
                got = self.ledger.state.get_state(ns, key)
                return got[0] if got else None
            vinfo = LifecycleValidationInfo(
                state_get,
                m.ApplicationPolicy(
                    channel_config_policy_reference=DEFAULT_ENDORSEMENT_REF
                ).encode())
        self._vinfo = vinfo
        self.mcs = MessageCryptoService(self.bundle, verifier)
        # private data plumbing (reference: transientstore + the
        # privdata coordinator wiring of peer.go createChannel); on a
        # durable ledger both stores are durable too — committed
        # private plaintext and the pending-reconciliation index
        # survive restarts (reference: pvtdatastorage/store.go,
        # transientstore/store.go are leveldb instances)
        import os as _os
        from fabric_mod_tpu.ledger.pvtdata import (
            PvtDataStore, TransientStore)
        pvt_root = (ledger.dir if getattr(ledger, "_durable", False)
                    else None)
        self.transient_store = TransientStore(
            dir_path=(_os.path.join(pvt_root, "transient")
                      if pvt_root else None))
        self.pvtdata_store = PvtDataStore(
            dir_path=(_os.path.join(pvt_root, "pvtdata")
                      if pvt_root else None))
        self.ledger.attach_pvt(self.transient_store, self.pvtdata_store,
                               self._collection_btl)
        self._install_bundle(bundle)

    def _static_collection_config(self, ns: str, collection: str):
        """The committed StaticCollectionConfig for (chaincode,
        collection), or None (reference: privdata's collection-config
        retrieval from the lifecycle definition)."""
        from fabric_mod_tpu.peer.lifecycle import (
            LIFECYCLE_NS, definition_key)
        got = self.ledger.state.get_state(LIFECYCLE_NS,
                                          definition_key(ns))
        if got is None:
            return None
        try:
            d = m.ChaincodeDefinition.decode(got[0])
            pkg = m.CollectionConfigPackage.decode(d.collections)
        except Exception:
            return None
        for cc in pkg.config:
            sc = cc.static_collection_config
            if sc is not None and sc.name == collection:
                return sc
        return None

    def collection_policy(self, ns: str, collection: str):
        """member_orgs_policy (SignaturePolicyEnvelope) of a committed
        collection config, or None."""
        sc = self._static_collection_config(ns, collection)
        return sc.member_orgs_policy if sc is not None else None

    def _collection_btl(self, ns: str, collection: str) -> int:
        """BTL from the committed chaincode definition's collection
        configs (reference: the BTL policy of pvtstatepurgemgmt)."""
        sc = self._static_collection_config(ns, collection)
        return sc.block_to_live if sc is not None else 0

    # -- bundle lifecycle -------------------------------------------------
    def _install_bundle(self, bundle: Bundle) -> None:
        # `bundle.msp_manager` is the bundle's own identity cache
        # (msp/cache.py), shared with every policy compiled from it.
        # A config update swaps the bundle, builds a fresh manager,
        # and therefore starts cold: revoked or re-rooted identities
        # can never be served from a previous epoch's cache.
        policy_eval = ApplicationPolicyEvaluator(
            bundle.msp_manager, bundle.policy_manager,
            sequence=bundle.sequence)
        def state_vp(ns: str, key: str):
            meta = self.ledger.state.get_metadata(ns, key)
            if meta:
                from fabric_mod_tpu.peer.txvalidator import (
                    VALIDATION_PARAMETER)
                return meta.get(VALIDATION_PARAMETER)
            return None

        validator = TxValidator(
            self.channel_id, bundle.msp_manager, policy_eval,
            self._verifier, self._vinfo,
            tx_id_exists=self.ledger.tx_id_exists,
            config_apply=self._validate_and_apply_config,
            state_metadata=state_vp,
            plugin_registry=self._plugin_registry,
            state_has_metadata=lambda: self.ledger.state.has_metadata())
        with self._lock:
            self._bundle = bundle
            self._validator = validator

    def bundle(self) -> Bundle:
        with self._lock:
            return self._bundle

    def validator(self) -> TxValidator:
        with self._lock:
            return self._validator

    # -- config tx path ---------------------------------------------------
    def _validate_and_apply_config(self, env: m.Envelope) -> None:
        """Re-validate an ordered CONFIG envelope against the current
        bundle and adopt it (reference: validator.go:400-421 +
        configtx validator Validate).  Called from inside block
        validation; raising marks the tx INVALID_CONFIG_TRANSACTION."""
        payload = protoutil.unmarshal_envelope_payload(env)
        cenv = m.ConfigEnvelope.decode(payload.data)
        if cenv.config is None:
            raise ConfigTxError("config envelope carries no config")
        bundle = self.bundle()
        if cenv.last_update is None:
            raise ConfigTxError("config envelope carries no last_update")
        cue = extract_config_update(cenv.last_update)
        verify_many = (self._verifier.verify_many
                       if self._verifier is not None else None)
        computed = propose_config_update(bundle, cue, verify_many)
        if computed.encode() != cenv.config.encode():
            raise ConfigTxError(
                "ordered config does not match the one computed from "
                "last_update under the current bundle")
        self._install_bundle(Bundle(self.channel_id, computed, self._csp))

    def init_from_genesis(self, genesis_block: m.Block) -> List[int]:
        """Commit block 0 (already validated out-of-band: genesis is
        the trust anchor, reference: peer channel join)."""
        flags = [m.TxValidationCode.VALID] * len(genesis_block.data.data)
        protoutil.set_block_txflags(genesis_block, bytes(flags))
        return self.ledger.commit_block(genesis_block, flags)

    # -- commit path ------------------------------------------------------
    def store_block(self, block: m.Block) -> List[int]:
        """validate -> MVCC -> commit (the reference's coordinator
        StoreBlock composition, gossip/state/state.go:817).

        On a router-bound channel the commit routes through the
        router's slice-pinned PipelinedCommitter: this call is still
        synchronous (waits for THIS block's commit, returns its final
        flags), but overlapping callers pipeline — stage(N+1) proceeds
        while commit(N) runs."""
        pipe = self.commit_pipeline()
        if pipe is not None:
            try:
                return pipe.store_block(block)
            except Exception:
                # the failure may be INHERITED — a pipe another
                # caller's block poisoned (sticky error) or closed
                # under us mid-rebuild.  One retry through a fresh
                # pipe separates that from this block's own error:
                # an own-error block fails again with the real cause,
                # and a gate rejection returns the SAME healthy pipe
                # so we re-raise without a pointless resubmit.
                retry = self.commit_pipeline()
                if retry is pipe:
                    raise
                return retry.store_block(block)
        flags = self.validator().validate(block)
        return self.ledger.commit_block(block, flags)

    def use_shard_router(self, router) -> None:
        """Bind this channel to a ChannelShardRouter (sharding/):
        commit_pipeline() then delegates to the router's slice-pinned
        engine — the router carries the rebuild-on-poison contract,
        plus placement.  The router must already hold this
        channel (add_channel); binding is one-way for the channel's
        lifetime (unbinding mid-stream would race two engines onto
        one ledger)."""
        router.bind_target(self.channel_id, self)
        with self._lock:
            self._shard_router = router

    def commit_pipeline(self):
        """The router's slice-pinned PipelinedCommitter when a shard
        router is bound, else None (the synchronous path).  Every
        commit producer on a bound channel (gossip drain, store_block
        callers) thereby feeds ONE in-order pipeline, and the router
        owns its rebuild-on-poison contract: the caller that hit a
        failed pipe gets the exception, the next call here gets a
        fresh engine built from the committed height."""
        with self._lock:
            router = self._shard_router
        if router is None:
            return None
        return router.pipeline_for(self.channel_id)

    # pipelined split: stage (host unpack + async device dispatch) may
    # run ahead of the previous block's commit; commit_staged resolves
    # the verdicts and commits.  `staged.needs_barrier` tells the
    # pipeline when staging must NOT run ahead (config / vp-write /
    # lifecycle blocks).
    def stage_block(self, block: m.Block, block_sigs=None):
        """`block_sigs`: the block signatures' SignedData from
        `mcs.check_block`, where the caller left the BlockValidation
        policy to this block's own verify batch (the deliver client).
        The policy is the one of the bundle in force NOW: the stage
        loop waits out every earlier config block (`needs_barrier`),
        so a block is never held against a policy an uncommitted
        config block is about to replace."""
        with self._lock:
            bundle, validator = self._bundle, self._validator
        if block_sigs is None:
            return validator.stage(block)
        return validator.stage(
            block, (block_validation_policy(bundle, block.header.number),
                    block_sigs))

    def commit_staged(self, staged) -> List[int]:
        # finish on the validator that staged: its pending evaluators
        # hold that validator's batch slots
        flags = staged.validator.finish(staged)
        return self.ledger.commit_block(
            staged.block, flags,
            rwsets=getattr(staged, "rwsets", None))

    def committer(self) -> Committer:
        return _ChannelCommitter(self)


class _ChannelCommitter:
    """Committer facade bound to the channel's CURRENT validator."""

    def __init__(self, channel: Channel):
        self._channel = channel

    def store_block(self, block: m.Block) -> List[int]:
        return self._channel.store_block(block)
