"""In-process end-to-end network: the minimum slice, whole loop.

(reference: the integration/nwo "network world order" declarative
topology builder, network.go:44-60, shrunk to one process: client ->
endorsers -> broadcast -> solo consenter -> deliver -> MCS verify ->
validator (device batch) -> MVCC -> commit.)

This is the BASELINE config #3 shape without gRPC between the parts;
the seams (Broadcast.submit, DeliverService.blocks, verify_many) are
exactly where the wire goes when the comm layer lands.
"""
from __future__ import annotations

import os
import tempfile
import time
from typing import Dict, List, Optional, Sequence

from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.channelconfig import Bundle, genesis
from fabric_mod_tpu.channelconfig.configtx import config_from_block
from fabric_mod_tpu.ledger.kvledger import LedgerManager
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.orderer import Broadcast, DeliverService, Registrar
from fabric_mod_tpu.peer.chaincode import ChaincodeRegistry, KvContract
from fabric_mod_tpu.peer.channel import Channel
from fabric_mod_tpu.peer.deliverclient import DeliverClient
from fabric_mod_tpu.peer.endorser import Endorser, endorse_and_submit
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.concurrency.threads import RegisteredThread


class Network:
    """One channel, N orgs, one solo orderer, one committing peer,
    one endorser per org — all in-process.

    `endorsement_policy` is the channel's default endorsement policy
    as a policydsl string (`genesis.application_group`; None: the
    implicit-meta MAJORITY of the orgs); `absolute_max_bytes` and
    `preferred_max_bytes` are the orderer's BatchSize byte limits
    beside `max_message_count`."""

    def __init__(self, root_dir: str, channel_id: str = "testchannel",
                 orgs: Sequence[str] = ("Org1", "Org2", "Org3"),
                 verifier=None, csp=None,
                 max_message_count: int = 500,
                 batch_timeout: str = "250ms",
                 ingress_batching: bool = False,
                 endorsement_policy: Optional[str] = None,
                 absolute_max_bytes: int = 10 * 1024 * 1024,
                 preferred_max_bytes: int = 2 * 1024 * 1024):
        self.channel_id = channel_id
        self.csp = csp or SwCSP()
        if verifier is None:
            from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
            verifier = FakeBatchVerifier(self.csp)
        self.verifier = verifier

        # crypto material (the cryptogen step)
        self.cas: Dict[str, calib.CA] = {
            org: calib.CA(f"ca.{org.lower()}", org) for org in orgs}
        self.orderer_ca = calib.CA("ca.orderer", "OrdererOrg")
        ocert, okey = self.orderer_ca.issue(
            "orderer0", "OrdererOrg", ous=["orderer"])
        self.orderer_signer = SigningIdentity(
            "OrdererOrg", ocert, calib.key_pem(okey), self.csp)

        self.peer_signers: Dict[str, SigningIdentity] = {}
        for org, ca in self.cas.items():
            cert, key = ca.issue(f"peer0.{org.lower()}", org, ous=["peer"])
            self.peer_signers[org] = SigningIdentity(
                org, cert, calib.key_pem(key), self.csp)
        first = orgs[0]
        ccert, ckey = self.cas[first].issue(
            f"client@{first.lower()}", first, ous=["client"])
        self.client = SigningIdentity(
            first, ccert, calib.key_pem(ckey), self.csp)
        self.admins: Dict[str, SigningIdentity] = {}
        for org, ca in self.cas.items():
            acert, akey = ca.issue(f"admin@{org.lower()}", org,
                                   ous=["admin"])
            self.admins[org] = SigningIdentity(
                org, acert, calib.key_pem(akey), self.csp)

        # genesis (the configtxgen step)
        self.genesis_block = genesis.standard_network(
            channel_id,
            {org: [calib.cert_pem(ca.cert)] for org, ca in self.cas.items()},
            {"OrdererOrg": [calib.cert_pem(self.orderer_ca.cert)]},
            endorsement_policy=endorsement_policy,
            max_message_count=max_message_count,
            absolute_max_bytes=absolute_max_bytes,
            preferred_max_bytes=preferred_max_bytes,
            batch_timeout=batch_timeout)

        # ordering service; with ingress batching, concurrent
        # broadcast submissions coalesce their policy verifies into
        # shared deadline-batched device dispatches (bccsp/tpu.py
        # BatchingVerifyService — the admission-control knob)
        self.ingress_service = None
        ingress_verify = None
        if ingress_batching:
            from fabric_mod_tpu.bccsp.tpu import BatchingVerifyService
            self.ingress_service = BatchingVerifyService(self.verifier)
            ingress_verify = self.ingress_service.verify_many
        self.registrar = Registrar(
            os.path.join(root_dir, "orderer"), self.orderer_signer,
            self.csp, verify_many=ingress_verify)
        self.support = self.registrar.create_channel(self.genesis_block)
        self.broadcast = Broadcast(self.registrar)
        self.deliver = DeliverService(self.support)

        # the committing peer
        _, config = config_from_block(self.genesis_block)
        bundle = Bundle(channel_id, config, self.csp)
        self.ledger_mgr = LedgerManager(os.path.join(root_dir, "peer"))
        self.ledger = self.ledger_mgr.create_or_open(channel_id)
        self.channel = Channel(channel_id, self.ledger, verifier, bundle,
                               self.csp)
        if self.ledger.height == 0:
            self.channel.init_from_genesis(self.genesis_block)

        # chaincode + endorsers (user contract + the system
        # chaincodes; wiring shared with the real peer process)
        from fabric_mod_tpu.peer.scc import build_default_registry
        self.chaincodes = build_default_registry(self.channel,
                                                 self.ledger)
        self.endorsers: Dict[str, Endorser] = {
            org: Endorser(self.channel, self.chaincodes,
                          self.peer_signers[org])
            for org in orgs}

    # -- client operations ------------------------------------------------
    def invoke(self, args: Sequence[bytes],
               endorsing_orgs: Optional[Sequence[str]] = None,
               chaincode: str = "mycc", transient=None,
               signer=None) -> str:
        orgs = list(endorsing_orgs or list(self.endorsers)[:2])
        return endorse_and_submit(
            self.channel_id, chaincode, args, signer or self.client,
            [self.endorsers[o] for o in orgs], self.broadcast,
            transient=transient)

    def pump_committed(self, want_txs: int, timeout: float = 30.0
                       ) -> int:
        """Run a deliver client until `want_txs` total txs committed.

        Each call starts a deliver client and stops it again: a caller
        that commits block after block (a traffic generator's rounds)
        keeps one `deliver_client()` running instead and waits on the
        ledger's height."""
        client = self.deliver_client()
        t = RegisteredThread(
            target=lambda: client.run(idle_timeout_s=5.0),
            name="e2e-deliver", structure="e2e")
        t.start()
        deadline = time.time() + timeout
        # a running count: each poll reads only the blocks that were
        # committed since the last one
        committed, counted = 0, 1
        while time.time() < deadline:
            height = self.ledger.height
            committed += sum(
                len(self.ledger.get_block_by_number(i).data.data)
                for i in range(counted, height))
            counted = height
            if committed >= want_txs:
                break
            time.sleep(0.02)
        client.stop()
        t.join(timeout=5)
        return committed

    def deploy_chaincode(self, name: str, version: str, sequence: int,
                         policy: bytes = b"", collections: bytes = b"",
                         approving_orgs: Optional[Sequence[str]] = None
                         ) -> int:
        """The full lifecycle ceremony (reference: approve-per-org ->
        commit): each approving org's ADMIN submits an approval
        endorsed by its OWN peer (org-local act), the approvals
        commit, then the commit op (endorsed by a majority) commits.
        Returns the total committed tx count afterwards."""
        from fabric_mod_tpu.peer.lifecycle import LIFECYCLE_NS
        orgs = list(approving_orgs
                    or list(self.endorsers)[:len(self.endorsers) // 2
                                            + 1])
        base = sum(len(self.ledger.get_block_by_number(i).data.data)
                   for i in range(1, self.ledger.height))
        args = [b"approve", name.encode(), version.encode(),
                str(sequence).encode(), policy, collections]
        txids = []
        for org in orgs:
            txids.append(self.invoke(args, endorsing_orgs=[org],
                                     chaincode=LIFECYCLE_NS,
                                     signer=self.admins[org]))
        got = self.pump_committed(base + len(orgs))
        if got < base + len(orgs):
            raise RuntimeError(
                f"approvals did not commit ({got}/{base + len(orgs)})")
        txids.append(self.invoke(
            [b"commit", name.encode(), version.encode(),
             str(sequence).encode(), policy, collections],
            chaincode=LIFECYCLE_NS))
        got = self.pump_committed(base + len(orgs) + 1)
        if got < base + len(orgs) + 1:
            raise RuntimeError("definition commit did not commit")
        # every ceremony tx must have VALIDATED — checked by txid, not
        # by block position (unrelated txs may share our blocks)
        for txid in txids:
            pt = self.ledger.get_transaction_by_id(txid)
            if pt is None or pt.validation_code != \
                    m.TxValidationCode.VALID:
                raise RuntimeError(
                    f"lifecycle tx {txid} invalid "
                    f"({None if pt is None else pt.validation_code})")
        return got

    def deliver_client(self, **kw) -> DeliverClient:
        return DeliverClient(self.channel, self.deliver, **kw)

    def close(self) -> None:
        self.registrar.close()
        self.ledger_mgr.close()
        if self.ingress_service is not None:
            self.ingress_service.close()


def run_pipeline(n_txs: int, verifier, reps_unused: int = 1,
                 stats: dict = None) -> float:
    """Endorse n_txs txs, broadcast them, commit them through the full
    peer pipeline; return committed tx/s over the ordering+commit span
    (endorsement/signing excluded — it is client work).

    `stats`, if given, receives the pipeline's stage wall times
    (stage_secs = host unpack + device dispatch, commit_secs = verdict
    resolve + MVCC + ledger commit, wall_secs = the measured span) so
    the bench can show how much verify time the double buffer hides."""
    from fabric_mod_tpu.observability import tracing
    trace_t0 = ({k: v["secs"]
                 for k, v in tracing.substage_totals().items()}
                if tracing.armed() else None)
    with tempfile.TemporaryDirectory() as root:
        net = Network(root, verifier=verifier)
        try:
            # endorse everything up front (client-side work)
            from fabric_mod_tpu.protos import protoutil
            envs = []
            orgs = list(net.endorsers)[:2]
            for i in range(n_txs):
                sp, prop, _ = protoutil.create_chaincode_proposal(
                    net.channel_id, "mycc",
                    [b"put", b"k%d" % i, b"v%d" % i], net.client)
                responses = [net.endorsers[o].process_proposal(sp)
                             for o in orgs]
                envs.append(protoutil.create_tx_from_responses(
                    prop, responses, net.client))

            t0 = time.perf_counter()
            for env in envs:
                net.broadcast.submit(env)
            # orderer cuts blocks; peer pulls + commits
            client = net.deliver_client()
            runner = RegisteredThread(target=client.run,
                                      name="e2e-deliver-runner",
                                      structure="e2e")
            runner.start()
            # wait until everything committed; the floor covers a COLD
            # XLA compile of the verify program inside the first
            # block's MCS/validate step (minutes on the CPU backend)
            want = net.ledger.height  # will grow; recompute below
            deadline = time.time() + max(420.0, n_txs / 50)
            while time.time() < deadline:
                committed = sum(
                    len(b.data.data)
                    for b in (net.ledger.get_block_by_number(i)
                              for i in range(1, net.ledger.height))
                    if b is not None)
                if committed >= n_txs:
                    break
                time.sleep(0.01)
            dt = time.perf_counter() - t0
            client.stop()
            runner.join(timeout=30)
            if committed < n_txs:
                raise RuntimeError(
                    f"only {committed}/{n_txs} txs committed")
            if stats is not None:
                stats["stage_secs"] = round(client.stage_secs, 3)
                stats["commit_secs"] = round(client.commit_secs, 3)
                # the device-verdict wait inside commit_secs — the
                # part the pipeline hides under the next block's
                # staging (commitpipe's await histogram, summed)
                stats["await_secs"] = round(client.await_secs, 3)
                stats["wall_secs"] = round(dt, 3)
                if trace_t0 is not None:
                    # FMT_TRACE sub-span split of the buckets above:
                    # which part of stage/await/commit actually burns
                    # the wall (recv/unpack/der_marshal/device_
                    # dispatch/verdict_await/policy_*/mvcc/
                    # ledger_write) — the data the next kernel is
                    # chosen by
                    stats["stage_attribution"] = {
                        k: round(v["secs"] - trace_t0.get(k, 0.0), 3)
                        for k, v in tracing.substage_totals().items()}
            return n_txs / dt
        finally:
            net.close()
