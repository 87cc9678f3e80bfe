"""The block signature rides the block's own verify batch.

`DeliverClient` runs only the host half of the MCS gate at pull time
(`MessageCryptoService.check_block`) and submits the block with its
`SignedData`; the BlockValidation policy's items join the block's
`BatchCollector` after the transactions' and the verdict is the first
thing the commit side reads.  What is held here: one verify call a
block, and the guarantee that no block whose signature set fails the
policy in force at its staging is committed, flagged or applied, nor
any block pulled after it.

CPU only, on the repo's fixtures (`e2e.Network`, `FakeBatchVerifier`).
"""
import copy
import os
import threading
import time

import numpy as np
import pytest

from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier, TpuVerifier
from fabric_mod_tpu.channelconfig import (
    Bundle, compute_update, signed_update_envelope)
from fabric_mod_tpu.channelconfig.bundle import (
    ORDERER, groups_of, policies_of, set_group, set_policy)
from fabric_mod_tpu.channelconfig.configtx import config_from_block
from fabric_mod_tpu.e2e import Network
from fabric_mod_tpu.ledger.kvledger import LedgerManager
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.orderer.blockwriter import block_signed_data
from fabric_mod_tpu.orderer.server import OrdererServer
from fabric_mod_tpu.peer.blocksprovider import (
    Endpoint, FailoverDeliverSource)
from fabric_mod_tpu.peer.channel import Channel
from fabric_mod_tpu.peer.deliverclient import (DeliverClient,
                                               _deferred_rejections)
from fabric_mod_tpu.peer.mcs import BlockVerificationError
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil

V = m.TxValidationCode
SIGNATURES = m.BlockMetadataIndex.SIGNATURES


# -- the fixtures' furniture ------------------------------------------------

def _wait(pred, t=20.0):
    deadline = time.time() + t
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


def _network(tmp_path, **kw):
    kw.setdefault("batch_timeout", "10s")
    kw.setdefault("max_message_count", 1)
    return Network(os.path.join(str(tmp_path), "net"), **kw)


def _chain(net, n_blocks, single_endorsed=()):
    """`n_blocks` one-transaction blocks (the count rule closes each);
    those numbered in `single_endorsed` carry one endorsement, which
    the channel's 2-of-3 policy refuses."""
    base = net.support.store.height
    for i in range(n_blocks):
        num = base + i
        orgs = ["Org1"] if num in single_endorsed else ["Org1", "Org2"]
        net.invoke([b"put", b"k%d" % num, b"v%d" % num],
                   endorsing_orgs=orgs)
        assert _wait(lambda: net.support.store.height > num), \
            f"the orderer never cut block {num}"
    return [net.support.store.get_block_by_number(base + i)
            for i in range(n_blocks)]


class _Peer:
    """A second committing peer over the network's genesis block, with
    a verifier of its own (the network's endorsers call theirs)."""

    def __init__(self, net, tmp_path, verifier, name="peer2"):
        self.mgr = LedgerManager(os.path.join(str(tmp_path), name))
        self.ledger = self.mgr.create_or_open(net.channel_id)
        _, config = config_from_block(net.genesis_block)
        self.channel = Channel(
            net.channel_id, self.ledger, verifier,
            Bundle(net.channel_id, config, net.csp), net.csp)
        self.channel.init_from_genesis(net.genesis_block)

    def close(self):
        self.mgr.close()


def _bad_signature(block):
    """A copy of `block` whose orderer signature no longer verifies.
    Header, data and the metadata's shape are untouched: only the
    BlockValidation policy can tell."""
    bad = copy.deepcopy(block)
    md = list(bad.metadata.metadata)
    meta = m.Metadata.decode(md[SIGNATURES])
    sig = bytearray(meta.signatures[0].signature)
    sig[-1] ^= 0x01
    meta.signatures[0].signature = bytes(sig)
    md[SIGNATURES] = meta.encode()
    bad.metadata.metadata = md
    return bad


def _resign(block, signer):
    """The orderer's signature anew (it covers the header), as
    `BlockWriter.write_block` makes it."""
    md = list(block.metadata.metadata)
    value = m.Metadata.decode(md[SIGNATURES]).value
    sig_header = protoutil.make_signature_header(
        signer.serialize(), protoutil.new_nonce()).encode()
    md[SIGNATURES] = m.Metadata(value=value, signatures=[
        m.MetadataSignature(
            signature_header=sig_header,
            signature=signer.sign_message(
                block_signed_data(block, value, sig_header)))]).encode()
    block.metadata.metadata = md


def _flags(block) -> list:
    """The block's flags as they stand in its metadata: NOT_VALIDATED
    for each transaction as the orderer cut it."""
    return list(protoutil.block_txflags(block))


class _ListSource:
    """Serves copies of `blocks` (by number) from `start`, then ends.
    `pulled` is set once the last of them has been handed over AND the
    client has come back for more."""

    def __init__(self, blocks):
        self._blocks = {b.header.number: b for b in blocks}
        self.pulled = threading.Event()

    def blocks(self, start, stop=None, stop_event=None, timeout_s=30.0):
        num = start
        while num in self._blocks and (stop is None or num <= stop):
            if stop_event is not None and stop_event.is_set():
                return
            yield copy.deepcopy(self._blocks[num])
            num += 1
        self.pulled.set()


class _CountingVerifier(FakeBatchVerifier):
    """Keeps the items of every call."""

    def __init__(self, csp=None):
        super().__init__(csp)
        self.calls = []

    def verify_many(self, items):
        self.calls.append(list(items))
        return super().verify_many(items)


class _HeldVerifier(_CountingVerifier):
    """No verdict resolves before `release` is set: whatever the
    pipeline may stage ahead of a verdict, it has staged by then."""

    def __init__(self, csp=None):
        super().__init__(csp)
        self.release = threading.Event()
        self.dispatched = []               # one entry per async call

    def verify_many_async(self, items):
        self.dispatched.append(len(items))
        resolve = super().verify_many_async(items)

        def held():
            assert self.release.wait(30), "the verdicts were never released"
            return resolve()
        return held


class _AllFalseVerifier(FakeBatchVerifier):
    def verify_many(self, items):
        return np.zeros(len(items), bool)


def _block_sig_items(net, block):
    """The verify items the block's signature set becomes."""
    msp = net.channel.bundle().msp_manager
    items = []
    for sd in net.channel.mcs.check_block(block):
        ident = msp.deserialize_identity(sd.identity)
        items.append(ident.verify_item(sd.data, sd.signature))
    return items


def _state_keys(ledger, nums):
    qe = ledger.new_query_executor()
    return [n for n in nums
            if qe.get_state("mycc", "k%d" % n) is not None]


# -- (a) one call a block ---------------------------------------------------

def test_one_verify_call_per_block_and_the_block_signature_is_in_it(
        tmp_path):
    net = _network(tmp_path)
    verifier = _CountingVerifier(net.csp)
    peer = _Peer(net, tmp_path, verifier)
    try:
        blocks = _chain(net, 5)
        client = DeliverClient(peer.channel, _ListSource(blocks))
        client.run(idle_timeout_s=2.0)
        assert client.rejected == []
        assert peer.ledger.height == 6
        # N calls for N blocks, not 2N, in block order (one stage
        # thread), each holding that block's signature item
        assert len(verifier.calls) == 5
        for block, call in zip(blocks, verifier.calls):
            (sig_item,) = _block_sig_items(net, block)
            assert sig_item in call
            # creator + two endorsements + the block signature, which
            # comes after the transactions' items
            assert len(call) == 4 and call[-1] == sig_item
    finally:
        peer.close()
        net.close()


def test_a_block_with_an_empty_batch_still_makes_one_call(tmp_path):
    """A block whose transactions stage nothing (here: an envelope the
    validator refuses before any signature) still verifies its block
    signature, in a call of one item."""
    net = _network(tmp_path)
    verifier = _CountingVerifier(net.csp)
    peer = _Peer(net, tmp_path, verifier)
    try:
        (block,) = _chain(net, 1)
        hollow = copy.deepcopy(block)
        hollow.data.data[0] = m.Envelope(payload=b"",
                                         signature=b"").encode()
        hollow.header.data_hash = protoutil.block_data_hash(hollow.data)
        _resign(hollow, net.orderer_signer)
        client = DeliverClient(peer.channel, _ListSource([hollow]))
        client.run(idle_timeout_s=2.0)
        assert client.rejected == []
        assert peer.ledger.height == 2
        assert [len(c) for c in verifier.calls] == [1]
        flags = protoutil.block_txflags(
            peer.ledger.get_block_by_number(1))
        assert len(flags) == 1 and flags[0] != V.VALID
    finally:
        peer.close()
        net.close()


# -- (b) a bad block in the middle, the rest already in the pipe -----------

def test_tampered_block_with_later_blocks_already_staged(tmp_path):
    net = _network(tmp_path)
    verifier = _HeldVerifier(net.csp)
    peer = _Peer(net, tmp_path, verifier)
    try:
        blocks = _chain(net, 6)
        served = [_bad_signature(b) if b.header.number == 3 else b
                  for b in blocks]
        source = _ListSource(served)
        client = DeliverClient(peer.channel, source, depth=8)
        runner = threading.Thread(
            target=lambda: client.run(idle_timeout_s=2.0), daemon=True)
        runner.start()
        # 4-6 are pulled and staged (their batches dispatched) before
        # any verdict, block 3's among them, is known
        assert source.pulled.wait(20)
        assert _wait(lambda: len(verifier.dispatched) == 6)
        assert peer.ledger.height == 1
        verifier.release.set()
        runner.join(30)
        assert not runner.is_alive()       # run() returned, no raise
        assert client.rejected == [3]
        assert peer.ledger.height == 3     # genesis, 1, 2
        assert _state_keys(peer.ledger, range(1, 7)) == [1, 2]
    finally:
        peer.close()
        net.close()


def test_rejected_block_is_neither_flagged_nor_applied(tmp_path):
    """The verdict is read before `finish` writes a flag into the
    block's metadata: held on the very object that was submitted."""
    net = _network(tmp_path)
    peer = _Peer(net, tmp_path, FakeBatchVerifier(net.csp))
    try:
        blocks = _chain(net, 3)
        bad = _bad_signature(blocks[2])
        sigs = peer.channel.mcs.check_block(bad)    # structurally sound
        staged = peer.channel.stage_block(bad, sigs)
        assert staged.gate is not None
        with pytest.raises(BlockVerificationError) as err:
            peer.channel.commit_staged(staged)
        assert err.value.number == 3
        assert _flags(bad) == [V.NOT_VALIDATED]
        assert peer.ledger.height == 1
        # the same block, untouched, passes the same way
        good = copy.deepcopy(blocks[0])
        staged = peer.channel.stage_block(
            good, peer.channel.mcs.check_block(good))
        assert peer.channel.commit_staged(staged) == [V.VALID]
        assert _flags(good) == [V.VALID]
    finally:
        peer.close()
        net.close()


def test_deferred_rejection_is_counted_once(tmp_path):
    net = _network(tmp_path)
    peer = _Peer(net, tmp_path, FakeBatchVerifier(net.csp))
    try:
        blocks = _chain(net, 2)
        before = _deferred_rejections().value
        client = DeliverClient(
            peer.channel,
            _ListSource([blocks[0], _bad_signature(blocks[1])]))
        client.run(idle_timeout_s=2.0)
        assert client.rejected == [2]
        assert _deferred_rejections().value - before == 1
    finally:
        peer.close()
        net.close()


# -- (c) the same through a failover source --------------------------------

class _BadSignatureOrdererServer(OrdererServer):
    """Serves real blocks; `bad` (a set of numbers) go out with an
    orderer signature that does not verify and everything else about
    them sound, so only the deferred verdict can refuse them."""

    def __init__(self, registrar, bad, **kw):
        super().__init__(registrar, **kw)
        self._bad = set(bad)

    def _handle_deliver(self, request_iter, context):
        for raw in super()._handle_deliver(request_iter, context):
            resp = m.DeliverResponse.decode(raw)
            if resp.block is not None \
                    and resp.block.header.number in self._bad:
                resp.block = _bad_signature(resp.block)
                yield resp.encode()
            else:
                yield raw


def test_failover_refetches_the_bad_block_and_commits_each_once(tmp_path):
    net = _network(tmp_path)
    peer = _Peer(net, tmp_path, FakeBatchVerifier(net.csp))
    _chain(net, 6)
    evil = _BadSignatureOrdererServer(net.registrar, {3},
                                      address="127.0.0.1:0")
    good = OrdererServer(net.registrar, "127.0.0.1:0")
    evil.start()
    good.start()
    try:
        source = FailoverDeliverSource(
            [Endpoint(f"127.0.0.1:{evil.port}"),
             Endpoint(f"127.0.0.1:{good.port}")],
            net.channel_id, base_backoff_s=0.05)
        committed = []
        client = DeliverClient(
            peer.channel, source,
            on_commit=lambda blk: committed.append(blk.header.number))
        runner = threading.Thread(
            target=lambda: client.run(idle_timeout_s=5.0), daemon=True)
        runner.start()
        assert _wait(lambda: peer.ledger.height == 7), (
            f"height {peer.ledger.height}, rejected {client.rejected}, "
            f"rotations {source.rotations}")
        # a block cut after the rejection flows on
        _chain(net, 1)
        assert _wait(lambda: peer.ledger.height == 8)
        client.stop()
        runner.join(10)
        assert not runner.is_alive()
        assert client.rejected == [3]
        assert source.rotations >= 1
        assert committed == [1, 2, 3, 4, 5, 6, 7]   # each exactly once
        assert _state_keys(peer.ledger, range(1, 8)) == list(range(1, 8))
    finally:
        evil.stop()
        good.stop()
        peer.close()
        net.close()


def test_failover_rewinds_from_a_quiet_tip(tmp_path):
    """The bad block is the orderer's last: the verdict lands while the
    puller waits for a block that is not coming, and the source still
    rewinds (within a poll of the quiet stream)."""
    net = _network(tmp_path)
    peer = _Peer(net, tmp_path, FakeBatchVerifier(net.csp))
    _chain(net, 2)
    evil = _BadSignatureOrdererServer(net.registrar, {2},
                                      address="127.0.0.1:0")
    good = OrdererServer(net.registrar, "127.0.0.1:0")
    evil.start()
    good.start()
    try:
        source = FailoverDeliverSource(
            [Endpoint(f"127.0.0.1:{evil.port}"),
             Endpoint(f"127.0.0.1:{good.port}")],
            net.channel_id, base_backoff_s=0.05)
        client = DeliverClient(peer.channel, source)
        runner = threading.Thread(
            target=lambda: client.run(idle_timeout_s=60.0), daemon=True)
        runner.start()
        assert _wait(lambda: peer.ledger.height == 3, t=10.0), (
            f"height {peer.ledger.height}, rejected {client.rejected}")
        client.stop()
        runner.join(10)
        assert not runner.is_alive()
        assert client.rejected == [2]
    finally:
        evil.stop()
        good.stop()
        peer.close()
        net.close()


def test_every_endpoint_bad_backs_off(tmp_path):
    """Every orderer serves the bad block, each a few blocks ahead of
    the verdict: a rewind to a block already reported is no progress,
    so the backoff engages and rotations stay bounded."""
    net = _network(tmp_path)
    peer = _Peer(net, tmp_path, FakeBatchVerifier(net.csp))
    _chain(net, 5)
    servers = [_BadSignatureOrdererServer(net.registrar, {2},
                                          address="127.0.0.1:0")
               for _ in range(2)]
    for srv in servers:
        srv.start()
    try:
        source = FailoverDeliverSource(
            [Endpoint(f"127.0.0.1:{srv.port}") for srv in servers],
            net.channel_id, base_backoff_s=0.25, max_backoff_s=5.0)
        client = DeliverClient(peer.channel, source)
        runner = threading.Thread(
            target=lambda: client.run(idle_timeout_s=5.0), daemon=True)
        runner.start()
        time.sleep(3.0)
        rotations = source.rotations
        client.stop()
        runner.join(15)
        assert not runner.is_alive()
        # 0.25 + 0.5 + 1 + 2 s of backoff, two rotations between each
        assert 2 <= rotations <= 12, rotations
        assert peer.ledger.height == 2     # genesis and block 1 only
        assert client.rejected and set(client.rejected) == {2}
        assert _state_keys(peer.ledger, range(1, 6)) == [1]
    finally:
        for srv in servers:
            srv.stop()
        peer.close()
        net.close()


# -- (d) a verifier that says no to everything -----------------------------

def test_all_lanes_false_rejects_block_one(tmp_path):
    net = _network(tmp_path)
    peer = _Peer(net, tmp_path, _AllFalseVerifier(net.csp))
    try:
        blocks = _chain(net, 2)
        client = DeliverClient(peer.channel, _ListSource(blocks))
        client.run(idle_timeout_s=2.0)     # returns, does not raise
        assert client.rejected == [1]
        assert peer.ledger.height == 1
        # the client is reusable and says the same again
        client.run(idle_timeout_s=2.0)
        assert client.rejected == [1, 1]
        assert peer.ledger.height == 1
    finally:
        peer.close()
        net.close()


# -- (e) the two paths agree -----------------------------------------------

def test_folded_pipeline_equals_verify_block_then_store_block(tmp_path):
    net = _network(tmp_path)
    sync = _Peer(net, tmp_path, FakeBatchVerifier(net.csp), "sync")
    folded = _Peer(net, tmp_path, FakeBatchVerifier(net.csp), "folded")
    try:
        blocks = _chain(net, 10, single_endorsed={3, 7})
        prev_hash = protoutil.block_header_hash(net.genesis_block.header)
        for block in blocks:
            block = copy.deepcopy(block)
            sync.channel.mcs.verify_block(net.channel_id, block,
                                          expected_prev_hash=prev_hash)
            sync.channel.store_block(block)
            prev_hash = protoutil.block_header_hash(block.header)
        client = DeliverClient(folded.channel, _ListSource(blocks))
        client.run(idle_timeout_s=2.0)
        assert client.rejected == []
        assert folded.ledger.height == sync.ledger.height == 11

        def flags(ledger):
            return [list(protoutil.block_txflags(
                ledger.get_block_by_number(n))) for n in range(1, 11)]
        assert flags(folded.ledger) == flags(sync.ledger)
        assert flags(sync.ledger)[2] == [V.ENDORSEMENT_POLICY_FAILURE]
        assert flags(sync.ledger)[0] == [V.VALID]
        assert folded.ledger.state_fingerprint() == \
            sync.ledger.state_fingerprint()
    finally:
        sync.close()
        folded.close()
        net.close()


@pytest.mark.parametrize("case", ["tampered", "prev_hash", "data_hash",
                                  "no_metadata", "sound"])
def test_verify_block_keeps_its_contract(tmp_path, case):
    """`verify_block` is `check_block` + the policy, on the spot, for
    its other callers (gossip, the relay, the orderer)."""
    net = _network(tmp_path)
    try:
        blocks = _chain(net, 2)
        mcs = net.channel.mcs
        prev = protoutil.block_header_hash(blocks[0].header)
        block = copy.deepcopy(blocks[1])
        if case == "sound":
            mcs.verify_block(net.channel_id, block,
                             expected_prev_hash=prev)
            return
        if case == "tampered":
            block, match = _bad_signature(block), "does not satisfy"
        elif case == "prev_hash":
            prev, match = b"\x00" * 32, "previous-hash mismatch"
        elif case == "data_hash":
            block.data.data[0] = block.data.data[0] + b"\x00"
            match = "data hash mismatch"
        else:
            block.metadata.metadata = []
            match = "no signature metadata"
        with pytest.raises(BlockVerificationError, match=match):
            mcs.verify_block(net.channel_id, block,
                             expected_prev_hash=prev)
    finally:
        net.close()


# -- (f) a block of exactly 2,048 transaction items ------------------------

class _ChunkingVerifier(TpuVerifier):
    """The device verifier's own chunking, cache and dedup over a
    software `_device_dispatch`: what each device call would hold, with
    no program to compile.  `deny` names calls (by order) whose lanes
    all come back False."""

    def __init__(self, csp, deny=()):
        super().__init__(cache_size=0)
        self._csp = csp
        self._deny = set(deny)
        self.device_calls = []

    def _device_dispatch(self, items):
        nth = len(self.device_calls)
        self.device_calls.append(list(items))
        if nth in self._deny:
            return lambda: np.zeros(len(items), bool)
        return lambda: np.asarray(self._csp.verify_batch(items), bool)


@pytest.mark.parametrize("second_chunk_says_no", [False, True])
def test_block_of_2048_items_reads_the_verdict_from_the_second_chunk(
        tmp_path, second_chunk_says_no):
    # 682 transactions of three items (creator + two endorsements)
    # and one of two: 2,048 items before the block signature's
    n_txs = 683
    net = _network(tmp_path, max_message_count=n_txs,
                   preferred_max_bytes=16 << 20)
    verifier = _ChunkingVerifier(
        net.csp, deny={1} if second_chunk_says_no else ())
    peer = _Peer(net, tmp_path, verifier)
    prev = tracing.armed()
    try:
        for i in range(n_txs):
            orgs = ["Org1"] if i == n_txs - 1 else ["Org1", "Org2"]
            net.invoke([b"put", b"w%d" % i, b"x"], endorsing_orgs=orgs)
        assert _wait(lambda: net.support.store.height == 2)
        block = net.support.store.get_block_by_number(1)
        assert len(block.data.data) == n_txs
        tracing.recorder().reset()
        tracing.enable(True)
        client = DeliverClient(peer.channel, _ListSource([block]))
        client.run(idle_timeout_s=2.0)
        tracing.enable(False)
        # two chunks: the widest bucket full of the transactions'
        # items, then the block signature alone
        assert [len(c) for c in verifier.device_calls] == [2048, 1]
        (sig_item,) = _block_sig_items(net, block)
        assert verifier.device_calls[1] == [sig_item]
        (dispatch,) = [s for s in tracing.recorder().recent_spans(
            limit=1 << 20) if s["name"] == "device_dispatch"]
        assert dispatch["attrs"]["items"] == 2049
        assert dispatch["attrs"]["chunks"] == 2
        assert dispatch["attrs"]["block_sigs"] == 1
        if second_chunk_says_no:
            assert client.rejected == [1]
            assert peer.ledger.height == 1
        else:
            assert client.rejected == []
            assert peer.ledger.height == 2
            flags = list(protoutil.block_txflags(
                peer.ledger.get_block_by_number(1)))
            assert flags.count(V.VALID) == n_txs - 1
            assert flags[-1] == V.ENDORSEMENT_POLICY_FAILURE
    finally:
        tracing.enable(prev)
        verifier.close()
        peer.close()
        net.close()


# -- (g) the policy in force at staging decides ----------------------------

def test_block_after_a_config_block_is_held_to_the_new_policy(tmp_path):
    """Config block N replaces the orderer's BlockValidation policy;
    N+1 is signed under the old one only.  Both are pulled before N
    commits; N+1 is staged after it (the barrier) and so refused."""
    net = _network(tmp_path)
    verifier = _HeldVerifier(net.csp)
    peer = _Peer(net, tmp_path, verifier)
    try:
        _chain(net, 1)
        cur = net.support.bundle().config
        desired = m.ConfigGroup.decode(cur.channel_group.encode())
        orderer = groups_of(desired)[ORDERER]
        pol = policies_of(orderer)["BlockValidation"]
        # ANY Writers -> ANY Admins: the orderer node's own identity
        # (OU orderer) writes, and is no admin
        pol.policy = m.Policy(
            type=m.PolicyType.IMPLICIT_META,
            value=m.ImplicitMetaPolicy(
                sub_policy="Admins",
                rule=m.ImplicitMetaRule.ANY).encode())
        set_policy(orderer, "BlockValidation", pol)
        set_group(desired, ORDERER, orderer)
        update = compute_update(net.channel_id, cur, desired)
        cert, key = net.orderer_ca.issue("admin@orderer", "OrdererOrg",
                                         ous=["admin"])
        admin = SigningIdentity("OrdererOrg", cert, calib.key_pem(key),
                                net.csp)
        net.broadcast.submit(
            signed_update_envelope(net.channel_id, update, [admin]))
        assert _wait(lambda: net.support.store.height == 3)
        _chain(net, 2)                     # blocks 3 and 4
        blocks = [net.support.store.get_block_by_number(n)
                  for n in range(1, 5)]
        # the synchronous check against the OLD bundle passes 3: what
        # a pull-time evaluation ahead of the commit would have said
        old = _Peer(net, tmp_path, FakeBatchVerifier(net.csp), "old")
        try:
            old.channel.mcs.verify_block(net.channel_id, blocks[2])
        finally:
            old.close()

        source = _ListSource(blocks)
        client = DeliverClient(peer.channel, source, depth=8)
        runner = threading.Thread(
            target=lambda: client.run(idle_timeout_s=2.0), daemon=True)
        runner.start()
        assert source.pulled.wait(20)      # 1-4 pulled, none committed
        assert peer.ledger.height == 1
        verifier.release.set()
        runner.join(30)
        assert not runner.is_alive()
        assert peer.channel.bundle().sequence == 1   # block 2 applied
        assert client.rejected == [3]
        assert peer.ledger.height == 3
        assert _state_keys(peer.ledger, range(1, 5)) == [1]
    finally:
        peer.close()
        net.close()


def test_missing_block_validation_policy_fails_closed(tmp_path):
    net = _network(tmp_path)
    peer = _Peer(net, tmp_path, FakeBatchVerifier(net.csp))
    try:
        blocks = _chain(net, 1)
        bundle = peer.channel.bundle()
        real = bundle.policy
        bundle.policy = lambda path: (
            None if path.endswith("BlockValidation") else real(path))
        client = DeliverClient(peer.channel, _ListSource(blocks))
        client.run(idle_timeout_s=2.0)
        assert client.rejected == [1]
        assert peer.ledger.height == 1
        with pytest.raises(BlockVerificationError, match="no orderer"):
            peer.channel.mcs.verify_block(net.channel_id, blocks[0])
    finally:
        peer.close()
        net.close()


def test_submit_without_signed_data_behaves_as_before(tmp_path):
    """`PipelinedCommitter.submit(block)`: no gate is staged, as for
    the channel's shared pipeline and the shard router, whose callers
    verify first."""
    from fabric_mod_tpu.peer.commitpipe import PipelinedCommitter
    net = _network(tmp_path)
    verifier = _CountingVerifier(net.csp)
    peer = _Peer(net, tmp_path, verifier)
    try:
        blocks = _chain(net, 2)
        pipe = PipelinedCommitter(peer.channel, depth=2)
        try:
            # even a block whose orderer signature is bad: the gate is
            # the caller's here
            pipe.submit(copy.deepcopy(blocks[0]))
            pipe.submit(_bad_signature(blocks[1]))
            assert pipe.flush(timeout_s=20)
        finally:
            pipe.close()
        assert peer.ledger.height == 3
        assert [len(c) for c in verifier.calls] == [3, 3]
        assert peer.channel.stage_block(
            copy.deepcopy(blocks[0])).gate is None
    finally:
        peer.close()
        net.close()
