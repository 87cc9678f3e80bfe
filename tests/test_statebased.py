"""Key-level (state-based) endorsement + lifecycle validation info.

(reference test model: integration/sbe state-based-endorsement suites
and core/common/validation/statebased/validator_keylevel tests: a
key's VALIDATION_PARAMETER overrides the chaincode-wide policy, with
intra-block ordering of override writes.)
"""
import threading
import time

import pytest

from fabric_mod_tpu.e2e import Network
from fabric_mod_tpu.policy import from_string
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil

V = m.TxValidationCode


@pytest.fixture()
def net(tmp_path):
    n = Network(str(tmp_path), batch_timeout="100ms",
                max_message_count=25)
    yield n
    n.close()


def _commit_all(net, n_envs, timeout=20.0):
    client = net.deliver_client()
    t = threading.Thread(target=client.run, daemon=True)
    t.start()
    deadline = time.time() + timeout
    committed = 0
    while time.time() < deadline:
        committed = sum(
            len(net.ledger.get_block_by_number(i).data.data)
            for i in range(1, net.ledger.height))
        if committed >= n_envs:
            break
        time.sleep(0.02)
    client.stop()
    t.join(timeout=5)
    return committed


def _all_flags(net):
    out = []
    for i in range(1, net.ledger.height):
        blk = net.ledger.get_block_by_number(i)
        out.extend(protoutil.block_txflags(blk))
    return out


def _org_policy(*orgs) -> bytes:
    dsl = "OR(%s)" % ", ".join(f"'{o}.peer'" for o in orgs)
    return m.ApplicationPolicy(signature_policy=from_string(dsl)).encode()


def test_key_level_policy_flips_between_blocks(net):
    # block A: create the key + pin it to Org3 only
    net.invoke([b"put", b"pinned", b"v0"])
    committed = _commit_all(net, 1)
    assert committed == 1
    net.invoke([b"setvp", b"pinned", _org_policy("Org3")],
               endorsing_orgs=["Org1", "Org2"])
    assert _commit_all(net, 2) == 2

    # block B: writing with 2-of-3 (Org1+Org2) violates the Org3 pin
    net.invoke([b"put", b"pinned", b"v1"],
               endorsing_orgs=["Org1", "Org2"])
    # while an Org3-endorsed write passes
    net.invoke([b"put", b"pinned", b"v2"], endorsing_orgs=["Org3"])
    assert _commit_all(net, 4) == 4

    flags = _all_flags(net)
    assert flags.count(V.ENDORSEMENT_POLICY_FAILURE) == 1
    assert flags.count(V.VALID) == 3
    qe = net.ledger.new_query_executor()
    assert qe.get_state("mycc", "pinned") == b"v2"
    # the metadata survived in the state DB
    meta = net.ledger.state.get_metadata("mycc", "pinned")
    assert meta and "VALIDATION_PARAMETER" in meta


def test_key_level_intra_block_dependency(net):
    """An override written by a valid tx i of a block leaves no write of
    the key by a tx j > i of the SAME block valid, whoever endorsed it
    (reference: vpmanagerimpl.go's ValidationParameterUpdatedError,
    which validator_keylevel.go turns into a policy failure); here the
    write's Org1+Org2 would fail the new Org3 pin as well."""
    net.invoke([b"put", b"k", b"v0"])
    assert _commit_all(net, 1) == 1

    # same block: [setvp -> Org3 only, write endorsed by Org1+Org2]
    net.invoke([b"setvp", b"k", _org_policy("Org3")],
               endorsing_orgs=["Org1", "Org2"])
    net.invoke([b"put", b"k", b"v1"], endorsing_orgs=["Org1", "Org2"])
    assert _commit_all(net, 3) == 3

    flags = _all_flags(net)
    # the setvp is VALID; the 2-of-3 write in the same block already
    # validates under the new Org3-only pin -> fails
    assert flags.count(V.ENDORSEMENT_POLICY_FAILURE) == 1
    qe = net.ledger.new_query_executor()
    assert qe.get_state("mycc", "k") == b"v0"


def test_vp_on_one_key_does_not_bypass_cc_policy_on_others(net):
    """A tx satisfying key A's narrow VP must still satisfy the
    chaincode-wide policy for its OTHER written keys (regression: the
    cc-wide check must not be skipped when any key has a VP)."""
    net.invoke([b"put", b"a", b"0"])
    assert _commit_all(net, 1) == 1
    # pin key "a" to Org3 only
    net.invoke([b"setvp", b"a", _org_policy("Org3")],
               endorsing_orgs=["Org1", "Org2"])
    assert _commit_all(net, 2) == 2

    # Org3 alone satisfies a's VP but NOT the cc-wide MAJORITY(2-of-3);
    # the tx also writes key "b" (no VP) -> must fail
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    from fabric_mod_tpu.protos import protoutil as pu
    b = RWSetBuilder()
    b.add_write("mycc", "a", b"x")       # VP-covered (Org3)
    b.add_write("mycc", "b", b"y")       # cc-wide policy applies
    env = pu.create_signed_tx(
        net.channel_id, "mycc", b.build().encode(), net.client,
        [net.peer_signers["Org3"]])      # satisfies a's VP only
    blk = pu.new_block(
        net.ledger.height,
        pu.block_header_hash(net.ledger.get_block_by_number(
            net.ledger.height - 1).header), [env])
    flags = net.channel.validator().validate(blk)
    assert flags == [V.ENDORSEMENT_POLICY_FAILURE]

    # control: Org3 + Org1 (VP satisfied AND 2-of-3 majority) passes
    env2 = pu.create_signed_tx(
        net.channel_id, "mycc", b.build().encode(), net.client,
        [net.peer_signers["Org3"], net.peer_signers["Org1"]])
    blk2 = pu.new_block(
        net.ledger.height,
        pu.block_header_hash(net.ledger.get_block_by_number(
            net.ledger.height - 1).header), [env2])
    flags2 = net.channel.validator().validate(blk2)
    assert flags2 == [V.VALID]


def test_lifecycle_definition_changes_cc_policy(net):
    """Committing a chaincode definition flips the namespace's
    endorsement policy for subsequent blocks (reference:
    plugindispatcher resolving lifecycle ValidationInfo)."""
    # default channel policy: MAJORITY Endorsement (2 of 3) — passes
    net.invoke([b"put", b"a", b"1"], endorsing_orgs=["Org1", "Org2"])
    assert _commit_all(net, 1) == 1

    # commit a definition pinning mycc to Org1 only (the full
    # approve->commit ceremony: 2 approvals + 1 commit = 3 more txs)
    net.deploy_chaincode("mycc", "2.0", 1, policy=_org_policy("Org1"))

    # now Org2-endorsed writes fail, Org1-endorsed pass
    net.invoke([b"put", b"b", b"2"], endorsing_orgs=["Org2"])
    net.invoke([b"put", b"c", b"3"], endorsing_orgs=["Org1"])
    assert _commit_all(net, 6) == 6
    flags = _all_flags(net)
    assert flags.count(V.ENDORSEMENT_POLICY_FAILURE) == 1
    qe = net.ledger.new_query_executor()
    assert qe.get_state("mycc", "c") == b"3"
    assert qe.get_state("mycc", "b") is None


# -- the in-block rule, asset-transfer-sbe, the barrier's span ---------------

def _next_block(net, envs):
    prev = net.ledger.get_block_by_number(net.ledger.height - 1)
    return protoutil.new_block(
        net.ledger.height, protoutil.block_header_hash(prev.header), envs)


def _signed(net, rwset, endorsing_orgs):
    return protoutil.create_signed_tx(
        net.channel_id, "mycc", rwset.build().encode(), net.client,
        [net.peer_signers[o] for o in endorsing_orgs])


def _rwset(write=None, vp=None, key="k"):
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    from fabric_mod_tpu.peer.txvalidator import VALIDATION_PARAMETER
    b = RWSetBuilder()
    if write is not None:
        b.add_write("mycc", key, write)
    if vp is not None:
        b.add_metadata_write("mycc", key, VALIDATION_PARAMETER, vp)
    return b


def _counter(name):
    from fabric_mod_tpu.observability.metrics import default_provider
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return 0.0


def test_new_owner_write_after_an_inblock_policy_change_fails(net):
    """The pin's new org endorses the write in the block that moved the
    pin to it: ENDORSEMENT_POLICY_FAILURE (Fabric), where the old
    reading took the in-block pin as the policy and passed it.  In the
    next block the same write is valid under the committed pin."""
    assert net.channel.store_block(_next_block(
        net, [_signed(net, _rwset(write=b"v0"), ["Org1", "Org2"])])) \
        == [V.VALID]
    updated = "fabric_validator_vp_updated_invalid_total"
    inblock = 'fabric_validator_key_policy_evals_total{source="inblock"}'
    before = _counter(updated), _counter(inblock)
    flags = net.channel.store_block(_next_block(net, [
        _signed(net, _rwset(vp=_org_policy("Org3")), ["Org1", "Org2"]),
        _signed(net, _rwset(write=b"v1"), ["Org3"])]))
    assert flags == [V.VALID, V.ENDORSEMENT_POLICY_FAILURE]
    assert (_counter(updated), _counter(inblock)) \
        == (before[0] + 1, before[1] + 1)
    assert net.ledger.new_query_executor().get_state("mycc", "k") == b"v0"
    assert net.channel.store_block(_next_block(
        net, [_signed(net, _rwset(write=b"v2"), ["Org3"])])) == [V.VALID]


def test_write_after_an_invalid_inblock_change_meets_the_committed_policy(
        net):
    """A policy change that fails its own policy changes nothing: the
    writes after it in the block are judged by the committed pin."""
    net.channel.store_block(_next_block(net, [_signed(
        net, _rwset(write=b"v0", vp=_org_policy("Org1")),
        ["Org1", "Org2"])]))
    flags = net.channel.store_block(_next_block(net, [
        # the committed pin is Org1's: Org2 alone cannot move it
        _signed(net, _rwset(vp=_org_policy("Org3")), ["Org2"]),
        _signed(net, _rwset(write=b"v1"), ["Org1"]),
        _signed(net, _rwset(write=b"v2"), ["Org3"])]))
    assert flags == [V.ENDORSEMENT_POLICY_FAILURE, V.VALID,
                     V.ENDORSEMENT_POLICY_FAILURE]
    assert net.ledger.new_query_executor().get_state("mycc", "k") == b"v1"
    meta = net.ledger.state.get_metadata("mycc", "k")
    assert meta["VALIDATION_PARAMETER"] == _org_policy("Org1")


@pytest.fixture()
def net2(tmp_path):
    """The test network's two orgs, under the default MAJORITY
    Endorsement: both peers."""
    n = Network(str(tmp_path), orgs=("Org1", "Org2"),
                batch_timeout="100ms", max_message_count=25)
    yield n
    n.close()


def _client(net, org):
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity
    cert, key = net.cas[org].issue(f"client@{org.lower()}", org,
                                   ous=["client"])
    return SigningIdentity(org, cert, calib.key_pem(key), net.csp)


def _sbe_tx(net, client, args, endorsing_orgs):
    """An asset-transfer-sbe envelope, endorsed on the committed state;
    None where an endorser refused it."""
    sp, prop, _ = protoutil.create_chaincode_proposal(
        net.channel_id, "sbe", [a.encode() for a in args], client)
    responses = [net.endorsers[o].process_proposal(sp)
                 for o in endorsing_orgs]
    if any(r.response.status != 200 for r in responses):
        return None
    return protoutil.create_tx_from_responses(prop, responses, client)


def test_sbe_asset_contract_sets_and_moves_the_key_policy(net2):
    """CreateAsset pins the asset to the creator's org and needs both
    orgs; an update passes with the owner's peer alone and fails with
    the other's; TransferAsset moves the pin, and with it who may
    update."""
    import json
    from fabric_mod_tpu.peer.chaincode import SbeAssetContract
    org1, org2 = _client(net2, "Org1"), _client(net2, "Org2")

    def commit(*envs):
        return net2.channel.store_block(_next_block(net2, list(envs)))

    def asset():
        raw = net2.ledger.new_query_executor().get_state("sbe", "asset1")
        meta = net2.ledger.state.get_metadata("sbe", "asset1")
        return json.loads(raw), meta["VALIDATION_PARAMETER"]

    assert commit(_sbe_tx(net2, org1, ["CreateAsset", "asset1", "10", "Tom"],
                          ["Org1"])) == [V.ENDORSEMENT_POLICY_FAILURE]
    assert commit(_sbe_tx(net2, org1, ["CreateAsset", "asset1", "10", "Tom"],
                          ["Org1", "Org2"])) == [V.VALID]
    assert asset() == ({"ID": "asset1", "Value": 10, "Owner": "Tom",
                        "OwnerOrg": "Org1"},
                       SbeAssetContract.owner_policy("Org1"))
    assert SbeAssetContract.owner_policy("Org1") == m.ApplicationPolicy(
        signature_policy=from_string("OutOf(1, 'Org1.peer')")).encode()
    assert _sbe_tx(net2, org2, ["CreateAsset", "asset1", "1", "Max"],
                   ["Org1", "Org2"]) is None         # it exists
    assert commit(_sbe_tx(net2, org2, ["UpdateAsset", "asset1", "20"],
                          ["Org2"])) == [V.ENDORSEMENT_POLICY_FAILURE]
    assert commit(_sbe_tx(net2, org1, ["UpdateAsset", "asset1", "30"],
                          ["Org1"])) == [V.VALID]
    assert commit(_sbe_tx(net2, org1,
                          ["TransferAsset", "asset1", "Max", "Org2"],
                          ["Org1"])) == [V.VALID]
    assert asset() == ({"ID": "asset1", "Value": 30, "Owner": "Max",
                        "OwnerOrg": "Org2"},
                       SbeAssetContract.owner_policy("Org2"))
    assert commit(
        _sbe_tx(net2, org1, ["UpdateAsset", "asset1", "40"], ["Org1"]),
    ) == [V.ENDORSEMENT_POLICY_FAILURE]
    assert commit(_sbe_tx(net2, org2, ["UpdateAsset", "asset1", "50"],
                          ["Org2"])) == [V.VALID]
    env = _sbe_tx(net2, org2, ["ReadAsset", "asset1"], ["Org2"])
    assert env is not None and asset()[0]["Value"] == 50


def test_decode_paths_agree_on_the_samples_rows(net2, monkeypatch):
    """A block of the sample's rows (metadata writes among them) stages
    and resolves alike through the generic per-tx decode and through
    the columnar decoders, which accept every row."""
    from fabric_mod_tpu.protos import batchdecode
    org1, org2 = _client(net2, "Org1"), _client(net2, "Org2")
    net2.channel.store_block(_next_block(net2, [_sbe_tx(
        net2, org1, ["CreateAsset", "asset0", "1", "Tom"],
        ["Org1", "Org2"])]))
    envs = [
        _sbe_tx(net2, org1, ["UpdateAsset", "asset0", "2"], ["Org1"]),
        _sbe_tx(net2, org1, ["TransferAsset", "asset0", "Max", "Org2"],
                ["Org1"]),
        # the new owner, in the block of the transfer
        _sbe_tx(net2, org2, ["UpdateAsset", "asset0", "3"], ["Org2"]),
        _sbe_tx(net2, org2, ["CreateAsset", "asset1", "4", "Ann"],
                ["Org1", "Org2"])]
    seen = {}
    for path, min_rows in (("generic", len(envs) + 1), ("columnar", 0)):
        monkeypatch.setattr(batchdecode, "COLUMNAR_MIN_ROWS", min_rows)
        validator = net2.channel.validator()
        staged = validator.stage(_next_block(net2, envs))
        if path == "columnar":
            assert all(b is not None for b in staged.rwsets.bodies)
        else:
            assert staged.rwsets is None
        seen[path] = (
            validator.finish(staged), staged.needs_barrier,
            [(w.flag, w.vp_writes, w.written_ns,
              [[(ke.ns, ke.key, ke.committed is not None, list(ke.inblock))
                for ke in a.key_evals] for a in w.actions])
             for w in staged.works])
    assert seen["generic"] == seen["columnar"]
    flags, barrier, works = seen["generic"]
    assert flags == [V.VALID, V.VALID, V.ENDORSEMENT_POLICY_FAILURE,
                     V.VALID]
    assert barrier
    # the transfer's new pin is staged for the write after it
    assert works[2][3] == [[("sbe", "asset0", True, [1])]]


class _LateVerifier:
    """The software verifier, answering each batch 0.2 s late: the
    block after a barrier block reaches the stage loop while the
    barrier block still waits for its verdicts."""

    def __init__(self, csp):
        from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
        self._inner = FakeBatchVerifier(csp)

    def verify_many(self, items):
        time.sleep(0.2)
        return self._inner.verify_many(items)


def _pipelined(net, tmp_path, blocks):
    """`blocks` through a second peer's commit pipeline with tracing
    armed: (flags per block, the spans recorded)."""
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    from fabric_mod_tpu.ledger.kvledger import LedgerManager
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.peer import PipelinedCommitter
    from fabric_mod_tpu.peer.channel import Channel
    _, config = config_from_block(net.genesis_block)
    mgr = LedgerManager(str(tmp_path / "second-peer"))
    try:
        chan = Channel(net.channel_id, mgr.create_or_open(net.channel_id),
                       _LateVerifier(net.csp),
                       Bundle(net.channel_id, config, net.csp), net.csp)
        chan.init_from_genesis(net.genesis_block)
        flags = []
        tracing.recorder().reset()
        with tracing.active():
            pipe = PipelinedCommitter(
                chan, on_commit=lambda _b, f: flags.append(list(f)))
            for blk in blocks:
                pipe.submit(m.Block.decode(blk.encode()))
            pipe.flush()
            pipe.close()
        return flags, tracing.recorder().recent_spans(limit=1 << 20)
    finally:
        mgr.close()


def _chain(net, *block_rwsets):
    """Blocks 1.. over the genesis block: (rwset, endorsing orgs) rows."""
    prev = protoutil.block_header_hash(net.genesis_block.header)
    out = []
    for number, rows in enumerate(block_rwsets, 1):
        blk = protoutil.new_block(
            number, prev, [_signed(net, rw, orgs) for rw, orgs in rows])
        prev = protoutil.block_header_hash(blk.header)
        out.append(blk)
    return out


def test_policy_writes_set_the_barrier_and_its_wait_is_a_span(net, tmp_path):
    blocks = _chain(
        net, [(_rwset(write=b"v0"), ["Org1", "Org2"])],
        [(_rwset(vp=_org_policy("Org3")), ["Org1", "Org2"])],
        # valid only under the pin block 2 commits
        [(_rwset(write=b"v1"), ["Org3"])])
    assert not net.channel.validator().stage(blocks[0]).needs_barrier
    assert net.channel.validator().stage(blocks[1]).needs_barrier
    barriers = _counter("fabric_commitpipe_barriers_total")
    flags, spans = _pipelined(net, tmp_path, blocks)
    assert flags == [[V.VALID], [V.VALID], [V.VALID]]
    assert _counter("fabric_commitpipe_barriers_total") == barriers + 1
    waits = [s for s in spans if s["name"] == "stage_barrier_wait"]
    assert [(s["attrs"]["block"], s["attrs"]["barrier_block"])
            for s in waits] == [(3, 2)]
    slot = {s["span_id"]: s for s in spans if s["name"] == "stage_wait_slot"}
    assert slot[waits[0]["parent_id"]]["attrs"]["block"] == 3
    # block 1 meets no key metadata, in the state or in the block
    stage = [s["attrs"] for s in spans if s["name"] == "key_policy_stage"]
    assert [(a["block"], a["keys"], a["committed"], a["inblock"])
            for a in stage] == [(2, 1, 0, 0), (3, 1, 1, 0)]
    resolve = [s for s in spans if s["name"] == "key_policy_resolve"]
    assert [(s["attrs"]["keys"], s["attrs"]["vp_updated_invalid"])
            for s in resolve] == [(1, 0)]
    finish = {s["span_id"]: s for s in spans if s["name"] == "policy_finish"}
    assert finish[resolve[0]["parent_id"]]["attrs"]["block"] == 3


def test_a_block_without_key_policies_records_no_barrier_or_resolution(
        net, tmp_path):
    blocks = _chain(net, [(_rwset(write=b"v0"), ["Org1", "Org2"])],
                    [(_rwset(write=b"v1", key="j"), ["Org1", "Org2"]),
                     (_rwset(write=b"v2"), ["Org2", "Org3"])])
    flags, spans = _pipelined(net, tmp_path, blocks)
    assert flags == [[V.VALID], [V.VALID, V.VALID]]
    names = {s["name"] for s in spans}
    # the state holds no key metadata and no tx writes any: nothing is
    # looked up, so the key-level pass opens no span either
    assert not {"stage_barrier_wait", "key_policy_resolve",
                "key_policy_stage"} & names
    assert "unpack" in names


@pytest.mark.parametrize("kind", ["memory", "durable"])
def test_state_says_whether_any_key_carries_metadata(kind, tmp_path):
    """Both state DBs answer `has_metadata`, which the validator reads
    before it looks up each written key's committed policy."""
    from fabric_mod_tpu.ledger.durable import DurableStateDB
    from fabric_mod_tpu.ledger.statedb import UpdateBatch, VersionedDB
    db = VersionedDB() if kind == "memory" \
        else DurableStateDB(str(tmp_path / "state"))
    try:
        batch = UpdateBatch()
        batch.put("ns", "k", b"v", (1, 0))
        db.apply_updates(batch, 1)
        assert not db.has_metadata()
        batch = UpdateBatch()
        batch.put_metadata("ns", "k", {"VALIDATION_PARAMETER": b"p"}, (2, 0))
        db.apply_updates(batch, 2)
        assert db.has_metadata()
        batch = UpdateBatch()
        batch.delete("ns", "k", (3, 0))
        db.apply_updates(batch, 3)
        assert not db.has_metadata()
    finally:
        if kind == "durable":
            db.close()


def test_no_lookup_until_a_key_carries_metadata(net):
    """The channel's validator asks the state for no key's policy while
    no key carries metadata, and asks for each written key once one
    does."""
    asked = []
    state = net.ledger.state
    get_metadata = state.get_metadata

    def counted(ns, key):
        asked.append((ns, key))
        return get_metadata(ns, key)

    state.get_metadata = counted
    try:
        net.invoke([b"put", b"plain", b"v0"])
        net.invoke([b"put", b"pinned", b"v0"])
        assert _commit_all(net, 2) == 2
        assert asked == [] and not state.has_metadata()
        net.invoke([b"setvp", b"pinned", _org_policy("Org3")],
                   endorsing_orgs=["Org1", "Org2"])
        assert _commit_all(net, 3) == 3
        assert state.has_metadata()
        net.invoke([b"put", b"plain", b"v1"])
        assert _commit_all(net, 4) == 4
        assert ("mycc", "plain") in asked
    finally:
        del state.get_metadata
    assert _all_flags(net) == [V.VALID] * 4
