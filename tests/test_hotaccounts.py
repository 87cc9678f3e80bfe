"""The Fabric++ paper's custom workload on the committing peer:
transactions that read eight balances and write eight, most of the
writes blind, over a small hot set.

What is held here, CPU only, on the repo's fixtures (`e2e.Network`,
`FakeBatchVerifier`) at a small size (200 accounts, 20 of them hot,
blocks of 40, RW 8):

* a chain the benchmark's generator makes (`benchmarks/traffic/
  hotaccounts.py`: the load, then rounds of one block, each endorsed
  on the state the block before left), pulled by a second peer through
  `DeliverClient` and the commit pipeline at its real depth: the flags
  and the state it leaves equal the plain rule's
  (`benchmarks/references/hot_accounts_mvcc.py`), from decoded
  envelopes under the serial MVCC and from stage's planes under the
  vectorized one;
* `HotAccountsContract.move` against the rule's own recomputation of
  it, over two transactions of which the second reads what the first
  wrote, writes what the first wrote, or touches nothing of it;
* what the contract refuses: a wrong arity, an account twice in a set,
  an account nobody created;
* the read check itself: a read stale within its block is a conflict,
  a blind write to a key an earlier transaction of the block wrote is
  not;
* the verify provider's chunking, with a stand-in device: a batch one
  item wider than the widest bucket records two `dispatch_chunk` spans
  and counts its chunks, a batch that fits one call records none.
"""
import hashlib
import os

import pytest

from benchmarks import reference
from benchmarks.references import hot_accounts_mvcc
from benchmarks.traffic import hotaccounts as traffic
from fabric_mod_tpu.bccsp.api import VerifyItem
from fabric_mod_tpu.bccsp.tpu import BUCKETS, FakeBatchVerifier
from fabric_mod_tpu.e2e import Network
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.observability.metrics import default_provider
from fabric_mod_tpu.orderer import DeliverService
from fabric_mod_tpu.peer.chaincode import (
    ChaincodeError, ChaincodeStub, HotAccountsContract)
from fabric_mod_tpu.peer.deliverclient import DeliverClient
from fabric_mod_tpu.protos import batchdecode
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from tests.test_block_sig_fold import _ChunkingVerifier, _Peer
from tests.test_smallbank import _Simulator

V = m.TxValidationCode
NS = "accounts"
RW = HotAccountsContract.RW
SETTINGS = {"orgs": 3, "accounts": 200, "rw": RW}
PARAMS = {
    "chaincode": NS, "accounts": 200, "rw": RW, "hot_set": 0.1,
    "hot_read": 0.4, "hot_write": 0.1, "initial_balance": 1000,
    "amount_max": 100, "stale_blocks": 0, "endorsements_per_tx": 2,
    "single_endorsed_per": 25, "corrupt_signature_per": 25,
    "warm_blocks": 0}


# -- the generator's chain through the pipeline, against the rule -----------

@pytest.mark.parametrize("vector", [False, True], ids=["serial", "vector"])
def test_peer_flags_and_state_equal_the_rules(tmp_path, monkeypatch, vector):
    block_txs, rounds = 40, 5
    assert block_txs < batchdecode.COLUMNAR_MIN_ROWS
    # planes for blocks of 40 too (the constant at 0), or for none
    if vector:
        monkeypatch.setattr(batchdecode, "COLUMNAR_MIN_ROWS", 0)
    net = Network(os.path.join(str(tmp_path), "net"),
                  max_message_count=block_txs, batch_timeout="10s")
    peer = None
    try:
        params = dict(PARAMS, provision_tx_s=block_txs * rounds)
        backlog = traffic.provision(net, params, 2 ** 31 + 11, 1.0,
                                    lambda msg: None)
        assert backlog.load_blocks == backlog.warm_blocks == 1
        assert backlog.n_blocks == 1 + rounds
        # the load: 200 accounts over one block of 40
        assert [t.args[1] - t.args[0] for t in backlog.txs[:block_txs]] \
            == [5] * block_txs
        moves = backlog.txs[block_txs:]
        assert all(t.op == "move" and len(t.reads) == RW for t in moves)
        expected = backlog.expected_codes
        # the hot set does its work: most of a block reads what an
        # earlier transaction of it wrote
        assert expected[V.MVCC_READ_CONFLICT] > len(moves) // 2
        assert expected[V.ENDORSEMENT_POLICY_FAILURE] > 0
        assert expected[V.VALID] > block_txs + rounds

        peer = _Peer(net, tmp_path, FakeBatchVerifier(net.csp))
        acked = []
        client = DeliverClient(
            peer.channel, DeliverService(net.support),
            on_commit=lambda blk: acked.append(blk.header.number))
        with tracing.active():
            tracing.recorder().reset()
            client.run(stop_at=backlog.n_blocks, idle_timeout_s=5.0)
            spans = tracing.recorder().recent_spans(limit=1 << 20)
        assert acked == list(range(1, backlog.n_blocks + 1))
        assert not client.rejected
        assert {s["attrs"]["path"] for s in spans
                if s["name"] == "mvcc_validate"} \
            == ({"vector"} if vector else {"serial"})
        extracts = [s["attrs"] for s in spans if s["name"] == "rwset_extract"]
        n_txs = len(backlog.txs)
        assert sum(a["planes"] for a in extracts) == (n_txs if vector else 0)
        assert sum(a["decoded"] for a in extracts) == (0 if vector else n_txs)
        assert sum(s["attrs"]["conflicts"] for s in spans
                   if s["name"] == "mvcc_validate") \
            == expected[V.MVCC_READ_CONFLICT]

        ledger = peer.ledger
        read = {}
        for num in acked:
            blk = ledger.get_block_by_number(num)
            read[num] = reference.ReadBlock(
                number=num, tx_bytes=list(blk.data.data),
                flags=bytes(protoutil.block_txflags(blk)),
                previous_hash=blk.header.previous_hash,
                header_hash=protoutil.block_header_hash(blk.header))
        held = {(NS, key): value for key, value, _ver
                in ledger.state.get_state_range(NS, "", "")}
        rule = hot_accounts_mvcc.Rule(SETTINGS, params,
                                      reference.Signatures().counts)
        compared = reference.compare(rule, backlog.txs, block_txs, acked,
                                     read, held)
        assert compared == dict.fromkeys(compared, 0), compared
        assert len(held) == 200
        recorded = {}
        for blk in read.values():
            for flag in blk.flags:
                recorded[flag] = recorded.get(flag, 0) + 1
        assert recorded == expected
    finally:
        if peer is not None:
            peer.close()
        net.close()


# -- the contract against the rule's recomputation --------------------------

def _invoke(state, op, args):
    sim = _Simulator(state)
    HotAccountsContract().invoke(ChaincodeStub(
        NS, sim, [op.encode()] + [b"%d" % a for a in args], "tx", "ch"))
    return sim.written


FIRST = (*range(0, 8), *range(8, 16), 7)           # reads 0-7, writes 8-15


@pytest.mark.parametrize("second", [
    (*range(12, 20), *range(20, 28), 3),           # reads four of FIRST's writes
    (*range(16, 24), *range(12, 20), 3),           # rewrites four of them
    (*range(16, 24), *range(24, 32), 3)],
    ids=["read_only_overlap", "write_only_overlap", "disjoint"])
def test_contract_writes_what_the_rule_recomputes(second):
    rule = hot_accounts_mvcc.Rule(SETTINGS, PARAMS, None)
    rule.held = {f"a_{i}": (100 + 3 * i, (1, 0)) for i in range(40)}
    state = {key: b"%d" % bal for key, (bal, _v) in rule.held.items()}
    for index, args in enumerate((FIRST, second)):
        due = rule.writes_of("move", args)
        written = _invoke(state, "move", args)
        assert written == {key: b"%d" % bal for key, bal in due.items()}
        assert list(written) == [f"a_{a}" for a in args[RW:-1]]
        state.update(written)
        for key, bal in due.items():
            rule.held[key] = (bal, (2, index))
    # the value is the sum read, the amount and the write's place
    total = sum(100 + 3 * i for i in range(8))
    assert state["a_8"] == b"%d" % (total + 7 + 1)
    assert state["a_11"] == b"%d" % (total + 7 + 4)
    # and the loader's
    assert _invoke({}, "create_accounts", (3, 6, 9)) \
        == {"a_3": b"9", "a_4": b"9", "a_5": b"9"} \
        == {k: b"%d" % v for k, v in
            rule.writes_of("create_accounts", (3, 6, 9)).items()}


def test_contract_wraps_its_sum_at_the_modulus():
    state = {f"a_{i}": b"%d" % (HotAccountsContract.MODULUS - 1)
             for i in range(16)}
    written = _invoke(state, "move", FIRST)
    assert written["a_8"] == b"%d" % (
        (8 * (HotAccountsContract.MODULUS - 1) + 7 + 1)
        % HotAccountsContract.MODULUS)
    assert hot_accounts_mvcc.MODULUS == HotAccountsContract.MODULUS


@pytest.mark.parametrize("op,args", [
    ("move", FIRST[:-1]), ("move", FIRST + (1,)),
    ("create_accounts", (0, 4)), ("transfer", FIRST),
    ("move", (0, 0, *range(2, 8), *range(8, 16), 1)),
    ("move", (*range(0, 8), 8, 8, *range(10, 16), 1)),
    ("move", (*range(0, 7), 99, *range(8, 16), 1))],
    ids=["one_argument_short", "one_argument_long", "loader_arity",
         "unknown_op", "read_account_twice", "written_account_twice",
         "missing_account"])
def test_contract_refuses(op, args):
    with pytest.raises(ChaincodeError):
        _invoke({f"a_{i}": b"10" for i in range(16)}, op, args)


# -- the read check, case by case -------------------------------------------

def _endorsed(net, op, *args):
    sp, prop, _ = protoutil.create_chaincode_proposal(
        net.channel_id, NS, [op.encode()] + [b"%d" % a for a in args],
        net.client)
    responses = [net.endorsers[o].process_proposal(sp)
                 for o in ("Org1", "Org2")]
    return protoutil.create_tx_from_responses(prop, responses, net.client)


def test_a_stale_read_is_a_conflict_and_a_blind_write_is_not(tmp_path):
    """Four transactions endorsed on the load's state, one block: the
    second reads an account the first wrote (stale within its block),
    the third writes one the first wrote and reads nothing written, the
    fourth reads what only the refused second would have written."""
    net = Network(str(tmp_path), max_message_count=4, batch_timeout="10s")
    try:
        for lo in (0, 16, 32, 48):
            net.broadcast.submit(
                _endorsed(net, "create_accounts", lo, lo + 16, 100))
        assert net.pump_committed(4) == 4
        block = [
            (*range(0, 8), *range(8, 16), 1),
            (*range(15, 23), *range(24, 32), 2),
            (*range(32, 40), *range(8, 16), 3),
            (*range(24, 32), *range(40, 48), 4)]
        for args in block:
            net.broadcast.submit(_endorsed(net, "move", *args))
        assert net.pump_committed(8) == 8
        assert list(protoutil.block_txflags(
            net.ledger.get_block_by_number(2))) == [
            V.VALID, V.MVCC_READ_CONFLICT, V.VALID, V.VALID]
        held = {key: (int(value), ver) for key, value, ver
                in net.ledger.state.get_state_range(NS, "", "")}
        # the blind writer's values stand, at its place in the block
        assert held["a_8"] == (800 + 3 + 1, (2, 2))
        assert held["a_24"] == (100, (1, 1))
        assert held["a_40"] == (800 + 4 + 1, (2, 3))
    finally:
        net.close()


def test_an_operation_on_a_missing_account_is_refused_at_endorsement(
        tmp_path):
    net = Network(str(tmp_path), max_message_count=1, batch_timeout="10s")
    try:
        sp, _, _ = protoutil.create_chaincode_proposal(
            net.channel_id, NS,
            [b"move"] + [b"%d" % a for a in FIRST], net.client)
        response = net.endorsers["Org1"].process_proposal(sp)
        assert response.response.status == 500
        assert "no account behind 'a_0'" in response.response.message
        assert response.endorsement is None
    finally:
        net.close()


# -- the provider's chunking -------------------------------------------------

def _chunk_counts() -> dict:
    out = {}
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith("fabric_bccsp_dispatch_chunks_total{"):
            out[line.split('"')[1]] = float(line.split()[-1])
    return out


@pytest.mark.parametrize("n_items,parts", [
    (BUCKETS[-1] + 1, [(0, BUCKETS[-1], BUCKETS[-1]), (1, 1, BUCKETS[0])]),
    (BUCKETS[-1], [])], ids=["one_item_too_wide", "fits_one_call"])
def test_a_chunked_batch_records_its_parts(n_items, parts):
    items = [VerifyItem(hashlib.sha256(b"%d" % i).digest(), b"sig", b"key")
             for i in range(n_items)]
    # every call's lanes come back False: nothing is verified here
    verifier = _ChunkingVerifier(None, deny=range(len(parts) or 1))
    before = _chunk_counts()
    try:
        with tracing.active():
            tracing.recorder().reset()
            verdicts = verifier.verify_many(items)
            spans = tracing.recorder().recent_spans(limit=1 << 20)
    finally:
        verifier.close()
    assert len(verdicts) == n_items and not verdicts.any()
    assert [len(c) for c in verifier.device_calls] \
        == ([p[1] for p in parts] or [n_items])
    chunks = [s["attrs"] for s in spans if s["name"] == "dispatch_chunk"]
    assert [(a["part"], a["items"], a["bucket"]) for a in chunks] == parts
    assert all(a["of"] == len(parts) for a in chunks)
    after = _chunk_counts()
    grown = {b: after[b] - before.get(b, 0.0) for b in after
             if after[b] != before.get(b, 0.0)}
    assert grown == {str(p[2]): 1.0 for p in parts}
