"""Smallbank on the committing peer: read-modify-write transactions
whose flags MVCC decides.

What is held here, CPU only, on the repo's fixtures (`e2e.Network`,
`FakeBatchVerifier`) at a small size (200 accounts, Zipf s = 1.0):

* a chain the benchmark's generator makes (`benchmarks/traffic/
  smallbank.py`: the load, then rounds of one block, each endorsed on
  the state the block before left), pulled by a second peer through
  `DeliverClient` and the commit pipeline at its real depth: the flags
  and the state it leaves equal the plain rule's
  (`benchmarks/references/smallbank_mvcc.py`), in blocks of 10 (the
  generic decode) and of 120 (the columnar one); each size also with
  the constant patched across it, so that both sizes commit from
  decoded envelopes under the serial MVCC and from stage's planes
  under the vectorized one;
* every operation of `SmallbankContract` against the rule's own
  recomputation of it;
* the read check itself: a read one block stale, a read stale within
  its block, and an invalid transaction whose write must leave the next
  reader alone;
* an operation on an account nobody created is refused at endorsement.
"""
import os
import random

import pytest

from benchmarks import reference
from benchmarks.references import smallbank_mvcc
from benchmarks.traffic import smallbank as traffic
from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
from fabric_mod_tpu.e2e import Network
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.orderer import DeliverService
from fabric_mod_tpu.peer.chaincode import (
    ChaincodeError, ChaincodeStub, SmallbankContract)
from fabric_mod_tpu.peer.deliverclient import DeliverClient
from fabric_mod_tpu.protos import batchdecode
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from tests.test_block_sig_fold import _Peer

V = m.TxValidationCode
NS = "smallbank"
PARAMS = {
    "chaincode": NS, "accounts": 200, "zipf_s": 1.0, "p_write": 0.95,
    "mix": {"transact_savings": 0.19, "deposit_checking": 0.19,
            "send_payment": 0.19, "write_check": 0.19, "amalgamate": 0.19,
            "balance": 0.05},
    "initial_balance": 1000, "amount_max": 100, "stale_blocks": 0,
    "endorsements_per_tx": 2, "single_endorsed_per": 25,
    "corrupt_signature_per": 25, "warm_blocks": 0}


# -- the generator's chain through the pipeline, against the rule -----------

@pytest.mark.parametrize("vector", [False, True], ids=["serial", "vector"])
@pytest.mark.parametrize("block_txs,rounds", [(10, 6), (120, 6)],
                         ids=["blocks_of_10", "blocks_of_120"])
def test_peer_flags_and_state_equal_the_rules(tmp_path, monkeypatch,
                                              block_txs, rounds, vector):
    assert 10 < batchdecode.COLUMNAR_MIN_ROWS <= 120     # one size on each side
    # commit takes the rows stage's planes hold and decodes the rest:
    # no planes for any block (the constant above it), or planes for
    # blocks of 10 too (the constant at 0)
    monkeypatch.setattr(batchdecode, "COLUMNAR_MIN_ROWS",
                        0 if vector else block_txs + 1)
    root = str(tmp_path)
    net = Network(os.path.join(root, "net"), max_message_count=block_txs,
                  batch_timeout="10s")
    peer = None
    try:
        params = dict(PARAMS, provision_tx_s=block_txs * rounds)
        backlog = traffic.provision(net, params, 2 ** 31 + 7, 1.0,
                                    lambda msg: None)
        assert backlog.n_blocks == backlog.load_blocks + rounds
        assert backlog.warm_blocks == backlog.load_blocks
        assert backlog.expected_codes[V.MVCC_READ_CONFLICT] > 0
        assert backlog.expected_codes[V.ENDORSEMENT_POLICY_FAILURE] > 0

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
        paths = {s["attrs"]["path"] for s in spans
                 if s["name"] == "mvcc_validate"}
        assert paths == ({"vector"} if vector else {"serial"})
        assert {s["attrs"]["decoder"] for s in spans
                if s["name"] == "unpack"} == (
            {"columnar"} if vector else {"generic"})
        extracts = [s["attrs"] for s in spans
                    if s["name"] == "rwset_extract"]
        n_txs = len(backlog.txs)
        assert sum(a["planes"] for a in extracts) == (n_txs if vector else 0)
        assert sum(a["decoded"] for a in extracts) == (0 if vector else n_txs)
        conflicts = sum(s["attrs"]["conflicts"] for s in spans
                        if s["name"] == "mvcc_validate")
        assert conflicts == backlog.expected_codes[V.MVCC_READ_CONFLICT]
        assert sum(s["attrs"]["reads"] for s in spans
                   if s["name"] == "mvcc_validate") > conflicts

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
        rule = smallbank_mvcc.Rule(
            {"orgs": 3, "accounts": 200}, params,
            reference.Signatures().counts)
        compared = reference.compare(rule, backlog.txs, block_txs, acked,
                                     read, held)
        assert compared == dict.fromkeys(compared, 0), compared
        assert len(held) == 400
        recorded = {}
        for blk in read.values():
            for flag in blk.flags:
                recorded[flag] = recorded.get(flag, 0) + 1
        assert recorded == backlog.expected_codes
    finally:
        if peer is not None:
            peer.close()
        net.close()


# -- the contract against the rule's recomputation --------------------------

class _Simulator:
    """A dict behind the stub's three calls."""

    def __init__(self, state):
        self.state, self.written = dict(state), {}

    def get_state(self, ns, key):
        return self.written.get(key, self.state.get(key))

    def set_state(self, ns, key, value):
        self.written[key] = value


def _invoke(state, op, args):
    sim = _Simulator(state)
    SmallbankContract().invoke(ChaincodeStub(
        NS, sim, [op.encode()] + [b"%d" % a for a in args], "tx", "ch"))
    return sim.written


@pytest.mark.parametrize("op,n_args", [
    ("transact_savings", 2), ("deposit_checking", 2), ("send_payment", 3),
    ("write_check", 2), ("amalgamate", 2), ("balance", 1),
    ("create_accounts", 3)])
def test_contract_writes_what_the_rule_recomputes(op, n_args):
    rng = random.Random(op)
    for _ in range(50):
        balances = {f"{kind}_{i}": rng.randint(-50, 300)
                    for i in range(6) for kind in "cs"}
        if op == "create_accounts":
            lo = rng.randint(6, 9)
            args = (lo, lo + rng.randint(0, 4), rng.randint(0, 10 ** 6))
        else:
            a, b = rng.sample(range(6), 2)
            # amounts on both sides of what the account holds, so that
            # write_check's penalty is drawn both ways
            args = {1: (a,), 2: (a, rng.randint(1, 400)),
                    3: (a, b, rng.randint(1, 400))}[n_args]
            if op == "amalgamate":
                args = (a, b)
        rule = smallbank_mvcc.Rule({"orgs": 3, "accounts": 6},
                                   {"accounts": 6}, None)
        rule.held = {key: (bal, (1, 0)) for key, bal in balances.items()}
        due = {key: b"%d" % bal
               for key, bal in rule.writes_of(op, args).items()}
        state = {key: b"%d" % bal for key, bal in balances.items()}
        assert _invoke(state, op, args) == due, (op, args)
    if op == "write_check":
        # one unit of penalty exactly where the check exceeds the sum
        state = {"s_0": b"10", "c_0": b"5"}
        assert _invoke(state, op, (0, 15)) == {"c_0": b"-10"}
        assert _invoke(state, op, (0, 16)) == {"c_0": b"-12"}


@pytest.mark.parametrize("op,args", [
    ("transact_savings", (7, 1)), ("balance", (7,)),
    ("send_payment", (0, 7, 1)), ("amalgamate", (7, 0)),
    ("send_payment", (0, 0, 1)), ("transact_savings", (0, -11)),
    ("deposit_checking", (0, -1)), ("close_account", (0,)),
    ("balance", (0, 1))],
    ids=["missing", "missing_read_only", "missing_payee", "missing_source",
         "to_itself", "savings_below_zero", "negative_deposit",
         "unknown_op", "wrong_arity"])
def test_contract_refuses(op, args):
    with pytest.raises(ChaincodeError):
        _invoke({"s_0": b"10", "c_0": b"10"}, op, args)


# -- the read check, case by case -------------------------------------------

def _endorsed(net, op, *args, orgs=("Org1", "Org2")):
    """An endorsed envelope that is not submitted yet."""
    sp, prop, _ = protoutil.create_chaincode_proposal(
        net.channel_id, NS, [op.encode()] + [b"%d" % a for a in args],
        net.client)
    responses = [net.endorsers[o].process_proposal(sp) for o in orgs]
    return protoutil.create_tx_from_responses(prop, responses, net.client)


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """Three blocks of four: the load; a block whose transactions were
    all endorsed on the load's state; a block that opens with a
    transaction endorsed one block earlier.  Yields (flags by block,
    balances)."""
    root = str(tmp_path_factory.mktemp("smallbank"))
    net = Network(os.path.join(root, "net"), max_message_count=4,
                  batch_timeout="10s")
    try:
        def block_of(envs):
            for env in envs:
                net.broadcast.submit(env)
            return net.pump_committed(4 * net.ledger.height)

        assert block_of([_endorsed(net, "create_accounts", lo, lo + 2, 100)
                         for lo in (0, 2, 4, 6)]) == 4
        late = _endorsed(net, "deposit_checking", 1, 7)
        assert block_of([
            _endorsed(net, "deposit_checking", 1, 5),
            _endorsed(net, "deposit_checking", 1, 6),
            _endorsed(net, "write_check", 2, 5, orgs=("Org1",)),
            _endorsed(net, "balance", 2)]) == 8
        assert block_of([
            late,
            _endorsed(net, "deposit_checking", 2, 1),
            _endorsed(net, "balance", 5),
            _endorsed(net, "balance", 6)]) == 12
        flags = {num: list(protoutil.block_txflags(
            net.ledger.get_block_by_number(num))) for num in (1, 2, 3)}
        balances = {key: (int(value), ver) for key, value, ver
                    in net.ledger.state.get_state_range(NS, "", "")}
        yield flags, balances
    finally:
        net.close()


def test_a_read_stale_within_its_block_is_a_conflict(chain):
    flags, balances = chain
    assert flags[1] == [V.VALID] * 4
    assert flags[2][:2] == [V.VALID, V.MVCC_READ_CONFLICT]
    assert balances["c_1"] == (105, (2, 0))


def test_a_read_one_block_stale_is_a_conflict(chain):
    flags, balances = chain
    assert flags[3][0] == V.MVCC_READ_CONFLICT
    assert balances["c_1"] == (105, (2, 0))


def test_an_invalid_transactions_write_leaves_the_next_reader_alone(chain):
    flags, balances = chain
    # the single-endorsed write_check fails the 2-of-3 policy, so the
    # balance query behind it in the block read nothing stale, and a
    # deposit endorsed a block later finds c_2 at the load's version
    assert flags[2][2:] == [V.ENDORSEMENT_POLICY_FAILURE, V.VALID]
    assert flags[3][1:] == [V.VALID] * 3
    assert balances["c_2"] == (101, (3, 1))
    assert balances["s_2"] == (100, (1, 1))


def test_an_operation_on_a_missing_account_is_refused_at_endorsement(
        tmp_path):
    net = Network(str(tmp_path), max_message_count=1, batch_timeout="10s")
    try:
        sp, _, _ = protoutil.create_chaincode_proposal(
            net.channel_id, NS, [b"deposit_checking", b"3", b"5"],
            net.client)
        response = net.endorsers["Org1"].process_proposal(sp)
        assert response.response.status == 500
        assert "no account behind 'c_3'" in response.response.message
        assert response.endorsement is None
        with pytest.raises(ValueError, match="endorsement failed"):
            net.invoke([b"deposit_checking", b"3", b"5"], chaincode=NS)
        assert net.support.store.height == 1        # nothing was ordered
    finally:
        net.close()


def test_pump_committed_counts_each_block_once(tmp_path):
    net = Network(str(tmp_path), max_message_count=2, batch_timeout="10s")
    try:
        for i in range(6):
            net.invoke([b"put", b"k%d" % i, b"v"])
        assert net.pump_committed(6) == 6
        # a second call starts from the chain as it stands
        for i in range(2):
            net.invoke([b"put", b"j%d" % i, b"v"])
        assert net.pump_committed(8) == 8
        assert net.pump_committed(9, timeout=0.3) == 8
    finally:
        net.close()
