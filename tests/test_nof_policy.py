"""A channel under a flat `OutOf(k, ...)` signature policy: what the
deployment `thakkar-nof-4` (benchmarks/configs: four orgs, k = 3)
forces of the program, held on the CPU at a width the benchmark does
not reach (sixteen orgs, k = 9), so that the program stays general.

* genesis states the channel's default endorsement policy as a
  SIGNATURE policy when one is given, and is byte for byte what it was
  when none is;
* the closure walk of `policy/cauthdsl.py` against a reference written
  here: distinct peer orgs with a counting signature >= k;
* one nested tree against a table worked by hand, so that the greedy
  used-flag discipline stays pinned;
* an in-process sixteen-org network commits a block of 8-, 9- and
  10-endorsement transactions, a corrupted ninth signature and a
  doubled endorser;
* the orderer's byte limits reach the cutter through `e2e.Network`;
* the device verifier chunks a batch wider than its widest bucket.
"""
import dataclasses
import random
import time

import numpy as np
import pytest

from fabric_mod_tpu import e2e
from fabric_mod_tpu.bccsp.api import VerifyItem
from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.bccsp.tpu import BUCKETS, TpuVerifier
from fabric_mod_tpu.channelconfig import genesis
from fabric_mod_tpu.channelconfig.bundle import (
    APPLICATION, ORDERER, set_group, set_policy)
from fabric_mod_tpu.channelconfig.configtx import config_from_block
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.observability.metrics import default_provider
from fabric_mod_tpu.policy import CompiledPolicy, from_string
from fabric_mod_tpu.policy.manager import ImplicitMetaPolicyObj
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from fabric_mod_tpu.protos.protoutil import SignedData

V = m.TxValidationCode
ORGS = [f"Org{i}" for i in range(1, 17)]
ENDORSEMENT_PATH = "/Channel/Application/Endorsement"


def out_of(k: int) -> str:
    return f"OutOf({k}, " + ", ".join(f"'{o}.peer'" for o in ORGS) + ")"


# --- genesis ---------------------------------------------------------------

def groups_of(block):
    _, config = config_from_block(block)
    return {e.key: e.value for e in config.channel_group.groups}


def test_a_stated_endorsement_policy_is_a_signature_policy(tmp_path):
    net = e2e.Network(str(tmp_path), orgs=ORGS,
                      endorsement_policy=out_of(9))
    try:
        policies = {e.key: e.value.policy for e in
                    groups_of(net.genesis_block)[APPLICATION].policies}
        assert policies["Endorsement"].type == m.PolicyType.SIGNATURE
        assert m.SignaturePolicyEnvelope.decode(
            policies["Endorsement"].value) == from_string(out_of(9))
        # lifecycle stays the majority of the orgs' own policies
        assert policies["LifecycleEndorsement"].type == \
            m.PolicyType.IMPLICIT_META
        bundle = net.channel.bundle()
        assert isinstance(bundle.policy(ENDORSEMENT_PATH), CompiledPolicy)
        assert isinstance(
            bundle.policy("/Channel/Application/LifecycleEndorsement"),
            ImplicitMetaPolicyObj)
    finally:
        net.close()


def meta(rule: int, sub_policy: str) -> m.ConfigPolicy:
    return m.ConfigPolicy(mod_policy="Admins", policy=m.Policy(
        type=m.PolicyType.IMPLICIT_META, value=m.ImplicitMetaPolicy(
            sub_policy=sub_policy, rule=rule).encode()))


def test_no_stated_policy_is_the_genesis_it_was(tmp_path):
    """The application group written out as it was before it took a
    policy, and the orderer group as `e2e.Network` asked for it before
    it passed byte limits: both encode to the same bytes."""
    net = e2e.Network(str(tmp_path), max_message_count=7,
                      batch_timeout="3s")
    try:
        got = groups_of(net.genesis_block)
        pems = {org: [calib.cert_pem(ca.cert)]
                for org, ca in net.cas.items()}
        app = m.ConfigGroup(mod_policy="Admins")
        for org in sorted(pems):
            set_group(app, org, genesis.org_group(org, pems[org]))
        rule = m.ImplicitMetaRule
        for name, how, sub in (
                ("Readers", rule.ANY, "Readers"),
                ("Writers", rule.ANY, "Writers"),
                ("Admins", rule.MAJORITY, "Admins"),
                ("Endorsement", rule.MAJORITY, "Endorsement"),
                ("LifecycleEndorsement", rule.MAJORITY, "Endorsement")):
            set_policy(app, name, meta(how, sub))
        assert got[APPLICATION].encode() == app.encode()
        ordr = genesis.orderer_group(
            [genesis.org_group("OrdererOrg",
                               [calib.cert_pem(net.orderer_ca.cert)])],
            ["OrdererOrg"], max_message_count=7, batch_timeout="3s")
        assert got[ORDERER].encode() == ordr.encode()
        cfg = net.support.cutter.config
        assert (cfg.absolute_max_bytes, cfg.preferred_max_bytes) == \
            (10 * 1024 * 1024, 2 * 1024 * 1024)
    finally:
        net.close()


# --- the walk against a reference ------------------------------------------

@pytest.fixture(scope="module")
def world():
    """Sixteen orgs: a peer each, a second peer of Org2, a client of
    Org1 (an identity of the channel that is no peer)."""
    csp = SwCSP()
    cas = {org: calib.CA(f"ca.{org.lower()}", org) for org in ORGS}

    def issue(org, cn, ou):
        cert, key = cas[org].issue(cn, org, ous=[ou])
        return SigningIdentity(org, cert, calib.key_pem(key), csp)

    return dict(
        mgr=MspManager([Msp(org, csp, [ca.cert])
                        for org, ca in cas.items()]),
        peers={org: issue(org, f"peer0.{org.lower()}", "peer")
               for org in ORGS},
        peer2=issue("Org2", "peer1.org2", "peer"),
        client=issue("Org1", "client@org1", "client"))


def signed(ident, data: bytes, corrupt: bool = False) -> SignedData:
    sig = ident.sign_message(data)
    if corrupt:
        sig = sig[:-1] + bytes([sig[-1] ^ 1])
    return SignedData(data=data, identity=ident.serialize(),
                      signature=sig)


def reference_verdict(entries, k: int) -> bool:
    """Distinct peer orgs with a counting signature >= k.  `entries`:
    (identity bytes, org, is a peer, the signature counts), in order;
    of an identity that comes twice the first decides (Fabric's
    SignatureSetToValidIdentities drops the repeats unverified)."""
    seen, orgs = set(), set()
    for identity, org, is_peer, counts in entries:
        if identity in seen:
            continue
        seen.add(identity)
        if is_peer and counts:
            orgs.add(org)
    return len(orgs) >= k


@pytest.mark.parametrize("k", [1, 9, 16])
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7, 424242])
def test_flat_nof_walk_equals_the_reference(world, seed, k):
    rng = random.Random(seed * 31 + k)
    pol = CompiledPolicy(from_string(out_of(k)), world["mgr"])
    data = b"prp-%d-%d" % (seed, k)
    verdicts = set()
    sizes = (0, 1, k - 1, k, k, k + 1, 16, 16, rng.randint(0, 16),
             rng.randint(0, 16))
    for round_, size in enumerate(sizes):
        chosen = rng.sample(ORGS, max(0, min(16, size)))
        sds, entries = [], []
        # every other round has no corrupted signature, so that both
        # verdicts come up for every k
        p_corrupt = 0.15 * (round_ % 2)

        def put(ident, org, is_peer):
            corrupt = rng.random() < p_corrupt
            sd = signed(ident, data, corrupt)
            sds.append(sd)
            entries.append((sd.identity, org, is_peer, not corrupt))

        for org in chosen:
            put(world["peers"][org], org, True)
            if rng.random() < 0.2:          # the same identity again
                put(world["peers"][org], org, True)
        if rng.random() < 0.5:
            put(world["client"], "Org1", False)
        order = list(range(len(sds)))
        rng.shuffle(order)
        sds = [sds[i] for i in order]
        entries = [entries[i] for i in order]
        want = reference_verdict(entries, k)
        assert pol.evaluate_signed_data(sds) == want, (size, chosen)
        verdicts.add(want)
    assert verdicts == {True, False}


NESTED = ("OutOf(2, AND('Org1.peer', 'Org2.peer'), "
          "AND('Org2.peer', 'Org3.peer'), AND('Org3.peer', 'Org4.peer'))")


@pytest.mark.parametrize("who,want", [
    # AND(1,2) takes 1 and 2; AND(2,3) finds 2 used and gives its
    # trial back; AND(3,4) takes 3 and 4
    (["Org1", "Org2", "Org3", "Org4"], True),
    # AND(1,2) takes 2, so neither of the others completes
    (["Org1", "Org2", "Org3"], False),
    # AND(2,3) takes 3, so AND(3,4) cannot
    (["Org2", "Org3", "Org4"], False),
    # a second peer of Org2 serves AND(2,3)
    (["Org1", "Org2", "Org2b", "Org3"], True),
    (["Org2", "Org2b", "Org3", "Org4"], False),
    (["Org1", "Org3", "Org4"], False),
    # greedy, not optimal: 2 then 2b go to the first two, 3 is used
    (["Org1", "Org2", "Org2b", "Org3", "Org4"], True),
    ([], False),
])
def test_nested_tree_keeps_the_used_flag_discipline(world, who, want):
    pol = CompiledPolicy(from_string(NESTED), world["mgr"])
    idents = dict(world["peers"], Org2b=world["peer2"])
    assert pol.evaluate_signed_data(
        [signed(idents[w], b"nested") for w in who]) == want


# --- sixteen orgs, one block ------------------------------------------------

def counter_values():
    out = {}
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith("fabric_policy_signature_evals_total{"):
            out[line.split('"')[1]] = float(line.split()[1])
    return out


@pytest.fixture(scope="module")
def block_of_five(tmp_path_factory):
    """Five transactions in one block of a sixteen-org channel under
    OutOf(9, ...): endorsed by 8, 9 and 10 orgs, by 9 with the ninth
    signature corrupted, and by 8 with the eighth's response twice."""
    net = e2e.Network(str(tmp_path_factory.mktemp("nof16")), orgs=ORGS,
                      endorsement_policy=out_of(9), max_message_count=5,
                      batch_timeout="10s",
                      preferred_max_bytes=4 * 1024 * 1024)
    try:
        def tx(i, n_orgs, alter=lambda responses: responses):
            sp, prop, _ = protoutil.create_chaincode_proposal(
                net.channel_id, "mycc", [b"put", b"k%d" % i, b"v%d" % i],
                net.client)
            responses = [net.endorsers[o].process_proposal(sp)
                         for o in ORGS[:n_orgs]]
            return protoutil.create_tx_from_responses(
                prop, alter(responses), net.client)

        def corrupt_ninth(responses):
            sig = responses[8].endorsement.signature
            responses[8] = dataclasses.replace(
                responses[8], endorsement=dataclasses.replace(
                    responses[8].endorsement,
                    signature=sig[:-1] + bytes([sig[-1] ^ 1])))
            return responses

        envs = [tx(0, 8), tx(1, 9), tx(2, 10), tx(3, 9, corrupt_ninth),
                tx(4, 8, lambda r: r + r[-1:])]
        before = counter_values()
        tracing.recorder().reset()
        was = tracing.armed()
        tracing.enable(True)
        try:
            for env in envs:
                net.broadcast.submit(env)
            assert net.pump_committed(5) == 5
        finally:
            tracing.enable(bool(was))
        spans = tracing.recorder().recent_spans(limit=10_000)
        after = counter_values()
        block = net.ledger.get_block_by_number(1)
        yield dict(
            flags=list(protoutil.block_txflags(block)),
            sizes=[len(d) for d in block.data.data],
            state={key: value for key, value, _ver in
                   net.ledger.state.get_state_range("mycc", "", "")},
            spans=spans,
            evals={r: after.get(r, 0) - before.get(r, 0)
                   for r in ("satisfied", "unsatisfied")})
    finally:
        net.close()


def test_block_of_five_flags_and_state(block_of_five):
    failure = V.ENDORSEMENT_POLICY_FAILURE
    assert block_of_five["flags"] == [
        failure, V.VALID, V.VALID, failure, failure]
    assert block_of_five["state"] == {"k1": b"v1", "k2": b"v2"}
    # nine endorsements make the envelope the deployment's ~7.5 KB
    assert 7000 < block_of_five["sizes"][1] < 8000


def test_block_of_five_is_counted_once_per_block(block_of_five):
    assert block_of_five["evals"] == {"satisfied": 2, "unsatisfied": 3}
    finish = [s for s in block_of_five["spans"]
              if s["name"] == "policy_finish"
              and s["attrs"].get("block") == 1]
    assert len(finish) == 1
    assert finish[0]["attrs"]["evals"] == 5
    # the stand-in verifier is no device: it names no chunks
    dispatch = [s for s in block_of_five["spans"]
                if s["name"] == "device_dispatch"]
    assert dispatch and all("chunks" not in s["attrs"] for s in dispatch)


# --- the byte rule -----------------------------------------------------------

@pytest.mark.parametrize("preferred,first_block", [
    (4 * 1024 * 1024, 12),      # closed by count
    (32 * 1024, 4),             # 5 x 7.4 KB would pass 32 KiB
])
def test_preferred_max_bytes_reaches_the_cutter(tmp_path, preferred,
                                                first_block):
    net = e2e.Network(str(tmp_path), orgs=ORGS,
                      endorsement_policy=out_of(9), max_message_count=12,
                      batch_timeout="300ms", preferred_max_bytes=preferred,
                      absolute_max_bytes=8 * 1024 * 1024)
    try:
        cfg = net.support.cutter.config
        assert (cfg.preferred_max_bytes, cfg.absolute_max_bytes) == \
            (preferred, 8 * 1024 * 1024)
        for i in range(12):
            net.invoke([b"put", b"k%d" % i, b"v"], endorsing_orgs=ORGS[:9])
        deadline = time.monotonic() + 20.0
        while sum(len(net.support.store.get_block_by_number(n).data.data)
                  for n in range(1, net.support.store.height)) < 12:
            assert time.monotonic() < deadline, "12 envelopes never cut"
            time.sleep(0.01)
        first = net.support.store.get_block_by_number(1)
        assert len(first.data.data) == first_block
        assert all(7000 < len(d) < 8000 for d in first.data.data)
    finally:
        net.close()


# --- a batch wider than the widest bucket ------------------------------------

def test_dispatch_chunks_past_the_widest_bucket():
    verifier = TpuVerifier(cache_size=0)
    items = [VerifyItem(i.to_bytes(32, "big"), b"sig", b"xy")
             for i in range(4500)]
    planted = {7, 2048 + 1000, 4096 + 403}
    calls = []

    def stand_in(chunk):
        calls.append(len(chunk))
        mask = np.array([int.from_bytes(it.digest, "big") not in planted
                         for it in chunk], bool)
        return lambda: mask

    verifier._device_dispatch = stand_in
    try:
        resolve = verifier._dispatch(items)
        assert calls == [BUCKETS[-1], BUCKETS[-1], 404]
        assert resolve.chunks == 3
        mask = resolve()
        assert mask.shape == (4500,)
        assert set(np.flatnonzero(~mask)) == planted
        # through the seam the validator calls, the count rides along
        calls.clear()
        assert verifier.verify_many_async(items[:2049]).chunks == 2
        assert calls == [BUCKETS[-1], 1]
        assert verifier.verify_many_async(items[:5]).chunks == 1
    finally:
        verifier.close()
