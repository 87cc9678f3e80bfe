"""Block-validator tests: the one-device-dispatch-per-block contract,
syntactic rejection matrix, endorsement-policy verdicts, duplicate
handling, and the full validate->MVCC->commit pipeline — modeled on
the reference's txvalidator/v20 suite (validator_test.go)."""
import dataclasses

import pytest

from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.ledger import KvLedger
from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
from fabric_mod_tpu.peer import Committer, TxValidator, ValidationInfoProvider
from fabric_mod_tpu.peer.txvalidator import VALIDATION_PARAMETER
from fabric_mod_tpu.policy import ApplicationPolicyEvaluator, from_string
from fabric_mod_tpu.protos import batchdecode
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil

V = m.TxValidationCode
CHANNEL = "testchannel"


class CountingVerifier:
    """sw-backed verifier that records each dispatch size."""

    def __init__(self):
        self._csp = SwCSP()
        self.calls = []

    def verify_many(self, items):
        self.calls.append(len(items))
        return self._csp.verify_batch(items)


@pytest.fixture(scope="module")
def world():
    csp = SwCSP()
    orgs, msps = {}, []
    for name in ("Org1", "Org2", "Org3"):
        ca = calib.CA(f"ca.{name.lower()}", name)
        msp = Msp(name, csp, [ca.cert])
        msps.append(msp)
        def mk(cn, ous, _ca=ca, _n=name):
            cert, key = _ca.issue(cn, _n, ous=ous)
            return SigningIdentity(_n, cert, calib.key_pem(key), csp)
        orgs[name] = dict(ca=ca, msp=msp,
                          peer=mk(f"peer0.{name.lower()}", ["peer"]),
                          client=mk(f"user@{name.lower()}", ["client"]))
    return dict(csp=csp, orgs=orgs, mgr=MspManager(msps))


def _default_policy() -> bytes:
    return m.ApplicationPolicy(signature_policy=from_string(
        "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')")).encode()


def _validator(world, verifier=None, tx_id_exists=None):
    verifier = verifier or CountingVerifier()
    return TxValidator(
        CHANNEL, world["mgr"],
        ApplicationPolicyEvaluator(world["mgr"]),
        verifier,
        ValidationInfoProvider(_default_policy()),
        tx_id_exists=tx_id_exists), verifier


def _rwset(key="k", val=b"v") -> bytes:
    b = RWSetBuilder()
    b.add_write("mycc", key, val)
    return b.build().encode()


def _tx(world, endorser_names=("Org1", "Org2"), key="k",
        creator_org="Org1", channel=CHANNEL):
    o = world["orgs"]
    return protoutil.create_signed_tx(
        channel, "mycc", _rwset(key),
        o[creator_org]["client"],
        [o[n]["peer"] for n in endorser_names])


def _block(envs, num=0, prev=b""):
    return protoutil.new_block(num, prev, envs)


def _tamper_endorsement(world, env, idx, xor):
    """`env` with the last byte of its `idx`-th endorsement signature
    XORed, re-signed so that the creator check still passes."""
    payload = protoutil.unmarshal_envelope_payload(env)
    tx = protoutil.extract_endorser_tx(payload)
    cap = m.ChaincodeActionPayload.decode(tx.actions[0].payload)
    e = cap.action.endorsements[idx]
    cap.action.endorsements[idx] = m.Endorsement(
        endorser=e.endorser,
        signature=e.signature[:-1] + bytes([e.signature[-1] ^ xor]))
    tx.actions[0] = m.TransactionAction(payload=cap.encode())
    return protoutil.sign_envelope(
        m.Payload(header=payload.header, data=tx.encode()),
        world["orgs"]["Org1"]["client"])


def _config_env(world):
    o = world["orgs"]
    ch = protoutil.make_channel_header(m.HeaderType.CONFIG, CHANNEL,
                                       tx_id="cfg")
    sh = protoutil.make_signature_header(
        o["Org1"]["client"].serialize(), b"nonce")
    return protoutil.sign_envelope(
        protoutil.make_payload(ch, sh, b"config-envelope"),
        o["Org1"]["client"])


def test_valid_block_single_dispatch(world):
    validator, verifier = _validator(world)
    envs = [_tx(world, key=f"k{i}") for i in range(8)]
    flags = validator.validate(_block(envs))
    assert flags == [V.VALID] * 8
    # ONE device dispatch for the whole block: 8 creators + 16
    # endorsements, endorsement pairs dedup'd within each tx's policy
    assert len(verifier.calls) == 1
    assert verifier.calls[0] == 8 + 16
    # flags written into block metadata
    blk = _block(envs)
    validator.validate(blk)
    assert bytes(protoutil.block_txflags(blk)) == bytes([V.VALID] * 8)


def test_under_endorsed_rejected(world):
    validator, _ = _validator(world)
    envs = [_tx(world, endorser_names=("Org1",)),        # 1-of-3 < 2
            _tx(world, endorser_names=("Org1", "Org2"))]
    flags = validator.validate(_block(envs))
    assert flags == [V.ENDORSEMENT_POLICY_FAILURE, V.VALID]


def test_same_org_double_endorsement_insufficient(world):
    """Two endorsements from the same org don't satisfy 2-of-3 distinct
    principals... they are two distinct identities but both satisfy
    only the Org1 leaf, so the second principal is unmet."""
    o = world["orgs"]
    cert, key = o["Org1"]["ca"].issue("peer9.org1", "Org1", ous=["peer"])
    peer9 = SigningIdentity("Org1", cert, calib.key_pem(key), world["csp"])
    env = protoutil.create_signed_tx(
        CHANNEL, "mycc", _rwset(), o["Org1"]["client"],
        [o["Org1"]["peer"], peer9])
    validator, _ = _validator(world)
    assert validator.validate(_block([env])) == [V.ENDORSEMENT_POLICY_FAILURE]


def test_tampered_endorsement_rejected(world):
    # flip a byte in the first endorsement signature
    env2 = _tamper_endorsement(world, _tx(world), 0, 0xFF)
    validator, _ = _validator(world)
    assert validator.validate(_block([env2])) == [V.ENDORSEMENT_POLICY_FAILURE]


def test_bad_creator_signature(world):
    env = _tx(world)
    tampered = m.Envelope(payload=env.payload + b"\x00",
                          signature=env.signature)
    validator, _ = _validator(world)
    flags = validator.validate(_block([tampered]))
    # payload no longer decodes cleanly or sig fails — either way dead
    assert flags[0] in (V.BAD_CREATOR_SIGNATURE, V.BAD_PAYLOAD)
    env2 = _tx(world)
    tampered2 = m.Envelope(payload=env2.payload,
                           signature=env2.signature[:-2] + b"\x00\x00")
    assert validator.validate(_block([tampered2])) == [V.BAD_CREATOR_SIGNATURE]


def test_wrong_channel_and_unknown_type(world):
    env = _tx(world, channel="otherchannel")
    validator, _ = _validator(world)
    assert validator.validate(_block([env])) == [V.BAD_CHANNEL_HEADER]

    # unknown header type
    o = world["orgs"]
    ch = protoutil.make_channel_header(99, CHANNEL, tx_id="t")
    sh = protoutil.make_signature_header(
        o["Org1"]["client"].serialize(), b"n")
    payload = protoutil.make_payload(ch, sh, b"")
    env2 = protoutil.sign_envelope(payload, o["Org1"]["client"])
    assert validator.validate(_block([env2])) == [V.UNKNOWN_TX_TYPE]


def test_txid_binding_enforced(world):
    """tx_id must equal sha256(nonce ‖ creator)."""
    env = _tx(world)
    payload = protoutil.unmarshal_envelope_payload(env)
    ch = m.ChannelHeader.decode(payload.header.channel_header)
    forged_ch = dataclasses.replace(ch, tx_id="0" * 64)
    new_payload = m.Payload(
        header=m.Header(channel_header=forged_ch.encode(),
                        signature_header=payload.header.signature_header),
        data=payload.data)
    env2 = protoutil.sign_envelope(
        new_payload, world["orgs"]["Org1"]["client"])
    validator, _ = _validator(world)
    assert validator.validate(_block([env2])) == [V.BAD_PROPOSAL_TXID]


def test_duplicate_txids(world):
    env = _tx(world)
    validator, _ = _validator(world)
    # in-block duplicate: first wins
    flags = validator.validate(_block([env, env]))
    assert flags == [V.VALID, V.DUPLICATE_TXID]
    # vs-ledger duplicate
    ch = protoutil.envelope_channel_header(env)
    validator2, _ = _validator(
        world, tx_id_exists=lambda t: t == ch.tx_id)
    assert validator2.validate(_block([env])) == [V.DUPLICATE_TXID]


def test_nil_and_garbage_envelopes(world):
    validator, _ = _validator(world)
    blk = protoutil.new_block(0, b"", [])
    blk.data.data = [b"", b"\xff\xff garbage"]
    flags = validator.validate(blk)
    assert flags[0] in (V.NIL_ENVELOPE, V.BAD_PAYLOAD)
    assert flags[1] == V.BAD_PAYLOAD


def test_config_tx_requires_config_machinery(world):
    """CONFIG txs skip endorsement but are fail-closed: without a
    wired config applier they are INVALID_CONFIG_TRANSACTION, and an
    applier's verdict decides (reference: validator.go:400-421 — a
    creator signature alone never commits governance)."""
    env = _config_env(world)
    validator, _ = _validator(world)
    assert validator.validate(_block([env])) == \
        [V.INVALID_CONFIG_TRANSACTION]

    # with an applier: its acceptance makes the tx VALID...
    seen = []
    validator._config_apply = seen.append
    assert validator.validate(_block([env])) == [V.VALID]
    assert len(seen) == 1
    # ...and its rejection marks the tx invalid

    def reject(_env):
        raise ValueError("mod policy says no")
    validator._config_apply = reject
    assert validator.validate(_block([env])) == \
        [V.INVALID_CONFIG_TRANSACTION]


def test_committer_pipeline_with_mvcc(world, tmp_path):
    """validate (device batch) -> MVCC -> commit; conflicting rwsets
    surface as MVCC conflicts, not policy failures."""
    led = KvLedger(str(tmp_path / "ch"), CHANNEL)
    validator, verifier = _validator(
        world, tx_id_exists=led.tx_id_exists)
    committer = Committer(validator, led)

    envs = [_tx(world, key="acct"), _tx(world, key="acct")]
    flags = committer.store_block(_block(envs))
    # both policy-valid; both blind writes -> both commit
    assert flags == [V.VALID, V.VALID]
    assert led.height == 1

    # a tx reading a now-stale version
    sim = led.new_tx_simulator("probe")
    sim.get_state("mycc", "acct")
    stale_rwset = sim.done().encode()
    o = world["orgs"]
    env_ok = protoutil.create_signed_tx(
        CHANNEL, "mycc", stale_rwset, o["Org1"]["client"],
        [o["Org1"]["peer"], o["Org2"]["peer"]])
    # commit something that bumps the version first
    bump = _tx(world, key="acct")
    flags2 = committer.store_block(
        _block([bump, env_ok], num=1,
               prev=led.blockstore.last_block_hash))
    assert flags2 == [V.VALID, V.MVCC_READ_CONFLICT]
    led.close()


# --- named validation plugins (reference: handlers/library/registry.go) ---

class _VetoPending:
    def finish(self, _mask):
        return False


class _VetoEvaluator:
    """A plugin that rejects every action (stages nothing)."""

    def prepare(self, _policy, _sds, _collector):
        return _VetoPending()


def _plugin_vinfo(plugin_name):
    class V:
        def validation_info(self, ns):
            return plugin_name, _default_policy()
    return V()


def test_registered_plugin_overrides_builtin_vscc(world):
    from fabric_mod_tpu.peer.plugins import PluginRegistry
    reg = PluginRegistry()
    reg.register("veto", _VetoEvaluator)
    validator = TxValidator(
        CHANNEL, world["mgr"],
        ApplicationPolicyEvaluator(world["mgr"]),
        CountingVerifier(), _plugin_vinfo("veto"),
        plugin_registry=reg)
    # perfectly endorsed tx — the veto plugin still rejects it
    flags = validator.validate(_block([_tx(world)]))
    assert flags == [V.ENDORSEMENT_POLICY_FAILURE]


def test_unknown_plugin_fails_closed(world):
    validator = TxValidator(
        CHANNEL, world["mgr"],
        ApplicationPolicyEvaluator(world["mgr"]),
        CountingVerifier(), _plugin_vinfo("no-such-plugin"))
    flags = validator.validate(_block([_tx(world)]))
    assert flags == [V.INVALID_OTHER_REASON]


def test_vscc_name_resolves_to_builtin(world):
    validator, _ = _validator(world)
    assert validator._plugins.names() == ["vscc"]
    flags = validator.validate(_block([_tx(world)]))
    assert flags == [V.VALID]


# --- the decode path is chosen by the block's row count -----------------
# (protos/batchdecode.COLUMNAR_MIN_ROWS): under the constant every row
# takes _stage_tx's generic chain, at or above it the two columnar
# pre-passes run first.  Same bytes, same outcome, either way.

T = batchdecode.COLUMNAR_MIN_ROWS
GARBAGE = b"\xff\xff garbage"


class RecordingVerifier(CountingVerifier):
    """Keeps each dispatched batch: the device's lanes, in order."""

    def __init__(self):
        super().__init__()
        self.batches = []

    def verify_many(self, items):
        self.batches.append(list(items))
        return super().verify_many(items)


def _vp_tx(world, key):
    """Writes `key` and pins it to Org3's peer (a vp_writes row)."""
    o = world["orgs"]
    b = RWSetBuilder()
    b.add_write("mycc", key, b"v")
    b.add_metadata_write(
        "mycc", key, VALIDATION_PARAMETER, m.ApplicationPolicy(
            signature_policy=from_string("'Org3.peer'")).encode())
    return protoutil.create_signed_tx(
        CHANNEL, "mycc", b.build().encode(), o["Org1"]["client"],
        [o["Org1"]["peer"], o["Org2"]["peer"]])


SPECIAL_ROWS = {
    "single": lambda w: _tx(w, endorser_names=("Org1",), key="single"),
    "corrupt": lambda w: _tamper_endorsement(
        w, _tx(w, key="corrupt"), 1, 1),
    "malformed": lambda w: m.Envelope(payload=GARBAGE, signature=b"sig"),
    "config": _config_env,
    "vp": lambda w: _vp_tx(w, "pinned"),
}


_PLAIN = []


def _plain_envs(world, n):
    """`n` well-endorsed transactions on keys of their own, signed
    once: the world fixture's identities are module-wide."""
    while len(_PLAIN) < n:
        _PLAIN.append(_tx(world, key=f"k{len(_PLAIN)}"))
    return _PLAIN[:n]


def _mixed_envs(world, n, only=None):
    """`n` envelopes.  One row: the kind `only` names (None: a valid
    transaction).  More: every special row among valid ones, and
    after them a second writer of the pinned key (an in-block
    key-level candidate)."""
    if n == 1:
        return [SPECIAL_ROWS[only](world)] if only else _plain_envs(world, 1)
    envs = _plain_envs(world, n)
    for pos, kind in zip(range(1, n, 2), SPECIAL_ROWS):
        envs[pos] = SPECIAL_ROWS[kind](world)
    if n > 12:
        envs[11] = _tx(world, key="pinned")
    return envs


def _stage_under(world, block, min_rows, monkeypatch):
    monkeypatch.setattr(batchdecode, "COLUMNAR_MIN_ROWS", min_rows)
    validator, verifier = _validator(world, RecordingVerifier())
    validator._config_apply = lambda env: None
    staged = validator.stage(block)
    flags = validator.finish(staged)
    return staged, flags, verifier.batches


DECODE_CASES = ([(1, kind) for kind in (None, *SPECIAL_ROWS)]
                + [(n, None) for n in (T - 1, T, T + 1)])
DECODE_IDS = [f"{n}-{kind or 'rows'}" for n, kind in DECODE_CASES]


@pytest.mark.parametrize("n,only", DECODE_CASES, ids=DECODE_IDS)
def test_decode_paths_stage_the_same(world, monkeypatch, n, only):
    """The lanes of the device batch, every _TxWork and the txflags
    do not depend on which decoder read the block."""
    envs = _mixed_envs(world, n, only)
    seen = {}
    for path in ("generic", "columnar"):
        blk = _block(envs)
        if n > 1:
            blk.data.data.append(GARBAGE)       # no Envelope at all
        staged, flags, batches = _stage_under(
            world, blk, len(blk.data.data) + 1 if path == "generic" else 0,
            monkeypatch)
        assert (staged.rwsets is None) == (path == "generic" or n < 4)
        seen[path] = dict(
            batches=batches, flags=flags,
            txflags=bytes(protoutil.block_txflags(blk)),
            works=[(w.flag, w.txid, w.vp_writes, w.is_config,
                    w.creator_slot, w.written_ns, len(w.actions),
                    [[(ke.ns, ke.key, ke.committed is not None,
                       list(ke.inblock))
                      for ke in a.key_evals] for a in w.actions])
                   for w in staged.works])
    assert seen["generic"] == seen["columnar"]
    assert len(seen["generic"]["batches"]) == 1
    flags = seen["generic"]["flags"]
    if n > 1:
        assert flags[-1] == V.BAD_PAYLOAD
        assert flags[1:10:2] == [
            V.ENDORSEMENT_POLICY_FAILURE, V.ENDORSEMENT_POLICY_FAILURE,
            V.BAD_PAYLOAD, V.VALID, V.VALID]
        # the pin written at row 9 is in force for row 11's write of
        # the same key: Org1 + Org2 do not satisfy 'Org3.peer'
        assert flags[11] == V.ENDORSEMENT_POLICY_FAILURE
        assert seen["generic"]["works"][9][2], "a vp_writes row"
        assert flags.count(V.VALID) == n - 4


@pytest.mark.parametrize("planes", [False, True],
                         ids=["planes-withheld", "planes-handed-over"])
@pytest.mark.parametrize("n", [1, T - 1, T, T + 1])
def test_decode_paths_commit_the_same_state(world, tmp_path, monkeypatch,
                                            n, planes):
    """commit_block after a generic stage (no planes to hand over)
    leaves the state a columnar stage leaves, whether commit is handed
    the columnar stage's planes (the vectorized MVCC over them) or
    they are withheld (rwsets=None: every envelope decoded, the
    serial MVCC)."""
    from fabric_mod_tpu.observability import tracing
    envs = _mixed_envs(world, n)
    seen = {}
    tracing.recorder().reset()
    try:
        for path, min_rows in (("generic", n + 1), ("columnar", 0)):
            monkeypatch.setattr(batchdecode, "COLUMNAR_MIN_ROWS", min_rows)
            led = KvLedger(str(tmp_path / path), CHANNEL)
            validator, _ = _validator(world, tx_id_exists=led.tx_id_exists)
            validator._config_apply = lambda env: None
            blk = _block(envs)
            staged = validator.stage(blk)
            if path == "generic":
                assert staged.rwsets is None
            with tracing.active():
                final = led.commit_block(
                    blk, validator.finish(staged),
                    rwsets=staged.rwsets if planes else None)
            seen[path] = (list(final), led.state_fingerprint(),
                          led.state_fingerprint_full())
            led.close()
        mvcc_paths = [s["attrs"]["path"]
                      for s in tracing.recorder().recent_spans(limit=1 << 20)
                      if s["name"] == "mvcc_validate"]
    finally:
        tracing.recorder().reset()
    assert seen["generic"] == seen["columnar"]
    assert seen["generic"][0].count(V.VALID) == (n if n == 1 else n - 4)
    # decode_block_rwsets refuses a batch under 4 rows: no planes at all
    assert mvcc_paths == [
        "serial", "vector" if planes and n >= 4 else "serial"]


def test_decode_path_engages_and_is_observable(world):
    """A 10-tx block takes the generic chain and says so: the unpack
    span's `decoder`, no body_decode span, the per-path block counter.
    A block at the constant takes the columnar pre-passes."""
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.peer.txvalidator import _decode_path_metrics
    assert 10 < T, "the test network's 10-tx blocks are small blocks"
    counters = _decode_path_metrics()
    tracing.recorder().reset()
    try:
        for n, path, other in ((10, "generic", "columnar"),
                               (T, "columnar", "generic")):
            validator, _ = _validator(world)
            before = {p: c.value for p, c in counters.items()}
            with tracing.active():
                staged = validator.stage(
                    _block(_plain_envs(world, n), num=n))
            spans = [s for s in tracing.recorder().recent_spans(limit=1 << 20)
                     if s["attrs"].get("block") == n]
            unpack = [s for s in spans if s["name"] == "unpack"]
            assert [s["attrs"]["decoder"] for s in unpack] == [path]
            assert unpack[0]["attrs"]["txs"] == n
            assert len([s for s in spans if s["name"] == "body_decode"]) \
                == (path == "columnar")
            assert (staged.rwsets is not None) == (path == "columnar")
            assert counters[path].value == before[path] + 1
            assert counters[other].value == before[other]
    finally:
        tracing.recorder().reset()
