"""MSP tests: chain validation, roles, principals, caches — modeled on
the reference's msp/testdata scenario matrix (expired, wrong CA,
revoked, NodeOUs) but with fixtures generated on the fly."""
import datetime
import sys
import threading

import pytest

from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.cache import CachedMsp
from fabric_mod_tpu.msp.identities import SigningIdentity, deserialize_cert
from fabric_mod_tpu.msp import mspimpl
from fabric_mod_tpu.msp.mspimpl import Msp, MspManager, MSPValidationError
from fabric_mod_tpu.protos import messages as m


@pytest.fixture(scope="module")
def org():
    csp = SwCSP()
    root = calib.CA("ca.org1.example.com", "Org1")
    inter = calib.CA.__new__(calib.CA)          # intermediate signed by root
    cert, key = root.issue("ica.org1.example.com", "Org1", is_ca=True)
    inter.cert, inter.key = cert, key
    peer_cert, peer_key = inter.issue("peer0.org1", "Org1", ous=["peer"])
    admin_cert, admin_key = root.issue("admin@org1", "Org1", ous=["admin"])
    client_cert, client_key = inter.issue("user1@org1", "Org1", ous=["client"])
    msp = Msp("Org1MSP", csp, [root.cert], [inter.cert])
    return dict(csp=csp, root=root, inter=inter, msp=msp,
                peer=(peer_cert, peer_key), admin=(admin_cert, admin_key),
                client=(client_cert, client_key))


def _ident(org, which):
    cert, key = org[which]
    return SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])


def test_serialize_deserialize_roundtrip(org):
    ident = _ident(org, "peer")
    got = org["msp"].deserialize_identity(ident.serialize())
    assert got.common_name() == "peer0.org1"
    assert got.ski() == ident.ski()


def test_validate_chain_through_intermediate(org):
    org["msp"].validate(_ident(org, "peer"))      # inter-signed
    org["msp"].validate(_ident(org, "admin"))     # root-signed


def test_foreign_ca_rejected(org):
    evil = calib.CA("ca.evil.example.com", "Evil")
    cert, key = evil.issue("peer0.org1", "Org1", ous=["peer"])
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    with pytest.raises(MSPValidationError):
        org["msp"].validate(ident)


def test_expired_cert_rejected(org):
    past = (datetime.datetime.now(datetime.timezone.utc)
            - datetime.timedelta(days=1))
    cert, key = org["root"].issue("old@org1", "Org1", not_after=past)
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    with pytest.raises(MSPValidationError, match="validity"):
        org["msp"].validate(ident)


def test_revoked_cert_rejected(org):
    cert, key = org["root"].issue("gone@org1", "Org1")
    msp = Msp("Org1MSP", org["csp"], [org["root"].cert],
              revoked_serials=[cert.serial_number])
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    with pytest.raises(MSPValidationError, match="revoked"):
        msp.validate(ident)


def test_ca_cert_rejected_as_identity(org):
    """Reference: msp/mspimpl.go:713-716 — a CA certificate (root,
    intermediate, or any leaf with CA=true) is not an identity."""
    root_ident = SigningIdentity(
        "Org1MSP", org["root"].cert, calib.key_pem(org["root"].key),
        org["csp"])
    with pytest.raises(MSPValidationError, match="CA certificate"):
        org["msp"].validate(root_ident)
    inter_ident = SigningIdentity(
        "Org1MSP", org["inter"].cert, calib.key_pem(org["inter"].key),
        org["csp"])
    with pytest.raises(MSPValidationError, match="CA certificate"):
        org["msp"].validate(inter_ident)


def test_revoked_intermediate_poisons_leaf(org):
    cert, key = org["inter"].issue("victim@org1", "Org1")
    msp = Msp("Org1MSP", org["csp"], [org["root"].cert],
              [org["inter"].cert],
              revoked_serials=[org["inter"].cert.serial_number])
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    with pytest.raises(MSPValidationError, match="revoked"):
        msp.validate(ident)


def test_crl_revocation(org):
    # CRL building/parsing is outside the wheel-less x509 fallback's
    # scope (bccsp/_x509fallback.py) — real wheel only
    x509 = pytest.importorskip("cryptography.x509")
    from cryptography.hazmat.primitives import hashes
    now = datetime.datetime.now(datetime.timezone.utc)
    cert, key = org["root"].issue("crled@org1", "Org1")
    crl = (x509.CertificateRevocationListBuilder()
           .issuer_name(org["root"].cert.subject)
           .last_update(now).next_update(now + datetime.timedelta(days=7))
           .add_revoked_certificate(
               x509.RevokedCertificateBuilder()
               .serial_number(cert.serial_number)
               .revocation_date(now).build())
           .sign(org["root"].key, hashes.SHA256()))
    msp = Msp("Org1MSP", org["csp"], [org["root"].cert], crls=[crl])
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    with pytest.raises(MSPValidationError, match="revoked"):
        msp.validate(ident)
    # a CRL from an untrusted issuer is refused outright
    evil = calib.CA("ca.evil", "Evil")
    bad_crl = (x509.CertificateRevocationListBuilder()
               .issuer_name(evil.cert.subject)
               .last_update(now).next_update(now + datetime.timedelta(days=7))
               .sign(evil.key, hashes.SHA256()))
    with pytest.raises(MSPValidationError, match="CRL"):
        Msp("Org1MSP", org["csp"], [org["root"].cert], crls=[bad_crl])


def test_key_usage_enforced(org):
    """A leaf whose KeyUsage forbids digitalSignature can't sign —
    reject it at validation time."""
    try:
        from cryptography import x509
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec as _ec
    except ImportError:       # wheel-less: the x509 fallback issues too
        from fabric_mod_tpu.bccsp import _x509fallback as x509
        from fabric_mod_tpu.bccsp._ecfallback import ec as _ec, hashes
    key = _ec.generate_private_key(_ec.SECP256R1())
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(x509.Name([x509.NameAttribute(
                x509.oid.NameOID.COMMON_NAME, "enc-only@org1")]))
            .issuer_name(org["root"].cert.subject)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=5))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.BasicConstraints(ca=False, path_length=None),
                           critical=True)
            .add_extension(x509.KeyUsage(
                digital_signature=False, key_cert_sign=False, crl_sign=False,
                content_commitment=False, key_encipherment=True,
                data_encipherment=False, key_agreement=False,
                encipher_only=False, decipher_only=False), critical=True)
            .sign(org["root"].key, hashes.SHA256()))
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    with pytest.raises(MSPValidationError, match="KeyUsage"):
        org["msp"].validate(ident)


def _role_principal(role, mspid="Org1MSP"):
    return m.MSPPrincipal(
        principal_classification=m.PrincipalClassification.ROLE,
        principal=m.MSPRole(msp_identifier=mspid, role=role).encode())


def test_role_principals(org):
    msp = org["msp"]
    peer, admin, client = (_ident(org, w) for w in ("peer", "admin", "client"))
    assert msp.satisfies_principal(peer, _role_principal(m.MSPRoleType.MEMBER))
    assert msp.satisfies_principal(peer, _role_principal(m.MSPRoleType.PEER))
    assert not msp.satisfies_principal(peer, _role_principal(m.MSPRoleType.ADMIN))
    assert msp.satisfies_principal(admin, _role_principal(m.MSPRoleType.ADMIN))
    assert msp.satisfies_principal(client, _role_principal(m.MSPRoleType.CLIENT))
    assert not msp.satisfies_principal(
        peer, _role_principal(m.MSPRoleType.MEMBER, "OtherMSP"))


def test_identity_and_ou_principals(org):
    msp = org["msp"]
    peer = _ident(org, "peer")
    ip = m.MSPPrincipal(
        principal_classification=m.PrincipalClassification.IDENTITY,
        principal=peer.serialize())
    assert msp.satisfies_principal(peer, ip)
    assert not msp.satisfies_principal(_ident(org, "client"), ip)
    oup = m.MSPPrincipal(
        principal_classification=m.PrincipalClassification.ORGANIZATION_UNIT,
        principal=m.OrganizationUnit(
            msp_identifier="Org1MSP",
            organizational_unit_identifier="peer").encode())
    assert msp.satisfies_principal(peer, oup)
    assert not msp.satisfies_principal(_ident(org, "client"), oup)


def test_sign_verify_through_identity(org):
    ident = _ident(org, "peer")
    sig = ident.sign_message(b"payload")
    assert ident.verify(b"payload", sig)
    assert not ident.verify(b"payload!", sig)
    item = ident.verify_item(b"payload", sig)
    assert item is not None and len(item.public_xy) == 64


def test_manager_routes_by_mspid(org):
    other_ca = calib.CA("ca.org2", "Org2")
    msp2 = Msp("Org2MSP", org["csp"], [other_ca.cert])
    mgr = MspManager([org["msp"], msp2])
    ident = _ident(org, "peer")
    got = mgr.deserialize_identity(ident.serialize())
    assert got.mspid == "Org1MSP"
    with pytest.raises(MSPValidationError, match="unknown MSP"):
        mgr.deserialize_identity(
            m.SerializedIdentity(mspid="NopeMSP", id_bytes=b"x").encode())


def test_cached_msp_agrees(org):
    cached = CachedMsp(org["msp"])
    ident = _ident(org, "peer")
    for _ in range(3):
        got = cached.deserialize_identity(ident.serialize())
        assert got.common_name() == "peer0.org1"
        cached.validate(got)
        assert cached.satisfies_principal(
            got, _role_principal(m.MSPRoleType.PEER))
    # negative result cached too
    evil = calib.CA("ca.evil", "Evil")
    cert, key = evil.issue("x", "Evil")
    bad = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    for _ in range(2):
        with pytest.raises(MSPValidationError):
            cached.validate(bad)


def _lookups():
    """fabric_msp_cache_lookups_total as {(cache, result): value}."""
    from fabric_mod_tpu.msp.cache import _LOOKUPS_OPTS
    from fabric_mod_tpu.observability.metrics import default_provider
    counter = default_provider().counter(_LOOKUPS_OPTS)
    return {labels: child.value for labels, child in counter._samples()}


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _lookups().items()}


def _set_clock(monkeypatch, at):
    monkeypatch.setattr(mspimpl, "now_utc", lambda: at)


def test_cache_counter_labels_and_hits(org):
    """Every lookup is counted once under (cache, result); a repeat of
    the same identity adds hits only, and no key is encoded again."""
    from fabric_mod_tpu.observability.metrics import default_provider
    cached = CachedMsp(MspManager([org["msp"]]))
    raw = _ident(org, "peer").serialize()
    peer = _role_principal(m.MSPRoleType.PEER)
    before = _lookups()
    for _ in range(2):
        ident = cached.deserialize_identity(raw)
        cached.validate(ident)
        assert cached.satisfies_principal(ident, peer)
    d = _delta(before)
    assert set(d) == {(c, r) for c in ("deserialize", "validate", "principal")
                      for r in ("hit", "miss")}
    assert {k: v for k, v in d.items() if k[1] == "miss"} == {
        ("deserialize", "miss"): 1, ("validate", "miss"): 1,
        ("principal", "miss"): 1}
    # the principal's miss asks the validate cache for the window: a hit
    assert d[("deserialize", "hit")] == 1 and d[("principal", "hit")] == 1
    assert d[("validate", "hit")] == 2
    assert cached.deserialize_identity(raw) is ident
    assert ident.serialize() is ident.serialize()
    text = default_provider().render_prometheus()
    assert ('fabric_msp_cache_lookups_total{cache="validate",result="hit"}'
            in text)


@pytest.mark.parametrize("first_to_expire", ["leaf", "intermediate"])
def test_cached_valid_is_refused_after_the_chain_expires(
        org, monkeypatch, first_to_expire):
    """A cached `valid` is served only up to the chain's EARLIEST
    not_valid_after, whichever certificate has it."""
    now = datetime.datetime.now(datetime.timezone.utc)
    soon = now + datetime.timedelta(hours=1)
    ica = calib.CA.__new__(calib.CA)
    ica.cert, ica.key = org["root"].issue(
        "ica2.org1", "Org1", is_ca=True,
        not_after=soon if first_to_expire == "intermediate" else None)
    cert, key = ica.issue(
        "short@org1", "Org1", ous=["peer"],
        not_after=soon if first_to_expire == "leaf" else None)
    cached = CachedMsp(Msp("Org1MSP", org["csp"], [org["root"].cert],
                           [ica.cert]))
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    peer = _role_principal(m.MSPRoleType.PEER)
    for _ in range(2):
        cached.validate(ident)
        assert cached.satisfies_principal(ident, peer)
    _set_clock(monkeypatch, soon - datetime.timedelta(seconds=1))
    before = _lookups()
    cached.validate(ident)
    assert cached.satisfies_principal(ident, peer)
    assert all(v == 0 for k, v in _delta(before).items() if k[1] == "miss")
    _set_clock(monkeypatch, soon + datetime.timedelta(seconds=1))
    for _ in range(2):
        with pytest.raises(MSPValidationError, match="validity"):
            cached.validate(ident)
        assert not cached.satisfies_principal(ident, peer)


def test_not_yet_valid_passes_once_valid(org, monkeypatch):
    """A refusal whose only cause is the validity window is never
    kept: the same certificate passes once the clock reaches it."""
    now = datetime.datetime.now(datetime.timezone.utc)
    later = now + datetime.timedelta(hours=1)
    cert, key = org["root"].issue("early@org1", "Org1", ous=["peer"],
                                  not_before=later)
    cached = CachedMsp(MspManager([org["msp"]]))
    ident = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    peer = _role_principal(m.MSPRoleType.PEER)
    for _ in range(2):
        with pytest.raises(MSPValidationError, match="validity"):
            cached.validate(ident)
        assert not cached.satisfies_principal(ident, peer)
    _set_clock(monkeypatch, later + datetime.timedelta(seconds=1))
    cached.validate(ident)
    assert cached.satisfies_principal(ident, peer)


def test_cached_refusals_keep_their_outcomes(org):
    """Untrusted, unknown-MSP and malformed identities: the same error
    on every call, a fresh exception each time (two threads may raise
    it at once)."""
    cached = CachedMsp(MspManager([org["msp"]]))
    evil = calib.CA("ca.evil", "Evil")
    cert, key = evil.issue("x", "Evil")
    bad = SigningIdentity("Org1MSP", cert, calib.key_pem(key), org["csp"])
    stranger = SigningIdentity("NopeMSP", cert, calib.key_pem(key),
                               org["csp"])
    for ident, what in ((bad, "no trusted issuer"), (stranger, "unknown MSP")):
        raised = []
        for _ in range(2):
            with pytest.raises(MSPValidationError, match=what) as ei:
                cached.validate(ident)
            raised.append(ei.value)
        assert raised[0] is not raised[1]
        assert not cached.satisfies_principal(
            ident, _role_principal(m.MSPRoleType.MEMBER))
    for _ in range(2):
        with pytest.raises(MSPValidationError, match="unknown MSP"):
            cached.deserialize_identity(stranger.serialize())
        with pytest.raises(Exception):
            cached.deserialize_identity(m.SerializedIdentity(
                mspid="Org1MSP", id_bytes=b"not a certificate").encode())


def test_identities_without_a_certificate_pass_through_uncached():
    """The idemix kind: pseudonymous per signature, nothing to key by."""
    class Pseudonym:
        mspid = "IdemixOrg"

    class Inner:
        calls = 0

        def deserialize_identity(self, raw):
            Inner.calls += 1
            return Pseudonym()

        def validate(self, ident):
            Inner.calls += 1

        def satisfies_principal(self, ident, principal):
            Inner.calls += 1
            return True

    cached = CachedMsp(Inner())
    for n in (1, 2):
        ident = cached.deserialize_identity(b"presentation")
        cached.validate(ident)
        assert cached.satisfies_principal(
            ident, _role_principal(m.MSPRoleType.MEMBER, "IdemixOrg"))
        assert Inner.calls == 3 * n


def test_threads_resolving_one_identity_agree(org):
    """More threads than cores, a short switch interval: every thread
    gets the same verdicts for a valid and for an untrusted identity,
    and the cache ends with one object per identity."""
    cached = CachedMsp(MspManager([org["msp"]]))
    good = _ident(org, "peer").serialize()
    evil = calib.CA("ca.evil", "Evil")
    cert, key = evil.issue("peer0.org1", "Org1", ous=["peer"])
    bad = SigningIdentity("Org1MSP", cert, calib.key_pem(key),
                          org["csp"]).serialize()
    peer = _role_principal(m.MSPRoleType.PEER)
    start = threading.Barrier(16)
    seen, errors = [], []

    def resolve():
        try:
            start.wait(timeout=30)
            for _ in range(50):
                out = []
                for raw in (good, bad):
                    ident = cached.deserialize_identity(raw)
                    try:
                        cached.validate(ident)
                        out.append("valid")
                    except MSPValidationError as e:
                        out.append(str(e))
                    out.append(cached.satisfies_principal(ident, peer))
                seen.append(tuple(out))
        except BaseException as e:          # reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=resolve) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(seen) == 16 * 50 and len(set(seen)) == 1
    assert seen[0][0] == "valid" and seen[0][1] is True
    assert "no trusted issuer" in seen[0][2] and seen[0][3] is False
    assert cached.deserialize_identity(good) is \
        cached.deserialize_identity(good)


def test_verify_item_fused_hash_emits_raw_message(org, monkeypatch):
    """Under FABRIC_MOD_TPU_FUSED_HASH the identity stages the RAW
    message (digest computed on device by the TPU provider); default
    stays the host-digest item.  Both shapes verify identically
    through a host provider (the device twin runs in bench
    --metric hashverify)."""
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier

    ident = _ident(org, "peer")
    msg = b"fused staging probe"
    sig = ident.sign_message(msg)

    monkeypatch.delenv("FABRIC_MOD_TPU_FUSED_HASH", raising=False)
    plain = ident.verify_item(msg, sig)
    assert plain.message is None and len(plain.digest) == 32

    monkeypatch.setenv("FABRIC_MOD_TPU_FUSED_HASH", "1")
    raw = ident.verify_item(msg, sig)
    assert raw.message == msg and raw.digest == b""
    assert raw.public_xy == plain.public_xy

    v = FakeBatchVerifier(org["csp"])
    assert list(v.verify_many([plain, raw])) == [True, True]
    bad = ident.verify_item(msg + b"!", sig)
    assert list(v.verify_many([bad])) == [False]
