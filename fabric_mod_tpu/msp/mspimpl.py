"""The X.509 MSP: deserialize, validate, classify, match principals.

(reference: msp/mspimpl.go, msp/mspimplvalidate.go, msp/mspimplsetup.go)

Validation builds the issuer chain by subject lookup against the MSP's
root/intermediate CAs and checks each link's signature, validity
window, CA flag, and (for leaves) revocation — the same checks the
reference performs with Go's x509 machinery, done explicitly here so
the trust model is visible and auditable.  Role classification uses
NodeOUs (OU=client/peer/admin/orderer) like reference v1.4.3+
configs, with an explicit admin-cert list as fallback.
"""
from __future__ import annotations

import datetime
from typing import Dict, List, Optional, Sequence, Tuple

try:
    from cryptography import x509
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric import (ec,
                                                           padding as _pad)
except ImportError:
    # Wheel-less container: minimal DER x509 fallback (see
    # bccsp/_x509fallback.py; bccsp/sw.py logged the downgrade).  RSA
    # chain links cannot occur there — our CA lib only mints EC certs.
    from fabric_mod_tpu.bccsp import _x509fallback as x509
    from fabric_mod_tpu.bccsp._ecfallback import InvalidSignature, ec
    from fabric_mod_tpu.bccsp._ecfallback import _Raiser
    _pad = _Raiser("RSA padding")

from fabric_mod_tpu.bccsp.api import BCCSP
from fabric_mod_tpu.msp.identities import (
    Identity, SigningIdentity, deserialize_cert, cert_fingerprint)
from fabric_mod_tpu.protos import messages as m


class MSPValidationError(Exception):
    pass


class MSPValidityWindowError(MSPValidationError):
    """A certificate of the chain is not yet, or no longer, valid: the
    one refusal that the clock alone can undo (msp/cache.py never
    keeps it)."""


def now_utc() -> datetime.datetime:
    """The clock every validity-window check reads, here and in
    msp/cache.py."""
    return datetime.datetime.now(datetime.timezone.utc)


ValidityWindow = Tuple[datetime.datetime, datetime.datetime]


def _check_link(child: x509.Certificate, issuer: x509.Certificate) -> bool:
    """Does `issuer` sign `child`?  (EC-only chain links.)"""
    pub = issuer.public_key()
    try:
        if isinstance(pub, ec.EllipticCurvePublicKey):
            pub.verify(child.signature, child.tbs_certificate_bytes,
                       ec.ECDSA(child.signature_hash_algorithm))
        else:                            # RSA CA (not issued by our CA lib)
            pub.verify(child.signature, child.tbs_certificate_bytes,
                       _pad.PKCS1v15(), child.signature_hash_algorithm)
        return True
    except InvalidSignature:
        return False
    except Exception:
        # Attacker-supplied certs can raise far beyond InvalidSignature
        # (UnsupportedAlgorithm on unknown sig-alg OIDs, ValueError /
        # TypeError on malformed params); any failure to verify the
        # link is a non-link, never a crash on the validation path.
        return False


def _is_ca_cert(cert: x509.Certificate) -> bool:
    try:
        bc = cert.extensions.get_extension_for_class(
            x509.BasicConstraints).value
        return bool(bc.ca)
    except x509.ExtensionNotFound:
        return False
    except Exception as e:               # duplicate/malformed extensions
        raise MSPValidationError(f"malformed certificate extensions: {e}")


class NodeOUs:
    """OU-based role classification config (reference:
    msp/configbuilder.go NodeOUs)."""

    def __init__(self, enable: bool = True, client_ou: str = "client",
                 peer_ou: str = "peer", admin_ou: str = "admin",
                 orderer_ou: str = "orderer"):
        self.enable = enable
        self.client_ou, self.peer_ou = client_ou, peer_ou
        self.admin_ou, self.orderer_ou = admin_ou, orderer_ou


class Msp:
    def __init__(self, mspid: str, csp: BCCSP,
                 root_certs: Sequence[x509.Certificate],
                 intermediate_certs: Sequence[x509.Certificate] = (),
                 admin_certs: Sequence[x509.Certificate] = (),
                 revoked_serials: Sequence[int] = (),
                 crls: Sequence[x509.CertificateRevocationList] = (),
                 node_ous: Optional[NodeOUs] = None):
        self.mspid = mspid
        self._csp = csp
        self.roots = list(root_certs)
        self.intermediates = list(intermediate_certs)
        self._by_subject: Dict[bytes, List[x509.Certificate]] = {}
        for c in [*self.roots, *self.intermediates]:
            self._by_subject.setdefault(
                c.subject.public_bytes(), []).append(c)
        self._root_fps = {cert_fingerprint(c) for c in self.roots}
        self._admin_fps = {cert_fingerprint(c) for c in admin_certs}
        self._revoked = set(revoked_serials)
        # CRLs (reference: msp/mspimplvalidate.go isIdentityRevoked):
        # only CRLs verifiably signed by one of our CAs contribute, and
        # each entry revokes (issuer, serial) — serials are only unique
        # per CA, so a CRL from CA1 must not shadow CA2's serial space.
        self._crl_revoked: set = set()   # {(issuer_subject_der, serial)}
        for crl in crls:
            issuer_cands = self._by_subject.get(
                crl.issuer.public_bytes(), [])
            if not any(crl.is_signature_valid(c.public_key())
                       for c in issuer_cands):
                raise MSPValidationError(
                    "CRL not signed by a trusted CA of this MSP")
            for rc in crl:
                self._crl_revoked.add(
                    (crl.issuer.public_bytes(), rc.serial_number))
        self.node_ous = node_ous or NodeOUs()

    # -- identity lifecycle --
    def deserialize_identity(self, serialized: bytes) -> Identity:
        sid = m.SerializedIdentity.decode(serialized)
        if sid.mspid != self.mspid:
            raise MSPValidationError(
                f"identity MSP {sid.mspid!r} != {self.mspid!r}")
        cert = deserialize_cert(sid.id_bytes)
        return Identity(self.mspid, cert, self._csp)

    def validate(self, ident: Identity) -> None:
        self.validated_window(ident)

    def validated_window(self, ident: Identity) -> ValidityWindow:
        """Raise MSPValidationError unless the identity chains to our
        roots and is unexpired/unrevoked; return the interval in which
        that verdict holds under this MSP's fixed roots and revocation
        lists: the chain's latest not_valid_before and earliest
        not_valid_after.

        CA certificates are not identities (reference:
        msp/mspimpl.go:713-716 'A CA certificate cannot be used
        directly as an identity', chain length >= 2 at
        mspimpl.go:747-749): a leaf with BasicConstraints CA=true — or
        one of the trust anchors themselves — is rejected outright.
        """
        if _is_ca_cert(ident.cert):
            raise MSPValidationError(
                "a CA certificate cannot be used as an identity")
        chain = self._chain_for(ident.cert)
        if len(chain) < 2:
            raise MSPValidationError(
                "identity chain must include at least one CA above the leaf")
        now = now_utc()
        for cert in chain:
            if now < cert.not_valid_before_utc or now > cert.not_valid_after_utc:
                raise MSPValidityWindowError(
                    f"certificate {cert.subject.rfc4514_string()!r} outside"
                    " validity window")
            # Revocation applies to the whole chain: a revoked
            # intermediate invalidates everything beneath it.
            if (cert.serial_number in self._revoked
                    or (cert.issuer.public_bytes(), cert.serial_number)
                    in self._crl_revoked):
                raise MSPValidationError("certificate revoked")
        self._check_key_usage(ident.cert)
        return (max(c.not_valid_before_utc for c in chain),
                min(c.not_valid_after_utc for c in chain))

    @staticmethod
    def _check_key_usage(cert: x509.Certificate) -> None:
        """Leaves carrying a KeyUsage extension must allow
        digitalSignature — identities exist to sign."""
        try:
            ku = cert.extensions.get_extension_for_class(x509.KeyUsage).value
        except x509.ExtensionNotFound:
            return
        except Exception as e:           # duplicate/malformed extensions
            raise MSPValidationError(
                f"malformed certificate extensions: {e}")
        if not ku.digital_signature:
            raise MSPValidationError(
                "leaf KeyUsage does not permit digitalSignature")

    def is_valid(self, ident: Identity) -> bool:
        try:
            self.validate(ident)
            return True
        except MSPValidationError:
            return False

    def _chain_for(self, cert: x509.Certificate) -> List[x509.Certificate]:
        """leaf -> ... -> root.  Raises if no path to a root exists."""
        chain = [cert]
        cur = cert
        for _ in range(10):                        # depth bound
            if cert_fingerprint(cur) in self._root_fps:
                return chain
            candidates = self._by_subject.get(
                cur.issuer.public_bytes(), [])
            issuer = next((c for c in candidates if _check_link(cur, c)), None)
            if issuer is None:
                raise MSPValidationError(
                    f"no trusted issuer for {cur.subject.rfc4514_string()!r}")
            try:
                bc = issuer.extensions.get_extension_for_class(
                    x509.BasicConstraints).value
                if not bc.ca:
                    raise MSPValidationError("issuer is not a CA")
            except x509.ExtensionNotFound:
                raise MSPValidationError("issuer lacks BasicConstraints")
            chain.append(issuer)
            cur = issuer
        raise MSPValidationError("chain too deep")

    # -- roles / principals --
    def _has_ou(self, ident: Identity, ou: str) -> bool:
        return ou in ident.organizational_units()

    def is_admin(self, ident: Identity) -> bool:
        if cert_fingerprint(ident.cert) in self._admin_fps:
            return True
        return self.node_ous.enable and self._has_ou(
            ident, self.node_ous.admin_ou)

    def satisfies_principal(self, ident: Identity,
                            principal: m.MSPPrincipal) -> bool:
        """(reference: msp/mspimpl.go SatisfiesPrincipal)"""
        cls = principal.principal_classification
        if cls == m.PrincipalClassification.ROLE:
            role = m.MSPRole.decode(principal.principal)
            if role.msp_identifier != self.mspid:
                return False
            if not self.is_valid(ident):
                return False
            r = role.role
            if r == m.MSPRoleType.MEMBER:
                return True
            if r == m.MSPRoleType.ADMIN:
                return self.is_admin(ident)
            if r == m.MSPRoleType.CLIENT:
                return self._has_ou(ident, self.node_ous.client_ou)
            if r == m.MSPRoleType.PEER:
                return self._has_ou(ident, self.node_ous.peer_ou)
            if r == m.MSPRoleType.ORDERER:
                return self._has_ou(ident, self.node_ous.orderer_ou)
            return False
        if cls == m.PrincipalClassification.IDENTITY:
            return principal.principal == ident.serialize()
        if cls == m.PrincipalClassification.ORGANIZATION_UNIT:
            ou = m.OrganizationUnit.decode(principal.principal)
            return (ou.msp_identifier == self.mspid
                    and self.is_valid(ident)
                    and self._has_ou(ident, ou.organizational_unit_identifier))
        return False

    # -- signing identity construction --
    def signing_identity(self, cert_pem: bytes,
                         key_pem: bytes) -> SigningIdentity:
        cert = deserialize_cert(cert_pem)
        return SigningIdentity(self.mspid, cert, key_pem, self._csp)


class MspManager:
    """Routes serialized identities to the right MSP by mspid
    (reference: msp/mspmgrimpl.go)."""

    def __init__(self, msps: Sequence[Msp] = ()):
        self._msps: Dict[str, Msp] = {m_.mspid: m_ for m_ in msps}

    def add(self, msp: Msp) -> None:
        self._msps[msp.mspid] = msp

    def get(self, mspid: str) -> Optional[Msp]:
        return self._msps.get(mspid)

    def msps(self) -> List[Msp]:
        return list(self._msps.values())

    def deserialize_identity(self, serialized: bytes) -> Identity:
        sid = m.SerializedIdentity.decode(serialized)
        msp = self._msps.get(sid.mspid)
        if msp is None:
            raise MSPValidationError(f"unknown MSP {sid.mspid!r}")
        return msp.deserialize_identity(serialized)

    def _msp_of(self, ident: Identity) -> Msp:
        msp = self._msps.get(ident.mspid)
        if msp is None:
            raise MSPValidationError(f"unknown MSP {ident.mspid!r}")
        return msp

    def validate(self, ident: Identity) -> None:
        self._msp_of(ident).validate(ident)

    def validated_window(self, ident: Identity) -> ValidityWindow:
        return self._msp_of(ident).validated_window(ident)

    def satisfies_principal(self, ident: Identity,
                            principal: m.MSPPrincipal) -> bool:
        msp = self._msps.get(ident.mspid)
        return msp is not None and msp.satisfies_principal(ident, principal)
