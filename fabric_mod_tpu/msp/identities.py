"""X.509 identities (reference: msp/identities.go).

An Identity wraps a certificate; `verify(msg, sig)` is hash-then-
BCCSP-verify exactly like the reference (msp/identities.go:169-196),
which is what lets the TPU batch provider take over every identity
signature check in the framework.  `verify_item` exposes the same
check as a VerifyItem so callers can batch instead.
"""
from __future__ import annotations

import hashlib
from typing import Optional

try:
    from cryptography import x509
    from cryptography.hazmat.primitives import serialization
except ImportError:
    # Wheel-less container: minimal DER x509 fallback (see
    # bccsp/_x509fallback.py; bccsp/sw.py logged the downgrade).
    from fabric_mod_tpu.bccsp import _x509fallback as x509
    from fabric_mod_tpu.bccsp._ecfallback import serialization

from fabric_mod_tpu.bccsp.api import BCCSP, VerifyItem
from fabric_mod_tpu.bccsp import sw as swlib
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.utils import knobs


def fused_hash_enabled() -> bool:
    """FABRIC_MOD_TPU_FUSED_HASH=1 moves the e = H(m) of batched
    verifies onto the device: `verify_item` emits raw-MESSAGE items
    and the TPU provider hashes them in the same jitted program as the
    ECDSA verify (ops/p256.batch_verify_raw) — no host digest loop on
    the block-commit path.  Read per call on purpose (cheap), so tests
    and bench A/B can flip it without rebuilding identities."""
    return knobs.get_bool("FABRIC_MOD_TPU_FUSED_HASH")


class Identity:
    """Immutable once built: `serialize()` and the public point are
    encoded once per object, so the identity that the MSP cache hands
    back for repeated creator/endorser bytes costs a lookup nothing
    but an attribute read."""

    def __init__(self, mspid: str, cert: x509.Certificate, csp: BCCSP):
        self.mspid = mspid
        self.cert = cert
        self._csp = csp
        self._serialized: Optional[bytes] = None
        self._public_xy: Optional[bytes] = None
        self._key = csp.key_import(
            cert.public_key().public_bytes(
                serialization.Encoding.PEM,
                serialization.PublicFormat.SubjectPublicKeyInfo),
            "pem-pub")

    # -- serialization --
    def cert_pem(self) -> bytes:
        return self.cert.public_bytes(serialization.Encoding.PEM)

    def serialize(self) -> bytes:
        out = self._serialized
        if out is None:
            out = self._serialized = m.SerializedIdentity(
                mspid=self.mspid, id_bytes=self.cert_pem()).encode()
        return out

    def ski(self) -> bytes:
        return self._key.ski()

    # -- attributes --
    def expires_at(self):
        return self.cert.not_valid_after_utc

    def organizational_units(self) -> list:
        return [ou.value for ou in self.cert.subject.get_attributes_for_oid(
            x509.NameOID.ORGANIZATIONAL_UNIT_NAME)]

    def common_name(self) -> str:
        cns = self.cert.subject.get_attributes_for_oid(x509.NameOID.COMMON_NAME)
        return cns[0].value if cns else ""

    # -- crypto --
    def digest_for(self, msg: bytes) -> bytes:
        alg = "SHA256" if self._key.curve == "P256" else "SHA384"
        return self._csp.hash(msg, alg)

    def verify(self, msg: bytes, sig: bytes) -> bool:
        """Hash-then-verify (reference: msp/identities.go:169)."""
        return self._csp.verify(self._key, sig, self.digest_for(msg))

    def verify_item(self, msg: bytes, sig: bytes) -> Optional[VerifyItem]:
        """The same check as a batchable work item (P-256 only).

        Under FABRIC_MOD_TPU_FUSED_HASH the item carries the RAW
        message instead of a host-computed digest — the TPU provider
        then computes e = H(m) on device inside the verify program
        (one dispatch for hash + ladder), which removes this method
        from the per-message hashlib loop the reference's
        hash-then-verify shape implies (msp/identities.go:169)."""
        if self._key.curve != "P256":
            return None
        xy = self._public_xy
        if xy is None:
            xy = self._public_xy = self._key.public_xy()
        if fused_hash_enabled():
            return VerifyItem(b"", sig, xy, message=msg)
        return VerifyItem(self.digest_for(msg), sig, xy)


class SigningIdentity(Identity):
    def __init__(self, mspid: str, cert: x509.Certificate,
                 private_key_pem: bytes, csp: BCCSP):
        super().__init__(mspid, cert, csp)
        self._priv = csp.key_import(private_key_pem, "pem-priv")

    def sign_message(self, msg: bytes) -> bytes:
        return self._csp.sign(self._priv, self.digest_for(msg))


def deserialize_cert(id_bytes: bytes) -> x509.Certificate:
    if id_bytes.lstrip().startswith(b"-----BEGIN"):
        return x509.load_pem_x509_certificate(id_bytes)
    return x509.load_der_x509_certificate(id_bytes)


def cert_fingerprint(cert: x509.Certificate) -> bytes:
    return hashlib.sha256(cert.public_bytes(
        serialization.Encoding.DER)).digest()
