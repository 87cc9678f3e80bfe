"""The plain reference, and the comparison that decides `correct`.

The reference is the deployment's semantics written down once more,
with nothing of the program in it: it imports `cryptography` (OpenSSL)
and `hashlib` only.  From what the traffic generator made
(`TxFacts`) it decides, per transaction, the validation code a
committing peer of this deployment must record, and the world state
that must result:

* a signature counts if OpenSSL verifies it over the stated message
  with the stated certificate's key AND its `s` is in the lower half
  of the group order (Fabric accepts low-S signatures only);
* the creator's signature over the envelope payload must count, else
  BAD_CREATOR_SIGNATURE;
* the chaincode's endorsement policy is the channel default, a
  MAJORITY of the application orgs' peers: with `n_orgs` orgs a
  transaction needs counting endorsements from more than half of them
  (2 of 3), else ENDORSEMENT_POLICY_FAILURE;
* every transaction writes one key nobody else touches and reads none,
  so MVCC passes all: a valid transaction's value is in the state, an
  invalid one's key is absent.

`compare` holds what was read back from the peer's ledger (after it
was closed and opened again from disk) against that.  Every number
compared is exact: its limit is 0.
"""
import dataclasses
from typing import Dict, List, Optional

from cryptography import x509
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature)

# Fabric's TxValidationCode (fabric-protos peer/transaction.proto)
VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
NOT_VALIDATED = 254

# order of the P-256 group (FIPS 186-4, D.1.2.3)
P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551


@dataclasses.dataclass
class SignedPart:
    org: str
    cert_pem: bytes
    message: bytes
    signature: bytes


@dataclasses.dataclass
class TxFacts:
    env_bytes: bytes
    ns: str
    key: str
    value: bytes
    creator: SignedPart
    endorsements: List[SignedPart]


class Reference:
    def __init__(self, n_orgs: int = 3):
        self.n_orgs = n_orgs
        self._keys: Dict[bytes, ec.EllipticCurvePublicKey] = {}

    def _key(self, cert_pem: bytes):
        key = self._keys.get(cert_pem)
        if key is None:
            key = x509.load_pem_x509_certificate(cert_pem).public_key()
            self._keys[cert_pem] = key
        return key

    def counts(self, part: SignedPart) -> bool:
        try:
            _r, s = decode_dss_signature(part.signature)
        except ValueError:
            return False
        if s > P256_N // 2:
            return False
        try:
            self._key(part.cert_pem).verify(
                part.signature, part.message, ec.ECDSA(hashes.SHA256()))
        except InvalidSignature:
            return False
        return True

    def flag(self, tx: TxFacts) -> int:
        if not self.counts(tx.creator):
            return BAD_CREATOR_SIGNATURE
        orgs = {e.org for e in tx.endorsements if self.counts(e)}
        if 2 * len(orgs) <= self.n_orgs:
            return ENDORSEMENT_POLICY_FAILURE
        return VALID


@dataclasses.dataclass
class ReadBlock:
    """One block as read back from the peer's reopened ledger."""
    number: int
    tx_bytes: List[bytes]
    flags: bytes
    previous_hash: bytes
    header_hash: bytes


def compare(txs: List[TxFacts], block_txs: int, acked: List[int],
            read: Dict[int, ReadBlock], state_get, n_state_keys: int,
            n_orgs: int = 3) -> Dict[str, int]:
    """`acked`: numbers of the blocks the peer acknowledged
    (`on_commit`).  `read`: what its ledger holds after a reopen.
    `state_get(ns, key) -> bytes | None`, `n_state_keys`: keys the
    state holds in the traffic's namespace.  Returns the numbers
    compared; each has the limit 0."""
    ref = Reference(n_orgs)
    out = {"blocks_unread": 0, "chain_breaks": 0, "tx_bytes_diff": 0,
           "flag_diff": 0, "flags_missing": 0, "state_diff": 0}
    n_invalid = 0
    expected_keys = 0
    prev: Optional[ReadBlock] = None
    for num in sorted(acked):
        blk = read.get(num)
        if blk is None:
            out["blocks_unread"] += 1
            prev = None
            continue
        if prev is not None and prev.number == num - 1 \
                and blk.previous_hash != prev.header_hash:
            out["chain_breaks"] += 1
        prev = blk
        mine = txs[(num - 1) * block_txs: num * block_txs]
        if len(blk.tx_bytes) != len(mine):
            out["tx_bytes_diff"] += abs(len(blk.tx_bytes) - len(mine))
        for j, tx in enumerate(mine):
            if j >= len(blk.tx_bytes) or blk.tx_bytes[j] != tx.env_bytes:
                out["tx_bytes_diff"] += 1
                continue
            want = ref.flag(tx)
            got = blk.flags[j] if j < len(blk.flags) else NOT_VALIDATED
            if got == NOT_VALIDATED:
                out["flags_missing"] += 1
            elif got != want:
                out["flag_diff"] += 1
            n_invalid += want != VALID
            expected_keys += want == VALID
            held = state_get(tx.ns, tx.key)
            if held != (tx.value if want == VALID else None):
                out["state_diff"] += 1
    # the state holds nothing but the valid transactions' keys
    if not out["blocks_unread"] and n_state_keys != expected_keys:
        out["state_diff"] += abs(n_state_keys - expected_keys)
    # a run in which no invalid transaction was due proves nothing
    # about False lanes
    out["no_invalid_tx_due"] = int(n_invalid == 0)
    return out
