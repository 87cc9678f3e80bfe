"""The plain reference, and the comparison that decides `correct`.

The reference is the deployment's semantics written down once more,
with nothing of the program in it: it imports `cryptography` (OpenSSL)
and the standard library only.  It has two parts.

What holds for every deployment is here:

* a signature counts if OpenSSL verifies it over the stated message
  with the stated certificate's key AND its `s` is in the lower half
  of the group order (Fabric accepts low-S signatures only);
* what the peer acknowledged is read back from its ledger after it
  was closed and opened again from disk: every block, each one's
  `previous_hash` the hash of the header before it, the transaction
  bytes as the orderer cut them, a validation code for every
  transaction;
* the world state is exactly what the valid transactions' writes,
  applied in chain order, leave: every value, no key missing, no key
  that should not be there.

What is the deployment's own is a rule, `references/<name>.py`, named
by the configuration (`"reference"`; `manifest.Cell.rule`): which
validation code a committing peer of this deployment must record for a
transaction, and which writes it applies.  `compare` asks the rule once
per transaction, in chain order, and holds what was read back against
its answers.  Every number compared is exact: its limit is 0.

The contract of a rule module:

    class Rule:
        def __init__(self, settings, params, counts): ...
        def judge(self, tx, block, index) -> (code, writes)

`settings` is the configuration's, `params` the cell's traffic
parameters, `counts(part) -> bool` the test of a signature above.
`judge` is called for every transaction of every acknowledged block,
in chain order (`block` is the block's number, `index` the
transaction's place in it), so a rule may keep state from one
transaction to the next (the versions an MVCC check needs).  `tx` is
what the traffic generator recorded (`TxFacts`, or the generator's own
kind of fact with at least `env_bytes`, `ns`, `creator` and
`endorsements`).  `code` is Fabric's TxValidationCode; `writes` maps
(namespace, key) to the value the peer's state must hold after this
transaction, or to None for a key it deletes: empty where the
transaction is invalid.  A rule imports `cryptography`, `hashlib` and
the standard library: nothing of the program.
"""
import dataclasses
from typing import Dict, List, Optional, Tuple

from cryptography import x509
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature)

# Fabric's TxValidationCode (fabric-protos peer/transaction.proto)
VALID = 0
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
    """What `cellrun.py` and `compare` read of a fact: `env_bytes`,
    `ns`, `creator`, `endorsements`.  `key` and `value` are between
    the generator and the rule."""
    env_bytes: bytes
    ns: str
    key: str
    value: bytes
    creator: SignedPart
    endorsements: List[SignedPart]


class Signatures:
    """Whether a signature counts, by OpenSSL and the low-S rule."""

    def __init__(self):
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


@dataclasses.dataclass
class ReadBlock:
    """One block as read back from the peer's reopened ledger."""
    number: int
    tx_bytes: List[bytes]
    flags: bytes
    previous_hash: bytes
    header_hash: bytes


def compare(rule, txs: list, block_txs: int, acked: List[int],
            read: Dict[int, ReadBlock],
            held: Dict[Tuple[str, str], bytes]) -> Dict[str, int]:
    """`rule`: the deployment's (`judge`, above).  `acked`: numbers of
    the blocks the peer acknowledged (`on_commit`).  `read`: what its
    ledger holds after a reopen.  `held`: the whole of the traffic's
    namespaces as its state holds them, (namespace, key) -> value.
    Returns the numbers compared; each has the limit 0."""
    out = {"blocks_unread": 0, "chain_breaks": 0, "tx_bytes_diff": 0,
           "flag_diff": 0, "flags_missing": 0, "state_diff": 0}
    n_invalid = 0
    expected: Dict[Tuple[str, str], bytes] = {}
    prev: Optional[ReadBlock] = None
    for num in sorted(acked):
        mine = txs[(num - 1) * block_txs: num * block_txs]
        # the rule sees every acknowledged transaction, read back or not
        due = []
        for j, tx in enumerate(mine):
            code, writes = rule.judge(tx, num, j)
            due.append(code)
            n_invalid += code != VALID
            for at, value in writes.items():
                if value is None:
                    expected.pop(at, None)
                else:
                    expected[at] = value
        blk = read.get(num)
        if blk is None:
            out["blocks_unread"] += 1
            prev = None
            continue
        if prev is not None and prev.number == num - 1 \
                and blk.previous_hash != prev.header_hash:
            out["chain_breaks"] += 1
        prev = blk
        if len(blk.tx_bytes) != len(mine):
            out["tx_bytes_diff"] += abs(len(blk.tx_bytes) - len(mine))
        for j, tx in enumerate(mine):
            if j >= len(blk.tx_bytes) or blk.tx_bytes[j] != tx.env_bytes:
                out["tx_bytes_diff"] += 1
                continue
            got = blk.flags[j] if j < len(blk.flags) else NOT_VALIDATED
            if got == NOT_VALIDATED:
                out["flags_missing"] += 1
            elif got != due[j]:
                out["flag_diff"] += 1
    # values that differ, keys that are missing, keys that should not
    # be there
    out["state_diff"] = sum(
        expected.get(at) != held.get(at) for at in set(expected) | set(held))
    # a run in which no invalid transaction was due proves nothing
    # about False lanes
    out["no_invalid_tx_due"] = int(n_invalid == 0)
    return out
