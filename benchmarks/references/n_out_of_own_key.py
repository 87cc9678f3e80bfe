"""Rule `n_out_of_own_key`: a flat `OutOf(n, 'Org1.peer', ...,
'Org<orgs>.peer')` signature policy, chaincode-wide, over transactions
that each write one key of their own.

* The creator's signature over the envelope payload must count, else
  BAD_CREATOR_SIGNATURE.
* An endorsement counts when its signature counts AND its certificate's
  subject carries the organizational unit `peer` (the policy's
  principals are the orgs' peers: a client's or an admin's signature
  satisfies none of them) AND its org is one of the channel's
  `settings.orgs` orgs, which the configuration's `network` names
  `Org1` ... `Org<orgs>`.  The org is the fact's `org`.
* The transaction needs counting endorsements from `settings.policy_n`
  DISTINCT orgs, else ENDORSEMENT_POLICY_FAILURE.
* Every transaction writes one key nobody else touches and reads none,
  so MVCC passes all and the rule keeps nothing between transactions.

The program evaluates the policy by Fabric's greedy walk: each
principal in turn takes the first identity not yet used that satisfies
it.  Over a FLAT NOutOf whose principals are distinct orgs' peers an
identity satisfies exactly one principal, so no choice of the walk can
cost a later principal its identity, and the walk's count is the
number of distinct orgs with a counting peer endorsement: that is what
is written here.  A nested tree (`OutOf(2, AND(a, b), AND(b, c))`),
or two principals one identity can satisfy, would need the walk.

Of a fact it reads `creator`, `endorsements`, `ns`, `key`, `value`; of
`settings`, `policy_n` and `orgs`, and nothing else from anywhere.
"""
from cryptography import x509
from cryptography.x509.oid import NameOID

# Fabric's TxValidationCode (fabric-protos peer/transaction.proto)
VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10


class Rule:
    def __init__(self, settings: dict, params: dict, counts):
        self.n = int(settings["policy_n"])
        self.orgs = {"Org%d" % i
                     for i in range(1, int(settings["orgs"]) + 1)}
        self.counts = counts
        self._is_peer = {}             # certificate PEM -> bool

    def is_peer(self, cert_pem: bytes) -> bool:
        got = self._is_peer.get(cert_pem)
        if got is None:
            subject = x509.load_pem_x509_certificate(cert_pem).subject
            got = "peer" in {a.value for a in subject.get_attributes_for_oid(
                NameOID.ORGANIZATIONAL_UNIT_NAME)}
            self._is_peer[cert_pem] = got
        return got

    def judge(self, tx, block: int, index: int):
        if not self.counts(tx.creator):
            return BAD_CREATOR_SIGNATURE, {}
        orgs = {e.org for e in tx.endorsements
                if e.org in self.orgs and self.is_peer(e.cert_pem)
                and self.counts(e)}
        if len(orgs) < self.n:
            return ENDORSEMENT_POLICY_FAILURE, {}
        return VALID, {(tx.ns, tx.key): tx.value}
