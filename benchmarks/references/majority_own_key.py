"""Rule `majority_own_key`: the channel's default endorsement policy
over transactions that each write one key of their own.

* The creator's signature over the envelope payload must count, else
  BAD_CREATOR_SIGNATURE.
* The chaincode's endorsement policy is the channel default, a
  MAJORITY of the application orgs' peers: with `settings.orgs` orgs a
  transaction needs counting endorsements from more than half of them
  (2 of 3, 3 of 5), else ENDORSEMENT_POLICY_FAILURE.
* Every transaction writes one key nobody else touches and reads none,
  so MVCC passes all and the rule keeps nothing between transactions:
  a valid transaction's value is in the state, an invalid one writes
  nothing.

Of a fact it reads `creator`, `endorsements`, `ns`, `key`, `value`.
"""

# Fabric's TxValidationCode (fabric-protos peer/transaction.proto)
VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10


class Rule:
    def __init__(self, settings: dict, params: dict, counts):
        self.n_orgs = int(settings["orgs"])
        self.counts = counts

    def judge(self, tx, block: int, index: int):
        if not self.counts(tx.creator):
            return BAD_CREATOR_SIGNATURE, {}
        orgs = {e.org for e in tx.endorsements if self.counts(e)}
        if 2 * len(orgs) <= self.n_orgs:
            return ENDORSEMENT_POLICY_FAILURE, {}
        return VALID, {(tx.ns, tx.key): tx.value}
