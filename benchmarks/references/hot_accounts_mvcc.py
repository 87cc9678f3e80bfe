"""Rule `hot_accounts_mvcc`: the Fabric++ paper's custom workload
(transactions that read eight balances and write eight) under the
channel's default endorsement policy and Fabric's MVCC.

* The creator's signature over the envelope payload must count, else
  BAD_CREATOR_SIGNATURE.
* The endorsement policy is the channel default, a MAJORITY of the
  application orgs' peers (2 of 3), as `majority_own_key` has it, else
  ENDORSEMENT_POLICY_FAILURE.
* MVCC, as Fabric's validator checks a block (validator.go,
  `validateKVRead`) and as `smallbank_mvcc` states it: the rule keeps,
  for every key ever written, `(balance, version)`, the version being
  the place `(block, index)` of the valid transaction that wrote it
  last.  A transaction was endorsed on some earlier state and recorded
  the version of each key it read (`None` for a key that did not
  exist).  If any recorded version is not the rule's current version
  of that key, the transaction is MVCC_READ_CONFLICT and leaves no
  write.  A write records no version: a transaction that writes a key
  an earlier one of its block wrote, without having read it, is valid
  and its value stands (a blind write).
* A valid transaction's writes are RECOMPUTED here, from its
  arguments and the rule's own balances; nothing is copied from the
  envelope.  So a chaincode that computes another balance, a version
  check that lets a stale read pass and a commit that applies blocks
  or writes in another order each leave a flag or a value that
  differs.

The operations (balance of account `id` under `a_<id>`, whole numbers
as decimal bytes):

    move(r_1..r_8, w_1..w_8, v)
                    t = a_<r_1> + ... + a_<r_8>; then for j = 1..8
                    a_<w_j> = (t + v + j) mod 1,000,000,007
    create_accounts(lo, hi, v)
                    a_<i> = v for lo <= i < hi (the load)

Of a fact it reads `creator`, `endorsements`, `ns`, `op`, `args`,
`reads`; of `settings`, `orgs`, `accounts` and `rw`; of `params`,
`accounts` and `rw`.
"""

# Fabric's TxValidationCode (fabric-protos peer/transaction.proto)
VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11

MODULUS = 1_000_000_007


class RuleError(RuntimeError):
    pass


class Rule:
    def __init__(self, settings: dict, params: dict, counts):
        self.n_orgs = int(settings["orgs"])
        for key in ("accounts", "rw"):
            if int(settings[key]) != int(params[key]):
                raise RuleError(
                    f"the configuration has {key} {settings[key]}, the "
                    f"traffic draws with {params[key]}")
        self.rw = int(settings["rw"])
        self.counts = counts
        self.held = {}              # key -> (balance, (block, index))

    def writes_of(self, op: str, args) -> dict:
        """key -> balance after the operation, from the rule's own
        balances; in the order written."""
        if op == "create_accounts":
            lo, hi, v = args
            return {f"a_{i}": v for i in range(lo, hi)}
        if op == "move":
            if len(args) != 2 * self.rw + 1:
                raise RuleError(f"move of {len(args)} arguments")
            reads, writes, v = args[:self.rw], args[self.rw:-1], args[-1]
            total = sum(self.held[f"a_{a}"][0] for a in reads)
            return {f"a_{a}": (total + v + j) % MODULUS
                    for j, a in enumerate(writes, 1)}
        raise RuleError(f"no operation {op!r}")

    def judge(self, tx, block: int, index: int):
        if not self.counts(tx.creator):
            return BAD_CREATOR_SIGNATURE, {}
        orgs = {e.org for e in tx.endorsements if self.counts(e)}
        if 2 * len(orgs) <= self.n_orgs:
            return ENDORSEMENT_POLICY_FAILURE, {}
        for key, version in tx.reads:
            current = self.held.get(key)
            if (current[1] if current else None) != version:
                return MVCC_READ_CONFLICT, {}
        writes = self.writes_of(tx.op, tx.args)
        for key, balance in writes.items():
            self.held[key] = (balance, (block, index))
        return VALID, {(tx.ns, key): b"%d" % balance
                       for key, balance in writes.items()}
