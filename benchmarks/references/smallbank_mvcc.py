"""Rule `smallbank_mvcc`: Smallbank's read-modify-write transactions
under the channel's default endorsement policy and Fabric's MVCC.

* The creator's signature over the envelope payload must count, else
  BAD_CREATOR_SIGNATURE.
* The endorsement policy is the channel default, a MAJORITY of the
  application orgs' peers (2 of 3), as `majority_own_key` has it, else
  ENDORSEMENT_POLICY_FAILURE.
* MVCC, as Fabric's validator checks a block (validator.go,
  `validateKVRead`): the rule keeps, for every key ever written,
  `(balance, version)`, the version being the place `(block, index)` of
  the valid transaction that wrote it last.  A transaction was endorsed
  on some earlier state and recorded the version of each key it read
  (`None` for a key that did not exist).  If any recorded version is
  not the rule's current version of that key, the transaction is
  MVCC_READ_CONFLICT and leaves no write: it read what a transaction
  before it in the chain, in an earlier block or earlier in its own,
  has since overwritten.
* A valid transaction's writes are RECOMPUTED here, from its
  operation, its arguments and the rule's own balances, by Smallbank's
  definitions below; nothing is copied from the envelope.  So a
  chaincode that computes another balance, a version check that lets a
  stale read pass and a commit that applies blocks in another order
  each leave a flag or a value that differs.

The operations (Alomari, Cahill, Fekete, Roehm, ICDE 2008; checking
balance `c_<id>`, savings balance `s_<id>`, whole numbers as decimal
bytes, signed):

    transact_savings(a, v)   s_a += v
    deposit_checking(a, v)   c_a += v
    send_payment(a, b, v)    c_a -= v, c_b += v
    write_check(a, v)        c_a -= v, one more unit off if v exceeds
                             s_a + c_a
    amalgamate(a, b)         c_b += s_a + c_a; s_a = c_a = 0
    balance(a)               writes nothing
    create_accounts(lo, hi, v)
                             c_i = s_i = v for lo <= i < hi (the load)

Of a fact it reads `creator`, `endorsements`, `ns`, `op`, `args`,
`reads`; of `settings`, `orgs` and `accounts`; of `params`, `accounts`.
"""

# Fabric's TxValidationCode (fabric-protos peer/transaction.proto)
VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11


class RuleError(RuntimeError):
    pass


class Rule:
    def __init__(self, settings: dict, params: dict, counts):
        self.n_orgs = int(settings["orgs"])
        if int(settings["accounts"]) != int(params["accounts"]):
            raise RuleError(
                f"the configuration has {settings['accounts']} accounts, "
                f"the traffic draws from {params['accounts']}")
        self.counts = counts
        self.held = {}              # key -> (balance, (block, index))

    def balance(self, key: str) -> int:
        return self.held[key][0]

    def writes_of(self, op: str, args) -> dict:
        """key -> balance after the operation, from the rule's own
        balances."""
        bal = self.balance
        if op == "create_accounts":
            lo, hi, v = args
            return {f"{kind}_{i}": v
                    for i in range(lo, hi) for kind in "cs"}
        if op == "transact_savings":
            a, v = args
            return {f"s_{a}": bal(f"s_{a}") + v}
        if op == "deposit_checking":
            a, v = args
            return {f"c_{a}": bal(f"c_{a}") + v}
        if op == "send_payment":
            a, b, v = args
            return {f"c_{a}": bal(f"c_{a}") - v,
                    f"c_{b}": bal(f"c_{b}") + v}
        if op == "write_check":
            a, v = args
            total = bal(f"s_{a}") + bal(f"c_{a}")
            return {f"c_{a}": bal(f"c_{a}") - v - (1 if v > total else 0)}
        if op == "amalgamate":
            a, b = args
            total = bal(f"s_{a}") + bal(f"c_{a}")
            return {f"s_{a}": 0, f"c_{a}": 0,
                    f"c_{b}": bal(f"c_{b}") + total}
        if op == "balance":
            return {}
        raise RuleError(f"no Smallbank operation {op!r}")

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
