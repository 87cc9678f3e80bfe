"""Rule `sbe_asset_transfer`: fabric-samples' `asset-transfer-sbe` under
key-level (state-based) endorsement and Fabric's MVCC.

Per transaction, in chain order:

* The creator's signature over the envelope payload must count, else
  BAD_CREATOR_SIGNATURE.
* Every key the transaction writes (an operation writes its asset's
  key, and CreateAsset and TransferAsset its VALIDATION_PARAMETER too)
  is judged by one policy, else ENDORSEMENT_POLICY_FAILURE:
  - where an earlier transaction of the SAME block that passed its own
    policy changed the key's policy: none can pass (Fabric's
    vpmanagerimpl.go answers ValidationParameterUpdatedError, and the
    key-level validator, validator_keylevel.go, turns it into a policy
    failure whoever endorsed).  "Passed" is the validator's verdict,
    before MVCC: a transfer that passes its policy and then conflicts
    still fails the writes after it;
  - else the key's committed policy: the owner org's peer
    (`OutOf(1, '<OwnerOrg>.peer')`) must have a counting endorsement;
  - else (an asset not yet created) the chaincode-wide policy, the
    channel's MAJORITY Endorsement: counting endorsements from more
    than half of the `orgs` orgs' peers.
* MVCC, as `smallbank_mvcc`: the rule keeps every key's version, the
  place (block, index) of the VALID transaction that wrote it last; a
  recorded read whose version is not the current one (None: the key
  did not exist) is MVCC_READ_CONFLICT and writes nothing.
* A VALID transaction's write is RECOMPUTED from its operation, its
  arguments, the creator's org and the rule's own assets, never copied
  from the envelope: the sample's document {ID, Value, Owner, OwnerOrg}
  as JSON with sorted keys and no spaces; a committed CreateAsset or
  TransferAsset moves the key's policy to the owner org.

    CreateAsset(id, value, owner)   {id, value, owner, creator's org}
    UpdateAsset(id, value)          the asset with Value = value
    TransferAsset(id, owner, org)   the asset with Owner, OwnerOrg =
                                    owner, org

So a policy chosen wrongly (the committed one where the block changed
it, or the chaincode-wide one where the key has its own), the checks in
another order and a chaincode that writes another document each leave
a flag or a value that differs.

Of a fact it reads `creator` (with its `org`), `endorsements`, `ns`,
`op`, `args`, `reads`; of `settings`, `orgs` and `assets`; of
`params`, `assets`.
"""
import json

# Fabric's TxValidationCode (fabric-protos peer/transaction.proto)
VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11


class RuleError(RuntimeError):
    pass


def document(asset_id: str, value: int, owner: str, owner_org: str) -> bytes:
    return json.dumps({"ID": asset_id, "Value": value, "Owner": owner,
                       "OwnerOrg": owner_org},
                      sort_keys=True, separators=(",", ":")).encode()


class Rule:
    def __init__(self, settings: dict, params: dict, counts):
        self.n_orgs = int(settings["orgs"])
        if int(settings["assets"]) != int(params["assets"]):
            raise RuleError(
                f"the configuration loads {settings['assets']} assets, "
                f"the traffic {params['assets']}")
        self.counts = counts
        self.assets = {}        # id -> [value, owner, owner org, version]
        self.block = None
        self.changed = set()    # keys whose policy the block changed

    def policy_holds(self, asset_id: str, orgs: set) -> bool:
        if asset_id in self.changed:
            return False
        held = self.assets.get(asset_id)
        if held is not None:
            return held[2] in orgs
        return 2 * len(orgs) > self.n_orgs

    def judge(self, tx, block: int, index: int):
        if block != self.block:
            self.block, self.changed = block, set()
        if not self.counts(tx.creator):
            return BAD_CREATOR_SIGNATURE, {}
        asset_id = tx.args[0]
        orgs = {e.org for e in tx.endorsements if self.counts(e)}
        if not self.policy_holds(asset_id, orgs):
            return ENDORSEMENT_POLICY_FAILURE, {}
        if tx.op != "UpdateAsset":
            self.changed.add(asset_id)
        for key, version in tx.reads:
            held = self.assets.get(key)
            if (held[3] if held else None) != version:
                return MVCC_READ_CONFLICT, {}
        if tx.op == "CreateAsset":
            _, value, owner = tx.args
            asset = [int(value), owner, tx.creator.org, None]
        elif tx.op == "UpdateAsset":
            asset = list(self.assets[asset_id])
            asset[0] = int(tx.args[1])
        elif tx.op == "TransferAsset":
            asset = list(self.assets[asset_id])
            asset[1:3] = tx.args[1:3]
        else:
            raise RuleError(f"no asset-transfer-sbe operation {tx.op!r}")
        asset[3] = (block, index)
        self.assets[asset_id] = asset
        return VALID, {(tx.ns, asset_id): document(asset_id, *asset[:3])}
