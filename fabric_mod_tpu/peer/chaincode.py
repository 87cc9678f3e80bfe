"""In-process chaincode runtime: contracts, stub, registry.

(reference: core/chaincode/chaincode_support.go:193 `Execute` and the
shim message protocol handler.go:180-202 HandleGetState/HandlePutState
— here the container+gRPC stream machinery collapses to a direct call:
a contract is a Python object invoked against a stub bound to a
TxSimulator.  The registry is the launch cache; external processes can
ride behind the same seam later, exactly like the reference's external
builders.)
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Protocol


class ChaincodeError(Exception):
    pass


class ChaincodeStub:
    """What a contract sees (reference: the shim's stub API surface —
    GetState/PutState/DelState/GetStateByRange over the tx simulator,
    which records the read-write set)."""

    def __init__(self, namespace: str, simulator, args: List[bytes],
                 txid: str, channel_id: str,
                 transient: Optional[Dict[str, bytes]] = None,
                 creator: bytes = b""):
        self.namespace = namespace
        self._sim = simulator
        self.args = args
        self.txid = txid
        self.channel_id = channel_id
        # side-channel inputs; never part of the ordered tx
        # (reference: the shim's GetTransient)
        self.transient = dict(transient or {})
        # serialized creator identity (reference: shim GetCreator)
        self.creator = creator
        # at most one event per tx (reference: shim SetEvent —
        # handler.go overwrites on repeat calls)
        self.event = None               # (name, payload) | None

    def set_event(self, name: str, payload: bytes = b"") -> None:
        """Attach a chaincode event to this tx's action; delivered to
        event listeners on commit (payload stripped on the filtered
        stream)."""
        if not name:
            raise ValueError("event name must be non-empty")
        self.event = (name, payload)

    def creator_mspid(self) -> str:
        """MSP id of the proposal creator ('' when unavailable)."""
        from fabric_mod_tpu.protos import messages as _m
        try:
            return _m.SerializedIdentity.decode(self.creator).mspid
        except Exception:
            return ""

    def get_state(self, key: str) -> Optional[bytes]:
        return self._sim.get_state(self.namespace, key)

    def put_state(self, key: str, value: bytes) -> None:
        self._sim.set_state(self.namespace, key, value)

    def del_state(self, key: str) -> None:
        self._sim.delete_state(self.namespace, key)

    def get_query_result(self, query):
        """Rich JSON-selector query (reference: the shim's
        GetQueryResult; handler.go HandleGetQueryResult).  Returns
        ([(key, doc)], bookmark)."""
        return self._sim.execute_query(self.namespace, query)

    def get_state_range(self, start: str, end: str):
        return self._sim.get_state_range(self.namespace, start, end)

    def set_state_metadata(self, key: str, name: str, value: bytes) -> None:
        """(reference: shim PutStateMetadata — e.g. key-level
        endorsement via the VALIDATION_PARAMETER entry)"""
        self._sim.set_state_metadata(self.namespace, key, name, value)

    # -- private data (reference: shim PutPrivateData/GetPrivateData) --
    def put_private_data(self, collection: str, key: str,
                         value: bytes) -> None:
        self._sim.set_private_data(self.namespace, collection, key, value)

    def get_private_data(self, collection: str, key: str):
        return self._sim.get_private_data(self.namespace, collection, key)

    def del_private_data(self, collection: str, key: str) -> None:
        self._sim.delete_private_data(self.namespace, collection, key)


class Contract(Protocol):
    def invoke(self, stub: ChaincodeStub) -> bytes: ...


class ChaincodeRegistry:
    """name -> contract (reference: the launch registry + system
    chaincode table, core/scc/scc.go)."""

    def __init__(self):
        self._contracts: Dict[str, Contract] = {}
        self._resolver: Optional[Callable[[str], Optional[Contract]]] = None

    def register(self, name: str, contract: Contract) -> None:
        self._contracts[name] = contract

    def set_resolver(self, resolver) -> None:
        """Miss handler (reference: the Launch-on-first-use path of
        chaincode_support.go:93 — the ChaincodeLauncher plugs in
        here).  A non-None result is cached; None is NOT, so a
        chaincode installed later becomes resolvable — misses must
        therefore be cheap (the launcher's miss is one listdir)."""
        self._resolver = resolver

    def get(self, name: str) -> Optional[Contract]:
        cc = self._contracts.get(name)
        if cc is None and self._resolver is not None:
            cc = self._resolver(name)
            if cc is not None:
                self._contracts[name] = cc
        return cc

    def execute(self, name: str, stub: ChaincodeStub) -> bytes:
        cc = self.get(name)
        if cc is None:
            raise ChaincodeError(f"chaincode {name!r} not installed")
        return cc.invoke(stub)


class FuncContract:
    """Adapter: plain function(stub) -> bytes as a contract."""

    def __init__(self, fn: Callable[[ChaincodeStub], bytes]):
        self._fn = fn

    def invoke(self, stub: ChaincodeStub) -> bytes:
        return self._fn(stub)


class KvContract:
    """The classic example contract: args [op, key, value?] with
    put/get/del — enough to drive the e2e pipeline and tests."""

    def invoke(self, stub: ChaincodeStub) -> bytes:
        if not stub.args:
            raise ChaincodeError("no args")
        op = stub.args[0].decode()
        if op == "put":
            stub.put_state(stub.args[1].decode(), stub.args[2])
            return b"ok"
        if op == "get":
            val = stub.get_state(stub.args[1].decode())
            return val if val is not None else b""
        if op == "del":
            stub.del_state(stub.args[1].decode())
            return b"ok"
        if op == "putev":
            # put + a chaincode event (drives the event deliver tests)
            stub.put_state(stub.args[1].decode(), stub.args[2])
            stub.set_event("kv-put", stub.args[1])
            return b"ok"
        if op == "setvp":
            # key-level endorsement override (state-based endorsement,
            # reference: integration/sbe suites)
            stub.set_state_metadata(stub.args[1].decode(),
                                    "VALIDATION_PARAMETER", stub.args[2])
            return b"ok"
        if op == "query":
            # rich query: args[1] = Mango query JSON; returns the
            # matches as a JSON array of {key, doc} (the marbles
            # queryMarblesByOwner pattern)
            import json
            results, bookmark = stub.get_query_result(stub.args[1])
            return json.dumps(
                {"results": [{"key": k, "doc": d} for k, d in results],
                 "bookmark": bookmark}).encode()
        if op == "putpvt":
            # value arrives via the transient map so it never lands in
            # the ordered tx (reference: the pvt marbles pattern)
            value = stub.transient.get("value")
            if value is None:
                raise ChaincodeError("putpvt needs transient 'value'")
            stub.put_private_data(stub.args[1].decode(),
                                  stub.args[2].decode(), value)
            return b"ok"
        if op == "getpvt":
            val = stub.get_private_data(stub.args[1].decode(),
                                        stub.args[2].decode())
            return val if val is not None else b""
        raise ChaincodeError(f"unknown op {op!r}")


class SmallbankContract:
    """Smallbank (Alomari, Cahill, Fekete, Roehm, ICDE 2008): every
    account `id` has a checking balance under key `c_<id>` and a
    savings balance under `s_<id>`, each a signed decimal integer as
    bytes.  Every operation reads through `get_state` and writes
    through `put_state`, so the simulator records the versions it saw:
    these are the read-modify-write transactions MVCC decides.

        transact_savings(a, v)   s_a += v; refused if that is negative
        deposit_checking(a, v)   c_a += v; v may not be negative
        send_payment(a, b, v)    c_a -= v, c_b += v
        write_check(a, v)        c_a -= v, and one more unit off where
                                 v exceeds s_a + c_a (the paper's
                                 overdraft penalty)
        amalgamate(a, b)         c_b += s_a + c_a, then s_a = c_a = 0
        balance(a)               returns s_a + c_a, writes nothing
        create_accounts(lo, hi, v)
                                 the loader: writes c_i = s_i = v for
                                 lo <= i < hi and reads nothing

    Departures from Blockbench's Fabric chaincode `smallbank.go` (Dinh
    et al., SIGMOD 2017; recalled, not read here): its operations are
    named `updateSaving`, `updateBalance`, `sendPayment`, `writeCheck`,
    `almagate` (sic) and `getBalance`, take string account names and
    keep the two balances under `saving_<name>` and `checking_<name>`;
    it has no loader, because it reads an ABSENT account as a default
    balance and so creates it at its first write.  Here an operation on
    an account that was never created is a `ChaincodeError`: a missing
    key is a fault of the traffic, not money.  Checking balances are
    signed, as the paper's `write_check` makes them: `send_payment`
    overdraws rather than refuses, so that an account `amalgamate` has
    emptied stays usable.
    """

    # operation -> how many whole numbers it takes
    ARITY = {"transact_savings": 2, "deposit_checking": 2,
             "send_payment": 3, "write_check": 2, "amalgamate": 2,
             "balance": 1, "create_accounts": 3}

    def invoke(self, stub: ChaincodeStub) -> bytes:
        if not stub.args:
            raise ChaincodeError("no args")
        op = stub.args[0].decode()
        if self.ARITY.get(op) != len(stub.args) - 1:
            raise ChaincodeError(
                f"no op {op!r} of {len(stub.args) - 1} arguments")
        try:
            nums = [int(a) for a in stub.args[1:]]
        except ValueError as e:
            raise ChaincodeError(f"{op}: {e}") from e
        return getattr(self, "_op_" + op)(stub, *nums)

    @staticmethod
    def _read(stub: ChaincodeStub, key: str) -> int:
        raw = stub.get_state(key)
        if raw is None:
            raise ChaincodeError(f"no account behind {key!r}")
        return int(raw)

    @staticmethod
    def _write(stub: ChaincodeStub, key: str, balance: int) -> None:
        stub.put_state(key, b"%d" % balance)

    def _op_transact_savings(self, stub, a: int, v: int) -> bytes:
        savings = self._read(stub, f"s_{a}") + v
        if savings < 0:
            raise ChaincodeError(f"savings of {a} would be {savings}")
        self._write(stub, f"s_{a}", savings)
        return b"ok"

    def _op_deposit_checking(self, stub, a: int, v: int) -> bytes:
        if v < 0:
            raise ChaincodeError(f"a deposit of {v}")
        self._write(stub, f"c_{a}", self._read(stub, f"c_{a}") + v)
        return b"ok"

    def _op_send_payment(self, stub, a: int, b: int, v: int) -> bytes:
        if a == b or v < 0:
            raise ChaincodeError(f"a payment of {v} from {a} to {b}")
        from_a = self._read(stub, f"c_{a}")
        to_b = self._read(stub, f"c_{b}")
        self._write(stub, f"c_{a}", from_a - v)
        self._write(stub, f"c_{b}", to_b + v)
        return b"ok"

    def _op_write_check(self, stub, a: int, v: int) -> bytes:
        savings = self._read(stub, f"s_{a}")
        checking = self._read(stub, f"c_{a}")
        penalty = 1 if v > savings + checking else 0
        self._write(stub, f"c_{a}", checking - v - penalty)
        return b"ok"

    def _op_amalgamate(self, stub, a: int, b: int) -> bytes:
        if a == b:
            raise ChaincodeError(f"amalgamate {a} into itself")
        total = self._read(stub, f"s_{a}") + self._read(stub, f"c_{a}")
        to_b = self._read(stub, f"c_{b}")
        self._write(stub, f"s_{a}", 0)
        self._write(stub, f"c_{a}", 0)
        self._write(stub, f"c_{b}", to_b + total)
        return b"ok"

    def _op_balance(self, stub, a: int) -> bytes:
        return b"%d" % (self._read(stub, f"s_{a}")
                        + self._read(stub, f"c_{a}"))

    def _op_create_accounts(self, stub, lo: int, hi: int, v: int) -> bytes:
        for i in range(lo, hi):
            self._write(stub, f"c_{i}", v)
            self._write(stub, f"s_{i}", v)
        return b"ok"


class HotAccountsContract:
    """The custom workload of the Fabric++ paper (Sharma, Schuhknecht,
    Agrawal, Dittrich, SIGMOD 2019, arXiv:1810.13177): N accounts with
    one balance each, and one kind of transaction, which reads the
    balances of RW accounts and writes the balances of RW accounts.
    Account `id` keeps its balance under key `a_<id>`, a whole number
    as decimal bytes.

        move(r_1..r_8, w_1..w_8, v)
                reads a_<r_i> through `get_state` in argument order,
                t = the sum read, then writes
                a_<w_j> = (t + v + j) mod 1,000,000,007 for j = 1..8
                in argument order.  A written account that was not
                read is a blind write: it records no version
        create_accounts(lo, hi, v)
                the loader: writes a_<i> = v for lo <= i < hi and
                reads nothing

    The paper's (recalled, not read here): how many balances a
    transaction reads and how many it writes (RW = 8 of each, the
    point its legends carry), and that the two sets are drawn apart,
    each from a small hot set with a probability of its own, so that
    most writes are blind and most reads are of accounts the
    transaction does not write.  This repo's: the value written.  The
    paper's chaincode is not recalled, and Fabric's validation never
    looks at a value; what is written here depends on every balance
    read, on the amount and on the place of the write, so that a
    reference which recomputes it notices a read served from the wrong
    state and two writes exchanged.  An account that was never created
    is a `ChaincodeError` where it is read, as in `SmallbankContract`
    (a blind write looks at nothing, so it cannot tell), and so is an
    account named twice among the reads or twice among the writes: the
    source draws each set without repeats.
    """

    RW = 8
    MODULUS = 1_000_000_007
    # operation -> how many whole numbers it takes
    ARITY = {"move": 2 * RW + 1, "create_accounts": 3}

    # the same entry: the arity check against `self.ARITY`, whole
    # numbers, then `_op_<name>`
    invoke = SmallbankContract.invoke

    def _op_move(self, stub, *nums: int) -> bytes:
        reads, writes, v = nums[:self.RW], nums[self.RW:-1], nums[-1]
        for accounts in (reads, writes):
            if len(set(accounts)) != self.RW:
                raise ChaincodeError(f"move: an account twice in {accounts}")
        total = 0
        for a in reads:
            raw = stub.get_state(f"a_{a}")
            if raw is None:
                raise ChaincodeError(f"no account behind 'a_{a}'")
            total += int(raw)
        for j, a in enumerate(writes, 1):
            stub.put_state(f"a_{a}", b"%d" % ((total + v + j) % self.MODULUS))
        return b"ok"

    def _op_create_accounts(self, stub, lo: int, hi: int, v: int) -> bytes:
        for i in range(lo, hi):
            stub.put_state(f"a_{i}", b"%d" % v)
        return b"ok"


class SbeAssetContract:
    """fabric-samples' `asset-transfer-sbe` chaincode (state-based
    endorsement; recalled, not read here).  An asset is the JSON
    document {ID, Value, Owner, OwnerOrg} under its ID as the key, and
    the key carries a key-level endorsement policy that the peer of the
    org owning the asset alone satisfies (the sample's
    `AddOrgs(RoleTypePeer, ownerOrg)`: `OutOf(1, '<OwnerOrg>.peer')`),
    set through the key's VALIDATION_PARAMETER.

        CreateAsset(id, value, owner)
                refused where the asset exists (the read records that
                the key was absent); OwnerOrg is the creator's MSP and
                the key's policy becomes that org's peer.  The key has
                no policy yet, so the chaincode-wide policy judges it
        UpdateAsset(id, value)
                reads the asset and writes it back with the new value.
                There is no ownership check: the key's policy is what
                stops a non-owner
        TransferAsset(id, newOwner, newOwnerOrg)
                reads the asset, writes it with the new owner and org
                and moves the key's policy to the new org's peer
        ReadAsset(id)
                returns the document; writes nothing

    `Value` is a whole number, as the sample's.  Departures: the
    document is written with sorted keys and no spaces (the sample's
    JSON.stringify keeps its fields' order), and the policy is written
    in the form this peer's key-level validation reads, an
    ApplicationPolicy holding the SignaturePolicyEnvelope (Fabric
    stores the bare envelope).
    """

    # operation -> how many arguments it takes
    ARITY = {"CreateAsset": 3, "UpdateAsset": 2, "TransferAsset": 3,
             "ReadAsset": 1}

    def invoke(self, stub: ChaincodeStub) -> bytes:
        if not stub.args:
            raise ChaincodeError("no args")
        op = stub.args[0].decode()
        if self.ARITY.get(op) != len(stub.args) - 1:
            raise ChaincodeError(
                f"no op {op!r} of {len(stub.args) - 1} arguments")
        return getattr(self, "_op_" + op)(
            stub, *(a.decode() for a in stub.args[1:]))

    @staticmethod
    def document(asset_id: str, value: int, owner: str,
                 owner_org: str) -> bytes:
        import json
        return json.dumps(
            {"ID": asset_id, "Value": value, "Owner": owner,
             "OwnerOrg": owner_org},
            sort_keys=True, separators=(",", ":")).encode()

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def owner_policy(org: str) -> bytes:
        """The VALIDATION_PARAMETER of an asset `org` owns (one per
        org: the policy's text is parsed once)."""
        from fabric_mod_tpu.policy import from_string
        from fabric_mod_tpu.protos import messages as m
        return m.ApplicationPolicy(signature_policy=from_string(
            f"OutOf(1, '{org}.peer')")).encode()

    @staticmethod
    def _value(raw: str) -> int:
        try:
            return int(raw)
        except ValueError as e:
            raise ChaincodeError(f"value {raw!r}: {e}") from e

    @staticmethod
    def _asset(stub: ChaincodeStub, asset_id: str) -> dict:
        import json
        raw = stub.get_state(asset_id)
        if raw is None:
            raise ChaincodeError(f"the asset {asset_id} does not exist")
        return json.loads(raw)

    def _put(self, stub: ChaincodeStub, asset: dict) -> None:
        stub.put_state(asset["ID"], self.document(
            asset["ID"], asset["Value"], asset["Owner"], asset["OwnerOrg"]))

    def _op_CreateAsset(self, stub, asset_id: str, value: str,
                        owner: str) -> bytes:
        if stub.get_state(asset_id) is not None:
            raise ChaincodeError(f"the asset {asset_id} already exists")
        org = stub.creator_mspid()
        if not org:
            raise ChaincodeError("the creator names no MSP")
        self._put(stub, {"ID": asset_id, "Value": self._value(value),
                         "Owner": owner, "OwnerOrg": org})
        stub.set_state_metadata(asset_id, "VALIDATION_PARAMETER",
                                self.owner_policy(org))
        return b"ok"

    def _op_UpdateAsset(self, stub, asset_id: str, value: str) -> bytes:
        asset = self._asset(stub, asset_id)
        asset["Value"] = self._value(value)
        self._put(stub, asset)
        return b"ok"

    def _op_TransferAsset(self, stub, asset_id: str, new_owner: str,
                          new_owner_org: str) -> bytes:
        asset = self._asset(stub, asset_id)
        asset["Owner"], asset["OwnerOrg"] = new_owner, new_owner_org
        self._put(stub, asset)
        stub.set_state_metadata(asset_id, "VALIDATION_PARAMETER",
                                self.owner_policy(new_owner_org))
        return b"ok"

    def _op_ReadAsset(self, stub, asset_id: str) -> bytes:
        raw = stub.get_state(asset_id)
        if raw is None:
            raise ChaincodeError(f"the asset {asset_id} does not exist")
        return raw
