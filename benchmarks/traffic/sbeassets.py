"""Traffic generator `sbeassets`: fabric-samples' `asset-transfer-sbe`
on a two-org channel, endorsed in rounds of one block.

The sample (recalled, not read here) keeps assets {ID, Value, Owner,
OwnerOrg} and gives every asset's key a key-level endorsement policy
that the owning org's peer alone satisfies; `CreateAsset` is judged by
the chaincode-wide policy (the channel's MAJORITY Endorsement: both
peers of two orgs), `UpdateAsset` and `TransferAsset` by the key's own
policy, so one endorsement from the owner's peer makes them valid.
The program's chaincode is `peer/chaincode.SbeAssetContract`
(namespace `sbe`, keys `asset<N>`).

The chain is made as `smallbank` makes its own, with its facts and
backlog and `backlog`'s software network; the software peer COMMITS as
the chain grows (its round machinery is copied here: the draw of a
round needs the owners the block before left):

* the load first: `assets` `CreateAsset` transactions, one asset each,
  in whole blocks, each from a client of an org drawn from the two,
  endorsed by both orgs' peers and sound.  They are warm-up blocks;
* then one round per block: draw `block_txs` operations by `mix` over
  the assets the block before left (uniformly), endorse them all on the
  software peer's state as it stands, order them and wait for its
  commit of the block they make (`stale_blocks` 0).  `UpdateAsset` and
  `TransferAsset` come from a client of the owner org and are endorsed
  by that org's peer alone; a transfer goes to another org;
  `CreateAsset` comes from either org, endorsed by both.

A round is endorsed by a few processes side by side (`Endorsing`),
each on its replica of the software peer's state, which the generator
brings up to date with the keys each committed block wrote.  They run
the program's endorser (`trusting_endorsers`) and the network's
contract class, for each org's client and peer identities issued by
the org's CA; nothing in them touches the chip.  A round endorsed in
one process took 0.73 ms a transaction on a v5e host: 127 s of the
cell's 245-256 s of set-up at 3,250 tx/s.

Injected faults, each `*_per` transactions one, at positions the seed
draws among the rounds' transactions (never the load's):

    non_owner_update_per       an UpdateAsset from a client of an org
                               that does not own the asset, endorsed by
                               that org's peer alone
    update_after_transfer_per  an UpdateAsset by the NEW owner (client
                               and peer of the org a TransferAsset
                               earlier in the same block moves the
                               asset to): in the second half of a
                               block, after a transfer of its own
    corrupt_signature_per      the last endorsement's signature with
                               its last bit flipped

All three are ENDORSEMENT_POLICY_FAILURE: the first and third under the
key's committed policy, the second under the in-block rule (an earlier
VALID transaction of the block changed the key's policy; Fabric's
key-level validator fails such a write whoever endorsed it).  Where two
operations of a block draw the same asset, the later is
MVCC_READ_CONFLICT, or ENDORSEMENT_POLICY_FAILURE where the earlier was
a transfer that passed its policy.

Read from the mix (`traffic/<mix>.json`, `params`), overridden by the
cell: `provision_tx_s` and `warm_blocks` as `backlog`; `chaincode`,
`assets` (the load; a whole number of blocks), `mix` (shares of
`create`, `update`, `transfer`), `value_max` (values are drawn from
1..value_max), `owners` (owner names are `owner<k>`, k < owners),
`stale_blocks` (0: nothing else is made) and the three `*_per`.

The seed decides every operation, asset, value, owner name and client
org, and where the faults go: every seed gives the same numbers of
transactions and blocks.  A fact (`smallbank`'s) carries the operation,
its arguments (the chaincode's strings) and the read set the endorsers
recorded, with the creator's org: what the rule `sbe_asset_transfer`
replays.  The generator keeps its own model of the codes (the
signatures it spoiled, the key-level and in-block rules, then MVCC),
holds the software peer's flags to it block by block, and returns the
counts by code (`expected_codes`) and by fault (`fault_codes`).
"""
import dataclasses
import multiprocessing
import os
import pickle
import random
import threading
import time
from typing import Optional

from benchmarks.reference import SignedPart
from benchmarks.traffic.backlog import (
    TrafficError, blocks_needed, trusting_endorsers)
from benchmarks.traffic.smallbank import (
    ENDORSEMENT_POLICY_FAILURE, MVCC_READ_CONFLICT, VALID,
    SmallbankBacklog as RoundsBacklog, SmallbankTx as RoundTx)

OPS = ("create", "update", "transfer")
FAULTS = ("non_owner_update", "update_after_transfer", "corrupt_signature")


@dataclasses.dataclass
class SbeBacklog(RoundsBacklog):
    # fault -> {code: transactions}
    fault_codes: dict = None


@dataclasses.dataclass
class Op:
    """One planned transaction."""
    name: str                   # the chaincode function
    asset: str
    args: tuple                 # the function's arguments
    client: str                 # the org of the client that sends it
    endorsers: tuple            # the orgs whose peers endorse it
    new_org: Optional[str] = None   # a transfer's
    fault: Optional[str] = None


def fault_places(rng: random.Random, params: dict, block_txs: int,
                 n_rounds: int) -> dict:
    """place among the rounds' transactions -> fault.  An
    update-after-transfer lies in the second half of its block, so that
    a transfer can stand before it."""
    n = n_rounds * block_txs
    counts = {f: max(1, n // int(params[f + "_per"])) for f in FAULTS}
    late = [p for p in range(n) if p % block_txs >= block_txs // 2]
    out = dict.fromkeys(rng.sample(late, counts["update_after_transfer"]),
                        "update_after_transfer")
    rest = rng.sample([p for p in range(n) if p not in out],
                      counts["non_owner_update"]
                      + counts["corrupt_signature"])
    for i, place in enumerate(rest):
        out[place] = ("non_owner_update" if i < counts["non_owner_update"]
                      else "corrupt_signature")
    return out


def draw_round(rng: random.Random, params: dict, block_txs: int,
               orgs: list, live: list, owner_of: dict, faults: dict,
               next_id: int) -> tuple:
    """One block of operations over the assets `live` as `owner_of`
    says they are owned: (ops, the next asset number).  `faults` maps
    a slot of the block to its fault; a faulted slot other than a
    spoiled signature is an update."""
    mix = params["mix"]
    if set(mix) != set(OPS) or abs(sum(mix.values()) - 1.0) > 1e-9:
        raise TrafficError(f"the mix is {mix}")
    value_max, owners = int(params["value_max"]), int(params["owners"])

    def value() -> str:
        return str(rng.randint(1, value_max))

    def owner() -> str:
        return f"owner{rng.randrange(owners)}"

    kinds = rng.choices(OPS, weights=[mix[o] for o in OPS], k=block_txs)
    for slot, fault in faults.items():
        if fault != "corrupt_signature":
            kinds[slot] = "update"
    for slot in sorted(s for s, f in faults.items()
                       if f == "update_after_transfer"):
        if not any(kinds[p] == "transfer" and p not in faults
                   for p in range(slot)):
            kinds[rng.choice([p for p in range(slot)
                              if p not in faults])] = "transfer"
    ops = []
    for slot, kind in enumerate(kinds):
        fault = faults.get(slot)
        if kind == "create":
            asset = f"asset{next_id}"
            next_id += 1
            ops.append(Op("CreateAsset", asset, (asset, value(), owner()),
                          rng.choice(orgs), tuple(orgs), fault=fault))
        elif fault == "update_after_transfer":
            moved = rng.choice([op for op in ops
                                if op.name == "TransferAsset"
                                and op.fault is None])
            ops.append(Op("UpdateAsset", moved.asset,
                          (moved.asset, value()), moved.new_org,
                          (moved.new_org,), fault=fault))
        else:
            asset = rng.choice(live)
            org = owner_of[asset]
            others = [o for o in orgs if o != org]
            if kind == "update":
                if fault == "non_owner_update":
                    org = rng.choice(others)
                ops.append(Op("UpdateAsset", asset, (asset, value()), org,
                              (org,), fault=fault))
            else:
                new_org = rng.choice(others)
                ops.append(Op("TransferAsset", asset,
                              (asset, owner(), new_org), org, (org,),
                              new_org=new_org, fault=fault))
    return ops, next_id


def expected_block(ops: list, orgs: list, owner_of: dict) -> list:
    """The codes of one block's operations, by the generator's own
    model: a spoiled signature fails; a write to an asset whose policy
    an earlier transaction of the block changed, having passed its own
    policy, fails; else the committed owner's peer must endorse (a new
    asset: a MAJORITY of the orgs' peers); then a read of an asset an
    earlier VALID transaction of the block wrote is stale."""
    changed, written, codes = set(), set(), []
    for op in ops:
        if op.fault == "corrupt_signature" or op.asset in changed:
            codes.append(ENDORSEMENT_POLICY_FAILURE)
            continue
        owner = owner_of.get(op.asset)
        if (owner not in op.endorsers if owner is not None
                else 2 * len(set(op.endorsers)) <= len(orgs)):
            codes.append(ENDORSEMENT_POLICY_FAILURE)
            continue
        if op.name != "UpdateAsset":
            changed.add(op.asset)
        if op.asset in written:
            codes.append(MVCC_READ_CONFLICT)
            continue
        written.add(op.asset)
        codes.append(VALID)
    return codes


class _Replica:
    """What an endorser reads of its channel: the id, and a simulator
    over a copy of the software peer's state."""

    def __init__(self, channel_id: str):
        from fabric_mod_tpu.ledger.statedb import VersionedDB
        self.channel_id = channel_id
        self.ledger = self
        self.state = VersionedDB()

    def new_tx_simulator(self, txid: str):
        from fabric_mod_tpu.ledger.kvledger import TxSimulator
        return TxSimulator(self.state, txid)


def _endorse_worker(conn, channel_id: str, ns: str, contract,
                    identities: dict) -> None:
    """One endorsing process.  It holds the orgs' peer and client
    identities (`identities`: (role, org) -> (certificate PEM, key
    PEM)) and a replica of the software peer's state, and answers each
    message (the state's changes since the last, then operations to
    endorse) with what `endorsed` takes, or with a `TrafficError`."""
    os.environ["JAX_PLATFORMS"] = "cpu"     # the run's process holds the chip
    import types
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.ledger.rwsetutil import (
        parse_tx_rwset, version_tuple)
    from fabric_mod_tpu.ledger.statedb import UpdateBatch
    from fabric_mod_tpu.msp.identities import (
        SigningIdentity, deserialize_cert)
    from fabric_mod_tpu.peer.chaincode import ChaincodeRegistry
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    csp = SwCSP()
    signer = {at: SigningIdentity(at[1], deserialize_cert(cert), key, csp)
              for at, (cert, key) in identities.items()}
    replica = _Replica(channel_id)
    registry = ChaincodeRegistry()
    registry.register(ns, contract())
    peers = {org: s for (role, org), s in signer.items() if role == "peer"}
    endorsers = trusting_endorsers(types.SimpleNamespace(
        channel=replica, chaincodes=registry, peer_signers=peers,
        endorsers=list(peers)))

    def endorse(op: Op) -> tuple:
        client = signer["client", op.client]
        sp, prop, _ = protoutil.create_chaincode_proposal(
            channel_id, ns,
            [op.name.encode()] + [a.encode() for a in op.args], client)
        responses = [endorsers[o].process_proposal(sp)
                     for o in op.endorsers]
        for r in responses:
            if r.response.status != 200:
                raise TrafficError(
                    f"{op.name}{op.args} was refused at endorsement: "
                    f"{r.response.message}")
        if op.fault == "corrupt_signature":
            sig = responses[-1].endorsement.signature
            responses[-1] = dataclasses.replace(
                responses[-1], endorsement=dataclasses.replace(
                    responses[-1].endorsement,
                    signature=sig[:-1] + bytes([sig[-1] ^ 1])))
        env = protoutil.create_tx_from_responses(prop, responses, client)
        action = m.ChaincodeAction.decode(m.ProposalResponsePayload.decode(
            responses[0].payload).extension)
        reads = []
        for rw_ns, kv in parse_tx_rwset(
                m.TxReadWriteSet.decode(action.results)):
            if rw_ns != ns:
                raise TrafficError(f"{op.name}{op.args} touched {rw_ns!r}")
            reads += [(r.key, version_tuple(r.version)) for r in kv.reads]
        return (env.encode(), env.payload, env.signature,
                [(r.payload + r.endorsement.endorser,
                  r.endorsement.signature) for r in responses], reads)

    while True:
        msg = pickle.loads(conn.recv_bytes())
        if msg is None:
            return
        changes, number, ops = msg
        try:
            batch = UpdateBatch()
            for key, value, version, metadata in pickle.loads(changes):
                batch.put(ns, key, value, version)
                if metadata:
                    batch.put_metadata(ns, key, metadata, version)
            replica.state.apply_updates(batch, number)
            out = [endorse(op) for op in ops]
        except Exception as e:               # raised in the generator
            out = TrafficError(f"endorsing process: {e!r}")
        conn.send_bytes(pickle.dumps(out))


class Endorsing:
    """Processes that endorse a round's operations side by side: each
    takes a contiguous share of the round, on its replica of the
    software peer's state, which the generator brings up to date with
    each committed block's changes before the next round.  The bytes
    are the program's endorser's (`trusting_endorsers`); only the
    interpreter they run in differs."""

    def __init__(self, n: int, channel_id: str, ns: str, contract,
                 identities: dict):
        ctx = multiprocessing.get_context("spawn")
        self.conns, self.procs = [], []
        try:
            for i in range(n):
                mine, theirs = ctx.Pipe()
                proc = ctx.Process(
                    target=_endorse_worker, name=f"sbeassets-endorse-{i}",
                    args=(theirs, channel_id, ns, contract, identities),
                    daemon=True)
                proc.start()
                theirs.close()
                self.conns.append(mine)
                self.procs.append(proc)
        except BaseException:
            self.close()
            raise

    def endorse(self, changes: list, number: int, ops: list) -> list:
        """`changes`: (key, value, version, metadata) of every key the
        block `number` wrote, as the software peer committed it."""
        blob = pickle.dumps(changes)
        share = -(-len(ops) // len(self.conns))
        for i, conn in enumerate(self.conns):
            conn.send_bytes(pickle.dumps(
                (blob, number, ops[i * share:(i + 1) * share])))
        out = []
        for conn in self.conns:
            try:
                got = pickle.loads(conn.recv_bytes())
            except EOFError:
                raise TrafficError("an endorsing process died") from None
            if isinstance(got, TrafficError):
                raise got
            out += got
        return out

    def close(self) -> None:
        for conn in self.conns:
            try:
                conn.send_bytes(pickle.dumps(None))
            except OSError:
                pass
        for proc in self.procs:
            proc.join(timeout=30.0)
            if proc.is_alive():
                proc.kill()
                proc.join()
        for conn in self.conns:
            conn.close()


def endorsing_processes(block_txs: int) -> int:
    """Eight at most, a few cores left to the run's own threads, and
    none that would endorse fewer than 20 transactions of a block."""
    cores = len(os.sched_getaffinity(0))
    return max(1, min(8, cores - 2, block_txs // 20))


def provision(net, params: dict, seed: int, seconds: float,
              say) -> SbeBacklog:
    from cryptography.hazmat.primitives import serialization
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    ns = params["chaincode"]
    contract = net.chaincodes.get(ns)
    if contract is None:
        raise TrafficError(
            f"the program has no chaincode {ns!r} "
            f"(peer/scc.build_default_registry)")
    if int(params["stale_blocks"]) != 0:
        raise TrafficError("stale_blocks: only 0 is made")
    cutter = net.support.cutter.config
    block_txs = cutter.max_message_count
    assets = int(params["assets"])
    if assets % block_txs:
        raise TrafficError(
            f"{assets} assets are no whole number of {block_txs}-tx "
            f"load blocks")
    load_blocks = assets // block_txs
    n_rounds = blocks_needed(params, block_txs, seconds)
    orgs = list(net.endorsers)
    if len(orgs) < 2:
        raise TrafficError("a transfer needs a second org")
    rng = random.Random(seed)
    faults = fault_places(rng, params, block_txs, n_rounds)

    # each org's client and endorsing peer, issued by the org's CA as
    # the network's own are
    identities = {}
    for org in orgs:
        for role, cn in (("client", f"client@{org.lower()}"),
                         ("peer", f"peer0.{org.lower()}")):
            cert, key = net.cas[org].issue(cn, org, ous=[role])
            identities[role, org] = (
                cert.public_bytes(serialization.Encoding.PEM),
                calib.key_pem(key))
    pem = {at: cert for at, (cert, _key) in identities.items()}
    txs = []
    state = net.ledger.state

    def endorsed(op: Op, got: tuple) -> m.Envelope:
        env_bytes, payload, signature, parts, reads = got
        txs.append(RoundTx(
            env_bytes=env_bytes, ns=ns,
            creator=SignedPart(op.client, pem["client", op.client],
                               payload, signature),
            endorsements=[SignedPart(o, pem["peer", o], message, sig)
                          for o, (message, sig) in zip(op.endorsers, parts)],
            op=op.name, args=op.args, reads=reads))
        return m.Envelope(payload=payload, signature=signature)

    # the software peer commits as the chain grows
    committed = threading.Condition()
    tip = net.ledger.height - 1
    died = []

    def on_commit(block) -> None:
        nonlocal tip
        with committed:
            tip = block.header.number
            committed.notify_all()

    client = net.deliver_client(on_commit=on_commit)

    def pull() -> None:
        try:
            client.run(idle_timeout_s=600.0)
        except Exception as e:              # reported by the waiter
            died.append(e)
        finally:
            with committed:
                committed.notify_all()

    def cut_and_commit(envs: list, number: int) -> None:
        config_seq = net.support.sequence()
        for env in envs:
            net.support.chain.order(env, config_seq)
        with committed:
            committed.wait_for(
                lambda: tip >= number or not puller.is_alive(),
                timeout=120.0)
        if tip < number:
            raise TrafficError(
                f"the software peer has not committed block {number} "
                f"(its tip is {tip}): {died or 'no commit in 120 s'}")

    live, owner_of = [], {}
    expected = {VALID: 0, ENDORSEMENT_POLICY_FAILURE: 0,
                MVCC_READ_CONFLICT: 0}
    fault_codes = {f: dict.fromkeys(expected, 0) for f in FAULTS}
    puller = threading.Thread(target=pull, name="sbeassets-sw-peer")
    puller.start()
    endorse_s = commit_s = 0.0
    next_id = 0
    changes = []
    endorsing = None
    try:
        endorsing = Endorsing(endorsing_processes(block_txs),
                              net.channel_id, ns, type(contract), identities)
        number = tip
        for b in range(load_blocks + n_rounds):
            if b < load_blocks:
                ops = []
                for _ in range(block_txs):
                    asset = f"asset{next_id}"
                    next_id += 1
                    ops.append(Op(
                        "CreateAsset", asset,
                        (asset, str(rng.randint(1, int(params["value_max"]))),
                         f"owner{rng.randrange(int(params['owners']))}"),
                        rng.choice(orgs), tuple(orgs)))
            else:
                first = (b - load_blocks) * block_txs
                ops, next_id = draw_round(
                    rng, params, block_txs, orgs, live, owner_of,
                    {p - first: f for p, f in faults.items()
                     if first <= p < first + block_txs}, next_id)
            t0 = time.perf_counter()
            envs = [endorsed(op, got) for op, got in zip(
                ops, endorsing.endorse(changes, number, ops))]
            t1 = time.perf_counter()
            number += 1
            cut_and_commit(envs, number)
            endorse_s += t1 - t0
            commit_s += time.perf_counter() - t1
            codes = expected_block(ops, orgs, owner_of)
            flags = list(protoutil.block_txflags(
                net.ledger.get_block_by_number(number)))
            if flags != codes:
                j = next(i for i, (f, c) in enumerate(zip(flags, codes))
                         if f != c)
                raise TrafficError(
                    f"block {number}, tx {j} ({ops[j].name}"
                    f"{ops[j].args}, fault {ops[j].fault}): the software "
                    f"peer recorded {flags[j]}, the generator's model "
                    f"{codes[j]}")
            changes = []
            for op, code in zip(ops, codes):
                expected[code] += 1
                if op.fault is not None:
                    fault_codes[op.fault][code] += 1
                if code != VALID:
                    continue
                value, version = state.get_state(ns, op.asset)
                changes.append((op.asset, value, version,
                                state.get_metadata(ns, op.asset)))
                if op.name == "CreateAsset":
                    live.append(op.asset)
                    owner_of[op.asset] = op.client
                elif op.name == "TransferAsset":
                    owner_of[op.asset] = op.new_org
    finally:
        if endorsing is not None:
            endorsing.close()
        client.stop()
        puller.join(timeout=60.0)
    if puller.is_alive():
        raise TrafficError("the software peer's deliver client did not stop")

    n_blocks = load_blocks + n_rounds
    if net.support.store.height != 1 + n_blocks:
        raise TrafficError(
            f"the orderer cut {net.support.store.height - 1} data "
            f"blocks, expected {n_blocks}")
    limit = cutter.preferred_max_bytes
    sizes = [sum(len(t.env_bytes) for t in txs[start:start + block_txs])
             for start in range(0, len(txs), block_txs)]
    longest = max(len(t.env_bytes) for t in txs)
    if max(sizes) + longest > limit:
        raise TrafficError(
            f"a block holds {max(sizes)} bytes: PreferredMaxBytes {limit} "
            f"closed it, not the count")
    n_round_txs = n_rounds * block_txs
    say(f"sbeassets: {load_blocks * block_txs} CreateAsset load txs in "
        f"{load_blocks} blocks, then {n_round_txs} txs in {n_rounds} "
        f"rounds of {block_txs} over {len(live)} assets at the end; "
        f"{min(len(t.env_bytes) for t in txs)}-{longest} bytes a tx, up to "
        f"{max(sizes)} a block; expected codes {expected}; by fault "
        f"{fault_codes}; endorse {endorse_s:.2f}s "
        f"({1e3 * endorse_s / len(txs):.2f} ms/tx), order+commit "
        f"{commit_s:.2f}s ({1e3 * commit_s / n_blocks:.0f} ms/block)")
    return SbeBacklog(
        block_txs, n_blocks, load_blocks + int(params["warm_blocks"]), txs,
        endorse_s, commit_s, load_blocks=load_blocks,
        expected_codes=expected, fault_codes=fault_codes)
