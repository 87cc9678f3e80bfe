"""Traffic generator `smallbank`: a chain of Smallbank transactions
over Zipf-skewed accounts, endorsed in rounds of one block.

Smallbank (Alomari, Cahill, Fekete, Roehm, ICDE 2008) as Blockbench
ships it for Fabric and as the Fabric++ paper (Sharma et al., SIGMOD
2019, arXiv:1810.13177) ran it: accounts with a checking and a savings
balance, five transactions that read balances and write them back,
one that only reads; accounts drawn from a Zipf distribution.  Unlike
`backlog`'s blind writes, what such a transaction is worth depends on
the state it was simulated on, so the chain cannot be endorsed in one
go against the genesis state.  It is made as a live channel makes it,
with `backlog`'s software network (its trusting endorsers, the
consenter's queue past broadcast's filter) and the software peer
COMMITTING as the chain grows:

* the load first: `create_accounts` transactions of
  `LOAD_ACCOUNTS_PER_TX` accounts each, in whole blocks, every one
  endorsed by `endorsements_per_tx` orgs and sound.  They are warm-up
  blocks: the peer under test commits them before the window opens;
* then one round per block: draw `block_txs` operations, endorse them
  all on the software peer's state as it stands, order them, and wait
  until the software peer has committed the block they make.  One
  `DeliverClient` of the software peer runs from the first round to
  the last (`e2e.Network.pump_committed` starts and stops one on every
  call and polls the chain: not for a round).  So every transaction of
  block b was simulated on the state block b - 1 left (`stale_blocks`
  0): a read is stale only where an earlier valid transaction of the
  SAME block wrote the key, the least a live channel sees.

Read from the mix (`traffic/<mix>.json`, `params`), overridden by the
cell:

    provision_tx_s, warm_blocks
                     as `backlog`: rounds = ceil(seconds x
                     provision_tx_s / block_txs) + warm_blocks; the
                     window opens at the commit of the load's blocks
                     and `warm_blocks` more
    accounts         how many accounts the load creates
    initial_balance  both balances of a new account
    zipf_s           an account of popularity rank r is drawn with
                     weight 1 / r^s; which account has which rank is a
                     permutation the seed decides
    p_write, mix     the share of the five updating operations, and
                     each operation's own share (they have to agree)
    amount_max       amounts are drawn from 1..amount_max
    stale_blocks     0 (nothing else is made)
    endorsements_per_tx, single_endorsed_per, corrupt_signature_per
                     as `backlog`; the single-endorsed and the corrupted
                     transactions are drawn among the rounds'
                     transactions, never the load's (an account that
                     was never created would fail every later
                     endorsement on it)

The seed decides the accounts' ranks, every operation with its
accounts and amount, and which transactions are made invalid: every
seed gives the same numbers of transactions and blocks.  How many
transactions conflict is the draw's (about 38 in 100 at s = 1.0 over
100,000 accounts and blocks of 500).

A fact (`SmallbankTx`) carries, beside what every fact has, the
operation, its arguments and the read set the endorsers recorded, each
key with the version read, parsed from the endorsed rwset: what the
rule `smallbank_mvcc` replays.  The generator also says, and returns as
`expected_codes`, how many transactions it expects each validation
code for, from the in-block rule alone, and holds the software peer's
own flags to that before it returns.
"""
import bisect
import dataclasses
import itertools
import random
import threading
import time

from benchmarks.reference import SignedPart
from benchmarks.traffic.backlog import (
    Backlog, TrafficError, blocks_needed, trusting_endorsers)

# accounts a load transaction creates: 20 writes add ~0.5 KB to a
# ~2.9 KB envelope, so 500 of them stay well under Fabric's 2 MB
# PreferredMaxBytes and the count rule closes the load's blocks too
LOAD_ACCOUNTS_PER_TX = 10

UPDATES = ("transact_savings", "deposit_checking", "send_payment",
           "write_check", "amalgamate")
VALID, ENDORSEMENT_POLICY_FAILURE, MVCC_READ_CONFLICT = 0, 10, 11


@dataclasses.dataclass
class SmallbankTx:
    env_bytes: bytes
    ns: str
    creator: SignedPart
    endorsements: list
    op: str
    args: tuple                 # whole numbers
    reads: list                 # (key, (block, index) | None)


@dataclasses.dataclass
class SmallbankBacklog(Backlog):
    load_blocks: int = 0
    expected_codes: dict = None


def draw_operations(rng: random.Random, params: dict, n: int) -> list:
    """`n` operations as (name, arguments)."""
    accounts = int(params["accounts"])
    mix = {op: float(share) for op, share in params["mix"].items()}
    if set(mix) != set(UPDATES) | {"balance"}:
        raise TrafficError(f"the mix names {sorted(mix)}")
    writing = sum(mix[op] for op in UPDATES)
    if abs(writing - float(params["p_write"])) > 1e-9 \
            or abs(writing + mix["balance"] - 1.0) > 1e-9:
        raise TrafficError(
            f"the mix's updates add up to {writing}, p_write is "
            f"{params['p_write']}, balance {mix['balance']}")
    by_rank = list(range(accounts))
    rng.shuffle(by_rank)
    cumulative = list(itertools.accumulate(
        (r + 1) ** -float(params["zipf_s"]) for r in range(accounts)))
    amount_max = int(params["amount_max"])

    def account() -> int:
        return by_rank[bisect.bisect_left(
            cumulative, rng.random() * cumulative[-1])]

    def other_than(a: int) -> int:
        b = account()
        while b == a:
            b = account()
        return b

    out = []
    for op in rng.choices(list(mix), weights=list(mix.values()), k=n):
        a = account()
        if op == "balance":
            args = (a,)
        elif op == "amalgamate":
            args = (a, other_than(a))
        elif op == "send_payment":
            args = (a, other_than(a), rng.randint(1, amount_max))
        else:
            args = (a, rng.randint(1, amount_max))
        out.append((op, args))
    return out


def expected_codes(blocks: list, invalid: set) -> dict:
    """How many transactions are due each validation code, by the
    in-block rule alone.  `blocks`: per block, per transaction, (place
    among the rounds' transactions or None, keys read, keys written)."""
    counts = {VALID: 0, ENDORSEMENT_POLICY_FAILURE: 0,
              MVCC_READ_CONFLICT: 0}
    for block in blocks:
        written = set()
        for place, reads, writes in block:
            if place in invalid:
                counts[ENDORSEMENT_POLICY_FAILURE] += 1
            elif not written.isdisjoint(reads):
                counts[MVCC_READ_CONFLICT] += 1
            else:
                counts[VALID] += 1
                written.update(writes)
    return counts


def provision(net, params: dict, seed: int, seconds: float,
              say) -> SmallbankBacklog:
    from cryptography.hazmat.primitives import serialization
    from fabric_mod_tpu.ledger.rwsetutil import (
        parse_tx_rwset, version_tuple)
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    ns = params["chaincode"]
    if net.chaincodes.get(ns) is None:
        raise TrafficError(
            f"the program has no chaincode {ns!r} "
            f"(peer/scc.build_default_registry)")
    if int(params["stale_blocks"]) != 0:
        raise TrafficError("stale_blocks: only 0 is made")
    cutter = net.support.cutter.config
    block_txs = cutter.max_message_count
    accounts = int(params["accounts"])
    if accounts < block_txs:
        raise TrafficError(
            f"{accounts} accounts do not fill a load block of {block_txs}")
    n_rounds = blocks_needed(params, block_txs, seconds)
    # the load in whole blocks, the accounts spread evenly over it
    load_blocks = -(-accounts // (LOAD_ACCOUNTS_PER_TX * block_txs))
    n_load = load_blocks * block_txs
    n_round_txs = n_rounds * block_txs

    rng = random.Random(seed)
    n_single = max(1, n_round_txs // int(params["single_endorsed_per"]))
    n_corrupt = max(1, n_round_txs // int(params["corrupt_signature_per"]))
    picked = rng.sample(range(n_round_txs), n_single + n_corrupt)
    single, corrupt = set(picked[:n_single]), set(picked[n_single:])
    n_endorse = int(params.get("endorsements_per_tx", 2))
    if not 2 <= n_endorse <= len(net.endorsers):
        raise TrafficError(
            f"endorsements_per_tx is {n_endorse}: the corrupted signature "
            f"is the second endorsement's, and the network has "
            f"{len(net.endorsers)} endorsing orgs")
    orgs = list(net.endorsers)[:n_endorse]
    endorsers = trusting_endorsers(net)
    # (operation, arguments, place among the rounds' transactions)
    initial = int(params["initial_balance"])
    plan = [("create_accounts", (i * accounts // n_load,
                                 (i + 1) * accounts // n_load, initial), None)
            for i in range(n_load)]
    plan += [(op, args, place) for place, (op, args) in enumerate(
        draw_operations(rng, params, n_round_txs))]

    def pem(identity) -> bytes:
        return identity.cert.public_bytes(serialization.Encoding.PEM)

    client_pem = pem(net.client)
    org_pem = {o: pem(net.peer_signers[o]) for o in orgs}
    txs, blocks = [], []            # facts; expected_codes' input

    def endorse(op: str, args: tuple, place) -> tuple:
        """One transaction: (envelope, its row of `blocks`)."""
        sp, prop, _ = protoutil.create_chaincode_proposal(
            net.channel_id, ns, [op.encode()] + [b"%d" % a for a in args],
            net.client)
        used = orgs[:1] if place in single else orgs
        responses = [endorsers[o].process_proposal(sp) for o in used]
        for r in responses:
            if r.response.status != 200:
                raise TrafficError(
                    f"{op}{args} was refused at endorsement: "
                    f"{r.response.message}")
        if place in corrupt:
            sig = responses[1].endorsement.signature
            responses[1] = dataclasses.replace(
                responses[1], endorsement=dataclasses.replace(
                    responses[1].endorsement,
                    signature=sig[:-1] + bytes([sig[-1] ^ 1])))
        env = protoutil.create_tx_from_responses(prop, responses,
                                                 net.client)
        action = m.ChaincodeAction.decode(m.ProposalResponsePayload.decode(
            responses[0].payload).extension)
        reads, writes = [], []
        for rw_ns, kv in parse_tx_rwset(
                m.TxReadWriteSet.decode(action.results)):
            if rw_ns != ns:
                raise TrafficError(f"{op}{args} touched {rw_ns!r}")
            reads += [(r.key, version_tuple(r.version)) for r in kv.reads]
            writes += [w.key for w in kv.writes]
        txs.append(SmallbankTx(
            env_bytes=env.encode(), ns=ns,
            creator=SignedPart("client", client_pem, env.payload,
                               env.signature),
            endorsements=[
                SignedPart(o, org_pem[o],
                           r.payload + r.endorsement.endorser,
                           r.endorsement.signature)
                for o, r in zip(used, responses)],
            op=op, args=args, reads=reads))
        return env, (place, [key for key, _ in reads], writes)

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

    puller = threading.Thread(target=pull, name="smallbank-sw-peer")
    puller.start()
    endorse_s = commit_s = 0.0
    try:
        number = tip
        for first in range(0, len(plan), block_txs):
            t0 = time.perf_counter()
            made = [endorse(*row) for row in plan[first:first + block_txs]]
            t1 = time.perf_counter()
            number += 1
            cut_and_commit([env for env, _ in made], number)
            blocks.append([row for _, row in made])
            endorse_s += t1 - t0
            commit_s += time.perf_counter() - t1
    finally:
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
    for start in range(0, len(txs), block_txs):
        sizes = [len(t.env_bytes) for t in txs[start:start + block_txs]]
        if sum(sizes) + max(sizes) > limit:
            raise TrafficError(
                f"block {1 + start // block_txs} holds {sum(sizes)} bytes: "
                f"PreferredMaxBytes {limit} closed it, not the count")
    expected = expected_codes(blocks, single | corrupt)
    recorded = {code: 0 for code in expected}
    for num in range(1, 1 + n_blocks):
        for flag in protoutil.block_txflags(
                net.ledger.get_block_by_number(num)):
            recorded[flag] = recorded.get(flag, 0) + 1
    if recorded != expected:
        raise TrafficError(
            f"the software peer recorded {recorded}, the in-block rule "
            f"expects {expected}: a block was endorsed on a state that "
            f"was not the one the block before it left")
    n_reads = sum(len(t.reads) for t in txs[n_load:])
    say(f"smallbank: {n_load} load txs of up to {LOAD_ACCOUNTS_PER_TX} accounts "
        f"in {load_blocks} blocks, then {n_round_txs} txs over {accounts} "
        f"accounts (Zipf s {params['zipf_s']}) in {n_rounds} rounds of "
        f"{block_txs}, up to {max(len(t.env_bytes) for t in txs)} bytes, "
        f"{n_reads / n_round_txs:.2f} reads a tx; {n_single} "
        f"single-endorsed, {n_corrupt} with a corrupted endorsement "
        f"signature; expected codes {expected} (MVCC_READ_CONFLICT "
        f"{100.0 * expected[MVCC_READ_CONFLICT] / n_round_txs:.1f}% of "
        f"the rounds' txs); endorse {endorse_s:.2f}s "
        f"({1e3 * endorse_s / len(txs):.2f} ms/tx), order+commit "
        f"{commit_s:.2f}s ({1e3 * commit_s / n_blocks:.0f} ms/block)")
    return SmallbankBacklog(
        block_txs, n_blocks, load_blocks + int(params["warm_blocks"]), txs,
        endorse_s, commit_s, load_blocks=load_blocks,
        expected_codes=expected)
