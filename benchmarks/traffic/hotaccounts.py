"""Traffic generator `hotaccounts`: the custom workload of the Fabric++
paper over a small hot set of accounts, endorsed in rounds of one
block.

The paper (Sharma, Schuhknecht, Agrawal, Dittrich, SIGMOD 2019,
arXiv:1810.13177, evaluation; recalled, not read here) drives Fabric
with N account balances and one kind of transaction, which reads RW
balances and writes RW balances.  HSS x N of the accounts are hot; each
account read is drawn from the hot set with probability HR and each
account written with probability HW, the two sets drawn apart.  Here:
`peer/chaincode.HotAccountsContract` (`move`), every set drawn without
repeats.

The chain is made as `smallbank` makes its own, and with its pieces
(the facts, the backlog, the in-block rule of `expected_codes`) and
`backlog`'s software network: the software peer COMMITS as the chain
grows,

* the load first: `create_accounts` transactions in whole blocks that
  the count rule closes, the accounts spread evenly over them (10,000
  accounts over one block of 1,024: 784 transactions of 10 accounts
  and 240 of 9), every one endorsed by `endorsements_per_tx` orgs and
  sound.  They are warm-up blocks;
* then one round per block: draw `block_txs` operations, endorse them
  all on the software peer's state as it stands, order them, wait for
  the software peer's commit of the block they make (`stale_blocks`
  0: a read is stale only where an earlier valid transaction of the
  SAME block wrote the key).

Read from the mix (`traffic/<mix>.json`, `params`), overridden by the
cell:

    provision_tx_s, warm_blocks
                     as `backlog`: rounds = ceil(seconds x
                     provision_tx_s / block_txs) + warm_blocks
    accounts         N, how many accounts the load creates
    rw               RW, accounts read and accounts written by a
                     transaction (the program's contract takes 8)
    hot_set          HSS, the hot accounts' share of N; which accounts
                     are hot is a permutation the seed decides
    hot_read, hot_write
                     HR and HW
    initial_balance, amount_max
                     every balance after the load; `v` is drawn from
                     1..amount_max
    stale_blocks     0 (nothing else is made)
    endorsements_per_tx, single_endorsed_per, corrupt_signature_per
                     as `smallbank`: the invalid transactions are drawn
                     among the rounds' transactions, never the load's

The seed decides which accounts are hot, every operation's accounts
and amount, and which transactions are made invalid: every seed gives
the same numbers of transactions, blocks, reads and writes.  How many
transactions conflict is the draw's (about 86 in 100 at RW 8, HR 0.4,
HW 0.1, HSS 0.01 over 10,000 accounts and blocks of 1,024: once a few
dozen valid transactions have written into the hundred hot accounts,
nearly every later reader of the block has read one of them).

A fact carries `op`, `args` and `reads` (key and version, parsed from
the endorsed rwset): what the rule `hot_accounts_mvcc` replays.  The
generator states how many transactions it expects each validation code
for, from the in-block rule alone, and holds the software peer's own
flags to that before it returns.
"""
import dataclasses
import random
import threading
import time

from benchmarks.reference import SignedPart
from benchmarks.traffic.backlog import (
    TrafficError, blocks_needed, trusting_endorsers)
from benchmarks.traffic.smallbank import (
    MVCC_READ_CONFLICT, SmallbankBacklog as RoundsBacklog,
    SmallbankTx as RoundTx, expected_codes)

# accounts a load transaction creates, at most (`smallbank`'s 10: ten
# writes add ~0.2 KB to a ~2.9 KB envelope)
LOAD_ACCOUNTS_PER_TX = 10


def hot_and_cold(rng: random.Random, params: dict) -> tuple:
    """(hot accounts, cold accounts): the first draws of a seed's
    stream."""
    accounts, rw = int(params["accounts"]), int(params["rw"])
    n_hot = round(float(params["hot_set"]) * accounts)
    if not rw <= n_hot <= accounts - rw:
        raise TrafficError(
            f"a hot set of {n_hot} of {accounts} accounts cannot give or "
            f"leave {rw} distinct ones")
    by_place = list(range(accounts))
    rng.shuffle(by_place)
    return by_place[:n_hot], by_place[n_hot:]


def draw_operations(rng: random.Random, params: dict, n: int) -> list:
    """`n` operations as (name, arguments): `move` with `rw` distinct
    accounts read, `rw` distinct accounts written and the amount."""
    hot, cold = hot_and_cold(rng, params)
    rw, amount_max = int(params["rw"]), int(params["amount_max"])

    def distinct(p_hot: float) -> list:
        out = []
        while len(out) < rw:
            a = rng.choice(hot if rng.random() < p_hot else cold)
            if a not in out:
                out.append(a)
        return out

    hot_read, hot_write = float(params["hot_read"]), float(params["hot_write"])
    return [("move", (*distinct(hot_read), *distinct(hot_write),
                      rng.randint(1, amount_max))) for _ in range(n)]


def provision(net, params: dict, seed: int, seconds: float,
              say) -> RoundsBacklog:
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

    n_single = max(1, n_round_txs // int(params["single_endorsed_per"]))
    n_corrupt = max(1, n_round_txs // int(params["corrupt_signature_per"]))
    # its own stream, so that the operations are `draw_operations`' of
    # this seed whatever is made invalid
    picked = random.Random(f"invalid:{seed}").sample(
        range(n_round_txs), n_single + n_corrupt)
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
        draw_operations(random.Random(seed), params, n_round_txs))]

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
        txs.append(RoundTx(
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

    puller = threading.Thread(target=pull, name="hotaccounts-sw-peer")
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
    sizes = [sum(len(t.env_bytes) for t in txs[start:start + block_txs])
             for start in range(0, len(txs), block_txs)]
    longest = max(len(t.env_bytes) for t in txs)
    if max(sizes) + longest > limit:
        raise TrafficError(
            f"a block holds {max(sizes)} bytes: PreferredMaxBytes {limit} "
            f"closed it, not the count")
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
    rounds = txs[n_load:]
    say(f"hotaccounts: {n_load} load txs of up to {LOAD_ACCOUNTS_PER_TX} "
        f"accounts in {load_blocks} block(s), then {n_round_txs} txs over "
        f"{accounts} accounts (hot set {params['hot_set']}, hot reads "
        f"{params['hot_read']}, hot writes {params['hot_write']}) in "
        f"{n_rounds} rounds of {block_txs}, up to {longest} bytes a tx and "
        f"{max(sizes)} a block, "
        f"{sum(len(t.reads) for t in rounds) / n_round_txs:.2f} reads a tx; "
        f"{n_single} single-endorsed, {n_corrupt} with a corrupted "
        f"endorsement signature; expected codes {expected} "
        f"(MVCC_READ_CONFLICT "
        f"{100.0 * expected[MVCC_READ_CONFLICT] / n_round_txs:.1f}% of the "
        f"rounds' txs); endorse {endorse_s:.2f}s "
        f"({1e3 * endorse_s / len(txs):.2f} ms/tx), order+commit "
        f"{commit_s:.2f}s ({1e3 * commit_s / n_blocks:.0f} ms/block)")
    return RoundsBacklog(
        block_txs, n_blocks, load_blocks + int(params["warm_blocks"]), txs,
        endorse_s, commit_s, load_blocks=load_blocks,
        expected_codes=expected)
