"""Traffic generator `backlog`: a chain the orderer has already cut.

A committing peer that joins a busy channel (or restarts on one) finds
blocks waiting and commits them flat out.  This generator makes that
chain with a SOFTWARE network, before the window opens: a client and
the endorsing peers of `e2e.Network` (their verifier is the software
CSP, never the device: endorsing is other peers' work) produce
endorsed transactions, broadcast ingress admits them and the solo
orderer cuts them into blocks by the count rule alone.

Two checks that other nodes make, and that took 4.6 of 5.7 ms per
transaction here (cProfile, this sandbox), are left out of set-up:
the endorsing peers do not verify the client's signature and ACL
before they simulate and sign (`TrustingEndorser`), and the envelopes
go to the consenter's queue past broadcast's ingress filter.  The
blocks are what the full path cuts, byte for byte in form; every
signature in them is made and is checked, by the peer under test and
by the reference.

Read from the mix (`traffic/<mix>.json`, `params`) and overridden by
the cell (`workloads/<cell>.json`, `params`):

    provision_tx_s   transactions provisioned per second of window
    warm_blocks      blocks the peer commits before the window opens
    endorsements_per_tx
                     how many orgs endorse a transaction, the first so
                     many of the network's (default 2); a mix sets it to
                     its policy's threshold, so that one bad signature
                     decides a flag
    single_endorsed_per, corrupt_signature_per
                     one transaction in so many carries one
                     endorsement only / a corrupted second endorsement
                     signature (its last bit flipped), so that False
                     lanes from the device decide flags in every run
    value_bytes      length of each written value

The seed decides WHICH transactions are made invalid and what the
values are; every seed gives the same number of transactions, of
blocks and of invalid transactions, at the same sizes.  Key pairs,
nonces and ECDSA signatures come from OpenSSL's generator, which takes
no seed: they differ from run to run at equal sizes.

Besides the blocks, `provision` returns what the plain reference needs
and the program never sees: for every transaction its bytes, its key
and value, and each signature with the certificate and the message it
was made over (`reference.TxFacts`).
"""
import dataclasses
import hashlib
import math
import random
import time

from benchmarks.reference import SignedPart, TxFacts


class TrafficError(RuntimeError):
    pass


def trusting_endorsers(net) -> dict:
    """The network's endorsing peers, without their checks of the
    client: simulate against the ledger, build the response, sign."""
    from fabric_mod_tpu.peer.endorser import Endorser
    from fabric_mod_tpu.protos import messages as m

    class TrustingEndorser(Endorser):
        def _pre_process(self, sp):
            prop = m.Proposal.decode(sp.proposal_bytes)
            header = m.Header.decode(prop.header)
            return (prop, m.ChannelHeader.decode(header.channel_header),
                    m.SignatureHeader.decode(header.signature_header))

    return {org: TrustingEndorser(net.channel, net.chaincodes,
                                  net.peer_signers[org])
            for org in net.endorsers}


@dataclasses.dataclass
class Backlog:
    block_txs: int
    n_blocks: int
    warm_blocks: int
    txs: list          # TxFacts, in the order submitted
    endorse_s: float
    submit_s: float


def blocks_needed(params: dict, block_txs: int, seconds: float) -> int:
    return (math.ceil(seconds * params["provision_tx_s"] / block_txs)
            + int(params["warm_blocks"]))


def value_for(seed: int, i: int, n: int) -> bytes:
    out = b""
    k = 0
    while len(out) < n:
        out += hashlib.sha256(b"%d:%d:%d" % (seed, i, k)).hexdigest().encode()
        k += 1
    return out[:n]


def provision(net, params: dict, seed: int, seconds: float, say) -> Backlog:
    from cryptography.hazmat.primitives import serialization
    from fabric_mod_tpu.protos import protoutil

    block_txs = net.support.cutter.config.max_message_count
    n_blocks = blocks_needed(params, block_txs, seconds)
    n_txs = n_blocks * block_txs
    rng = random.Random(seed)
    n_single = max(1, n_txs // int(params["single_endorsed_per"]))
    n_corrupt = max(1, n_txs // int(params["corrupt_signature_per"]))
    picked = rng.sample(range(n_txs), n_single + n_corrupt)
    single, corrupt = set(picked[:n_single]), set(picked[n_single:])
    n_endorse = int(params.get("endorsements_per_tx", 2))
    if not 2 <= n_endorse <= len(net.endorsers):
        raise TrafficError(
            f"endorsements_per_tx is {n_endorse}: the corrupted signature "
            f"is the second endorsement's, and the network has "
            f"{len(net.endorsers)} endorsing orgs")
    orgs = list(net.endorsers)[:n_endorse]
    endorsers = trusting_endorsers(net)

    def pem(identity) -> bytes:
        return identity.cert.public_bytes(serialization.Encoding.PEM)

    client_pem = pem(net.client)
    org_pem = {o: pem(net.peer_signers[o]) for o in orgs}
    ns = params.get("chaincode", "mycc")
    n_value = int(params["value_bytes"])

    t0 = time.perf_counter()
    envs, txs = [], []
    for i in range(n_txs):
        key, value = b"k%d" % i, value_for(seed, i, n_value)
        sp, prop, _ = protoutil.create_chaincode_proposal(
            net.channel_id, ns, [b"put", key, value], net.client)
        used = orgs[:1] if i in single else orgs
        responses = [endorsers[o].process_proposal(sp) for o in used]
        if i in corrupt:
            sig = responses[1].endorsement.signature
            responses[1] = dataclasses.replace(
                responses[1], endorsement=dataclasses.replace(
                    responses[1].endorsement,
                    signature=sig[:-1] + bytes([sig[-1] ^ 1])))
        env = protoutil.create_tx_from_responses(prop, responses,
                                                 net.client)
        envs.append(env)
        txs.append(TxFacts(
            env_bytes=env.encode(), ns=ns, key=key.decode(), value=value,
            creator=SignedPart("client", client_pem, env.payload,
                               env.signature),
            endorsements=[
                SignedPart(o, org_pem[o],
                           r.payload + r.endorsement.endorser,
                           r.endorsement.signature)
                for o, r in zip(used, responses)]))
    endorse_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    config_seq = net.support.sequence()
    for env in envs:
        net.support.chain.order(env, config_seq)
    deadline = time.monotonic() + 120.0
    while net.support.store.height < 1 + n_blocks:
        if time.monotonic() > deadline:
            raise TrafficError(
                f"the orderer cut {net.support.store.height - 1} of "
                f"{n_blocks} blocks in 120 s")
        time.sleep(0.005)
    submit_s = time.perf_counter() - t0
    if net.support.store.height != 1 + n_blocks:
        raise TrafficError(
            f"the orderer cut {net.support.store.height - 1} data "
            f"blocks, expected {n_blocks}")
    say(f"backlog: {n_txs} txs of up to {max(len(t.env_bytes) for t in txs[:64])} bytes in "
        f"{n_blocks} blocks of {block_txs}; {n_single} single-endorsed, "
        f"{n_corrupt} with a corrupted endorsement signature; endorse "
        f"{endorse_s:.2f}s ({1e3 * endorse_s / n_txs:.2f} ms/tx), "
        f"order+cut {submit_s:.2f}s")
    return Backlog(block_txs, n_blocks, int(params["warm_blocks"]), txs,
                   endorse_s, submit_s)
