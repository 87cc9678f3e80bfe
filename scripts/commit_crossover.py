#!/usr/bin/env python3
"""Whether `KvLedger.commit_block` needs a row count of its own beside
`batchdecode.COLUMNAR_MIN_ROWS`: one commit of a block with the planes
stage handed over (rows read from them, the vectorized MVCC) and with
`rwsets=None` (every envelope decoded on the commit side, the serial
MVCC), block size by block size.

    python3 scripts/commit_crossover.py [--sizes 96,120,250,500]
                                        [--blocks 3] [--reps 3]
                                        [--accounts 10000]

A host measurement, no device and no network of nodes: signed endorser
transactions of two shapes (three orgs, two endorsements; envelopes
of ~2.2 KB, where the cells' carry 2.9-3.5 KB),

* `blind`: `mycc`, one 32-byte write to a key of its own, no read (the
  rows of `default500.backlog` and `thakkar4.backlog-nof`);
* `smallbank`: the six Smallbank operations at the cell's mix over
  Zipf-skewed accounts (s = 1.0), 1.81 recorded reads and ~1.5 writes a
  row, every block endorsed on the state the block before left, so that
  reads conflict inside a block as in `smallbank.backlog-zipf`
  (`--accounts` of them, two keys each, prefilled: a tenth of the
  cell's 100,000, which makes conflicts more frequent than its 37%),

staged once by a real `TxValidator` (whose `StagedBlock.rwsets` are the
planes), then committed into a fresh durable ledger per arm and pass,
the two arms taking turns, every incoming flag VALID.  The first block
of a pass is not timed.  One JSON line per shape and size: the median
commit in ms a block under either arm and the `rwset_extract`,
`mvcc_validate` and `ledger_write` spans' shares of it; then the table
that goes into the comment at the routing site.  Final flags must be
equal between the arms or the script stops.
"""
import argparse
import bisect
import itertools
import json
import os
import random
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHANNEL = "crossover"
SPANS = ("rwset_extract", "mvcc_validate", "ledger_write")
# (operation, weight, reads and writes as offsets into (c_a, s_a, c_b))
C_A, S_A, C_B = 0, 1, 2
OPS = (("transact_savings", 0.19, (S_A,), (S_A,)),
       ("deposit_checking", 0.19, (C_A,), (C_A,)),
       ("send_payment", 0.19, (C_A, C_B), (C_A, C_B)),
       ("write_check", 0.19, (C_A, S_A), (C_A,)),
       ("amalgamate", 0.19, (S_A, C_A, C_B), (S_A, C_A, C_B)),
       ("balance", 0.05, (C_A, S_A), ()))


class AllTrue:
    """A verifier that checks nothing: the flags commit is handed are
    VALID whatever it says, only `stage`'s planes are wanted."""

    def verify_many_async(self, items):
        return lambda: self.verify_many(items)

    def verify_many(self, items):
        import numpy as np
        return np.ones(len(items), bool)


def world():
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity
    from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
    csp = SwCSP()
    msps, signers = [], []
    for org in ("Org1", "Org2", "Org3"):
        ca = calib.CA(f"ca.{org.lower()}", org)
        msps.append(Msp(org, csp, [ca.cert]))
        cert, key = ca.issue(f"peer0.{org.lower()}", org, ous=["peer"])
        signers.append(SigningIdentity(org, cert, calib.key_pem(key), csp))
    return MspManager(msps), signers


def validator_of(mgr):
    from fabric_mod_tpu.peer import TxValidator, ValidationInfoProvider
    from fabric_mod_tpu.policy import ApplicationPolicyEvaluator, from_string
    from fabric_mod_tpu.protos import messages as m
    vinfo = ValidationInfoProvider(m.ApplicationPolicy(
        signature_policy=from_string(
            "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')")).encode())
    return TxValidator(CHANNEL, mgr, ApplicationPolicyEvaluator(mgr),
                       AllTrue(), vinfo)


def blind_results(rng, n_rows, n_blocks, accounts):
    """-> ([[rwset bytes a row] a block], no prefill)."""
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    blocks = []
    for num in range(n_blocks):
        rows = []
        for i in range(n_rows):
            b = RWSetBuilder()
            b.add_write("mycc", "k%d" % (num * n_rows + i),
                        bytes(rng.randrange(256) for _ in range(32)))
            rows.append(b.build().encode())
        blocks.append(rows)
    return blocks, {}


def smallbank_results(rng, n_rows, n_blocks, accounts):
    """-> ([[rwset bytes a row] a block], {key: version} to prefill).
    Versions are kept as the rule of `stale_blocks` 0 has them: a row
    reads what the block before left; a row whose read an earlier valid
    row of its block wrote is the in-block conflict and writes
    nothing."""
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    cum = list(itertools.accumulate(
        1.0 / rank for rank in range(1, accounts + 1)))
    perm = list(range(accounts))
    rng.shuffle(perm)
    op_cum = list(itertools.accumulate(w for _, w, _, _ in OPS))

    def account():
        return perm[bisect.bisect_left(cum, rng.random() * cum[-1])]

    prefill = {f"{kind}_{a}": (0, 0)
               for a in range(accounts) for kind in "cs"}
    versions = dict(prefill)
    blocks = []
    for num in range(n_blocks):
        rows, written = [], {}
        for i in range(n_rows):
            _, _, reads, writes = OPS[bisect.bisect_left(
                op_cum, rng.random() * op_cum[-1])]
            a, b_ = account(), account()
            keys = (f"c_{a}", f"s_{a}", f"c_{b_}")
            b = RWSetBuilder()
            for off in reads:
                b.add_read("smallbank", keys[off], versions[keys[off]])
            for off in writes:
                b.add_write("smallbank", keys[off],
                            b"%d" % rng.randrange(2_000_000))
            rows.append(b.build().encode())
            if not any(keys[off] in written for off in reads):
                for off in writes:
                    written[keys[off]] = (1 + num, i)
        versions.update(written)
        blocks.append(rows)
    return blocks, prefill


SHAPES = {"blind": ("mycc", blind_results),
          "smallbank": ("smallbank", smallbank_results)}


def commit_pass(root, staged, ns, prefill, planes):
    """One fresh durable ledger, `prefill` put into `ns`, every block
    committed: ([ms a block, the first left out], {span: ms a block},
    the final flags)."""
    from fabric_mod_tpu.ledger import KvLedger
    from fabric_mod_tpu.ledger.statedb import UpdateBatch
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.protos import messages as m
    led = KvLedger(root, CHANNEL)
    try:
        if prefill:
            batch = UpdateBatch()
            for key, ver in prefill.items():
                batch.put(ns, key, b"1000000", ver)
            led.state.apply_updates(batch, 0)
        led.commit_block(m.Block.decode(staged[0][0]), [])      # block 0
        ms, flags = [], []
        for raw, incoming, rwsets in staged[1:]:
            block = m.Block.decode(raw)
            if len(ms) == 1:
                tracing.recorder().reset()      # the first block's spans
            t0 = time.perf_counter()
            flags.append(list(led.commit_block(
                block, incoming, rwsets=rwsets if planes else None)))
            ms.append(1e3 * (time.perf_counter() - t0))
        totals = tracing.substage_totals()
        spans = {name: 1e3 * totals.get(name, {"secs": 0.0})["secs"]
                 / (len(ms) - 1) for name in SPANS}
        return ms[1:], spans, flags
    finally:
        led.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", default="96,120,250,500")
    ap.add_argument("--blocks", type=int, default=3,
                    help="timed blocks a pass (one more is committed first)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--accounts", type=int, default=10_000)
    ap.add_argument("--seed", type=int, default=35)
    args = ap.parse_args()
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil
    mgr, signers = world()
    validator = validator_of(mgr)
    sizes = [int(n) for n in args.sizes.split(",")]
    table = {}
    for shape, (ns, results_of) in SHAPES.items():
        for n_rows in sizes:
            rng = random.Random(args.seed)
            per_block, prefill = results_of(
                rng, n_rows, 1 + args.blocks, args.accounts)
            genesis = protoutil.new_block(0, b"", [])
            staged, prev = [(genesis.encode(), [], None)], \
                protoutil.block_header_hash(genesis.header)
            for num, rows in enumerate(per_block, start=1):
                block = protoutil.new_block(num, prev, [
                    protoutil.create_signed_tx(
                        CHANNEL, ns, results, signers[0], signers[:2])
                    for results in rows])
                prev = protoutil.block_header_hash(block.header)
                rwsets = validator.stage(block).rwsets
                if rwsets is None or rwsets.fallbacks:
                    raise SystemExit(
                        f"{shape} x {n_rows}: stage handed over no planes "
                        "or refused rows; nothing to compare")
                staged.append((block.encode(), [0] * n_rows, rwsets))
            arms = {"planes": [], "envelope": []}
            spans = {arm: [] for arm in arms}
            seen = {}
            with tempfile.TemporaryDirectory() as tmp, tracing.active():
                for rep in range(args.reps):
                    for arm in arms:
                        ms, sp, flags = commit_pass(
                            os.path.join(tmp, f"{arm}{rep}"), staged,
                            ns, prefill, arm == "planes")
                        arms[arm] += ms
                        spans[arm].append(sp)
                        seen[arm] = flags
            tracing.recorder().reset()
            if seen["planes"] != seen["envelope"]:
                raise SystemExit(f"{shape} x {n_rows}: the arms' flags differ")
            valid = sum(f.count(m.TxValidationCode.VALID)
                        for f in seen["planes"])
            line = {"shape": shape, "txs_per_block": n_rows,
                    "valid_pct": 100.0 * valid / (n_rows * len(per_block)),
                    "envelope_bytes": len(m.Block.decode(
                        staged[1][0]).data.data[0])}
            for arm, ms in arms.items():
                line[f"{arm}_ms_per_block_median"] = statistics.median(ms)
                line[f"{arm}_ms_per_block_min"] = min(ms)
                for name in SPANS:
                    line[f"{arm}_{name}_ms"] = statistics.median(
                        sp[name] for sp in spans[arm])
                table[shape, arm, n_rows] = statistics.median(ms)
            print(json.dumps(line), flush=True)
    print("\n  rows a block         " + "".join(f"{n:>8}" for n in sizes))
    for shape in SHAPES:
        for arm in ("planes", "envelope"):
            print(f"  {shape + ', ' + arm:<21}" + "".join(
                f"{table[shape, arm, n]:8.2f}" for n in sizes))
    return 0


if __name__ == "__main__":
    sys.exit(main())
