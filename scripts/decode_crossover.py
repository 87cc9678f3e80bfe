#!/usr/bin/env python3
"""Where `batchdecode.COLUMNAR_MIN_ROWS` comes from: `TxValidator.stage`
timed under either decode path, block size by block size.

    python3 scripts/decode_crossover.py [--sizes 10,25,50,75,100,500]
                                        [--txs 400] [--reps 5]

A host measurement, no device: an `e2e.Network` per block size, the
benchmark's own `backlog` generator for the blocks (three orgs, two
endorsements a transaction, ~2.9 KB envelopes), the channel's own
`TxValidator`, and a verifier that marshals the batch as the device
verifier's dispatch does and returns.  Each size stages the same blocks
with the constant forced below and above the block's row count, the
two paths taking turns, and prints one JSON line: the median and the
fastest pass of either path, in ms a block.  The constant goes where
"columnar" starts to win, rounded up.
"""
import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class MarshalOnly:
    """The host half of a device dispatch, and every verdict True."""

    def verify_many_async(self, items):
        import numpy as np
        from fabric_mod_tpu.bccsp import tpu
        tpu.marshal_items(
            items, next(b for b in tpu.BUCKETS if b >= len(items)))
        return lambda: np.ones(len(items), bool)

    def verify_many(self, items):
        return self.verify_many_async(items)()


def blocks_of(n_rows: int, n_blocks: int, seed: int, root: str):
    from benchmarks.manifest import Cell
    from benchmarks.traffic import backlog
    from fabric_mod_tpu import e2e
    net = e2e.Network(root, max_message_count=n_rows, batch_timeout="10s",
                      verifier=MarshalOnly())
    params = dict(Cell("testnet10.backlog").params, warm_blocks=0,
                  provision_tx_s=n_rows * n_blocks)
    backlog.provision(net, params, seed, 1.0, lambda msg: None)
    blocks = [net.support.store.get_block_by_number(i)
              for i in range(1, 1 + n_blocks)]
    return net, blocks


def time_path(validator, blocks, min_rows: int) -> float:
    from fabric_mod_tpu.protos import batchdecode
    batchdecode.COLUMNAR_MIN_ROWS = min_rows
    t0 = time.perf_counter()
    for block in blocks:
        validator.stage(block)
    return 1e3 * (time.perf_counter() - t0) / len(blocks)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", default="10,25,50,75,100,500")
    ap.add_argument("--txs", type=int, default=400,
                    help="transactions staged a pass, at least")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=33)
    args = ap.parse_args()
    for n_rows in map(int, args.sizes.split(",")):
        n_blocks = max(3, -(-args.txs // n_rows))
        with tempfile.TemporaryDirectory() as root:
            net, blocks = blocks_of(n_rows, n_blocks, args.seed, root)
            try:
                validator = net.channel.validator()
                passes = {"generic": [], "columnar": []}
                time_path(validator, blocks, n_rows + 1)   # caches fill
                for _ in range(args.reps):
                    passes["generic"].append(
                        time_path(validator, blocks, n_rows + 1))
                    passes["columnar"].append(
                        time_path(validator, blocks, 0))
            finally:
                net.close()
        line = {"txs_per_block": n_rows, "blocks": n_blocks}
        for path, ms in passes.items():
            line[f"{path}_ms_per_block_median"] = statistics.median(ms)
            line[f"{path}_ms_per_block_min"] = min(ms)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
