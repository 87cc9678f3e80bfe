#!/usr/bin/env python3
"""What one call of a verify program costs the device, call after
call, with nothing else in the way.

    python3 scripts/device_call_probe.py [--buckets 64,2048] [--calls 60]
        [--programs tables,ladder]

Chip only (one process, no threads, no ledger; real signatures over
six keys, one lane in eight tampered, so each program's verdicts are
held to the fixture's).  For each bucket of `bccsp/tpu.BUCKETS` asked
for, each of the provider's two programs (`tables`: the lanes' keys
have fixed-base tables on the device; `ladder`: they have none) is
called `--calls` times in each of three ways and one JSON line a
program says how far apart the results came, in ms (median, 10th and
90th percentile):

    serial          dispatch, fetch the result, dispatch the next
    depth2          the next call is dispatched before the last
                    result is fetched (the commit pipeline's shape);
                    inputs placed anew for every call, as the
                    provider does
    depth2_resident the same with the inputs already on the device

`depth2` is the least period the device path allows a block that
makes one such call.  A traced benchmark run reports the program's own
event in the profiler (`verify_kernel_ms_per_call`); PR 33 found the
period to be longer than that event by ~8 ms for bucket 64
(PERF.md section 6), host or no host.

Last, the paths of `KeyTables`' rule no benchmark cell takes, one
JSON line a batch through a fresh `TpuVerifier`: at the widest bucket
asked for, batches over twice `NEW_TABLES_PER_BATCH` keys, each sent
three times: keys met for the first time while slots are free (a
table call and a ladder call, the budget's tables built), the same
again (the rest built), the same again (every key known: the cost to
compare with); then, the slots filled, keys met for the first time
(the ladder alone, nothing built) and three times more (the split,
the rest built, every key known).  A line
says how the lanes split, the tables built, the host's time to
dispatch (builds, put, marshal, enqueue), the time to the verdicts,
and whether the merged verdicts are the fixture's.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def periods(dispatch, fetch, calls: int, depth: int) -> dict:
    import numpy as np
    ahead = [dispatch() for _ in range(depth - 1)]
    stamps = []
    for _ in range(calls):
        ahead.append(dispatch())
        fetch(ahead.pop(0))
        stamps.append(time.perf_counter())
    for out in ahead:
        fetch(out)
    gaps = np.diff(stamps) * 1e3
    return {"median": float(np.median(gaps)),
            "p10": float(np.percentile(gaps, 10)),
            "p90": float(np.percentile(gaps, 90))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--buckets", default="64,2048")
    ap.add_argument("--calls", type=int, default=60)
    ap.add_argument("--programs", default="tables,ladder")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from fabric_mod_tpu.bccsp import tpu
    from fabric_mod_tpu.ops import p256
    from fabric_mod_tpu.ops.compilecache import enable_compile_cache
    from fabric_mod_tpu.utils.fixtures import make_verify_items
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"a TPU is needed; jax reports {device}", file=sys.stderr)
        return 1
    enable_compile_cache()
    buckets = [int(b) for b in args.buckets.split(",")]
    for bucket in buckets:
        items, expect = make_verify_items(
            bucket, n_keys=6, invalid_every=8, seed=b"probe-%d" % bucket)
        d, r, s, qx, qy, pre_ok, _ = tpu.marshal_items(items, bucket)
        slot, slot_ok, _, tables = tpu.KeyTables().assign(
            [it.public_xy for it in items])
        scalars, _ = p256.marshal_scalars(d, r, s)
        ladder_args, _ = p256.marshal_inputs(d, r, s, qx, qy)
        # program -> (core, host arguments placed anew for every call,
        # arguments that stay on the device)
        programs = {
            "tables": (p256.verify_core_tables,
                       scalars + (slot, slot_ok), (tables,)),
            "ladder": (p256._select_core(bucket, None), ladder_args, ()),
        }
        for name in args.programs.split(","):
            core, host_args, device_args = programs[name]
            resident = tuple(map(jnp.asarray, host_args))
            t0 = time.perf_counter()
            got = np.asarray(core(*resident, *device_args)) & pre_ok
            line = {"bucket": bucket, "program": name,
                    "device": device.device_kind, "calls": args.calls,
                    "first_call_s": time.perf_counter() - t0,
                    "lanes_differ": int((got != np.asarray(expect)).sum())}
            fresh = lambda: core(*map(jnp.asarray, host_args), *device_args)
            line["serial_ms"] = periods(fresh, np.asarray, args.calls, 1)
            line["depth2_ms"] = periods(fresh, np.asarray, args.calls, 2)
            line["depth2_resident_ms"] = periods(
                lambda: core(*resident, *device_args), np.asarray,
                args.calls, 2)
            print(json.dumps(line), flush=True)

    # the sides of the rule no cell takes: each batch timed whole
    n = max(buckets)
    n_keys = 2 * tpu.NEW_TABLES_PER_BATCH
    verifier = tpu.TpuVerifier(cache_size=0)

    def tables_built() -> float:
        from fabric_mod_tpu.observability.metrics import default_provider
        return next(float(line.split()[1]) for line in
                    default_provider().render_prometheus().splitlines()
                    if line.startswith("fabric_bccsp_key_tables_built_total "))

    def batch(what: str, items, expect) -> None:
        built = tables_built()
        t0 = time.perf_counter()
        resolve = verifier.verify_many_async(items)
        t1 = time.perf_counter()
        got = resolve()
        t2 = time.perf_counter()
        print(json.dumps({
            "rule": what, "lanes": len(items), "keys": n_keys,
            "table_lanes": resolve.table_lanes,
            "ladder_lanes": resolve.ladder_lanes,
            "tables_built": tables_built() - built,
            "dispatch_ms": 1e3 * (t1 - t0), "total_ms": 1e3 * (t2 - t0),
            "lanes_differ": int((got != np.asarray(expect)).sum())}),
            flush=True)

    def fixture(tag: bytes):
        return make_verify_items(n, n_keys=n_keys, invalid_every=5,
                                 seed=b"probe-rule-" + tag)

    first = fixture(b"free")
    for what in ("new keys, slots free", "the same again",
                 "every key known"):
        batch(what, *first)
    from fabric_mod_tpu.bccsp.sw import SwCSP
    csp = SwCSP()
    while verifier._tables._free:           # no device call: keys alone
        verifier._tables.assign([csp.key_gen().public_xy() for _ in
                                 range(tpu.NEW_TABLES_PER_BATCH)])
    late = fixture(b"full")
    for what in ("new keys, slots full", "met before, slots full",
                 "the same again", "every key known"):
        batch(what, *late)
    verifier.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
