#!/usr/bin/env python3
"""What one call of the verify program costs the device, call after
call, with nothing else in the way.

    python3 scripts/device_call_probe.py [--buckets 64,2048] [--calls 60]

Chip only (one process, no threads, no ledger, random inputs: the
ladder's time does not depend on the verdict).  For each bucket of
`bccsp/tpu.BUCKETS` asked for, the program the provider would pick is
called `--calls` times in each of three ways and one JSON line says
how far apart the results came, in ms (median, 10th and 90th
percentile):

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
    args = ap.parse_args()

    import jax
    import numpy as np
    from fabric_mod_tpu.ops import p256
    from fabric_mod_tpu.ops.compilecache import enable_compile_cache
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"a TPU is needed; jax reports {device}", file=sys.stderr)
        return 1
    enable_compile_cache()
    rng = np.random.default_rng(33)
    for bucket in map(int, args.buckets.split(",")):
        planes = [rng.integers(0, 256, (bucket, 32), dtype=np.uint8)
                  for _ in range(5)]
        core_args, _ = p256.marshal_inputs(*planes)
        core = p256._select_core(bucket, None)
        resident = p256.place_core_args(core_args, None)
        t0 = time.perf_counter()
        np.asarray(core(*resident))
        line = {"bucket": bucket, "device": device.device_kind,
                "calls": args.calls,
                "first_call_s": time.perf_counter() - t0}
        fresh = lambda: core(*p256.place_core_args(core_args, None))
        line["serial_ms"] = periods(fresh, np.asarray, args.calls, 1)
        line["depth2_ms"] = periods(fresh, np.asarray, args.calls, 2)
        line["depth2_resident_ms"] = periods(
            lambda: core(*resident), np.asarray, args.calls, 2)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
