"""Records `probe.xplane.pb` and `probe.json`: a small trace of a
known number of executions of one named program, for
`test_reduce.py`.  Run on the chip; writes into `chiprun_out/`.

    chiprun -- python benchmarks/testdata/record_probe.py
"""
import glob
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

CALLS = 5


def bench_probe(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) + 1.0
    return x


def main() -> None:
    out = os.path.join("chiprun_out", "probe")
    os.makedirs(out, exist_ok=True)
    f = jax.jit(bench_probe)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        t0 = time.perf_counter()
        for _ in range(CALLS):
            x = f(x)
            x.block_until_ready()
            time.sleep(0.01)
        window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        shutil.copy(src, os.path.join(out, "probe.xplane.pb"))
    with open(os.path.join(out, "probe.json"), "w") as fh:
        json.dump({"calls": CALLS, "window_s": window_s,
                   "device_kind": jax.devices()[0].device_kind}, fh)
    print("recorded", os.path.getsize(os.path.join(out, "probe.xplane.pb")),
          "bytes")


if __name__ == "__main__":
    main()
