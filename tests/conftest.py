"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so CI needs no TPU, mirroring how
the reference runs validation logic against mocked state (SURVEY.md §4).
Env vars must be set before jax is first imported anywhere.
"""
import os

# Force CPU: unit tests run on the virtual 8-device CPU mesh whatever
# the machine holds.  The env var is set before jax is imported; the
# config update below pins it for code that imported jax earlier.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache, shared across pytest processes.  The
# crypto cores (p256 ladder/pallas, fp256bn pairing, the sharded verify
# lowerings) cost several hundred seconds of CPU XLA compile time per
# cold run; with the cache primed a full tier-1 pass spends none of it.
# Keyed by HLO + compile options, so a genuine kernel change recompiles
# and re-caches automatically.  ops/compilecache.py is the one place
# that names the directory.  Opt out with FMT_NO_COMPILE_CACHE=1 (e.g.
# to time cold compiles): the package's own import-time enable calls
# then still name the directory, but nothing reads or writes it.
if os.environ.get("FMT_NO_COMPILE_CACHE", "") in ("", "0"):
    from fabric_mod_tpu.ops.compilecache import enable_compile_cache

    enable_compile_cache()
else:
    jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

# FMT_RACECHECK=1 arms every guard in fabric_mod_tpu/concurrency for
# the whole run (the package reads the env var at import): guarded
# queues, field/thread ownership, the lock-order registry, and
# leak-checked teardowns all raise RaceError instead of racing.  This
# is the suite-wide race tier — the analog of the reference running
# its whole unit suite under `go test -race`
# (scripts/run-unit-tests.sh:142-161).
RACECHECK = os.environ.get("FMT_RACECHECK", "") not in ("", "0")


def pytest_sessionfinish(session, exitstatus):
    if not RACECHECK:
        return
    from fabric_mod_tpu.concurrency import live_registered
    leaked = live_registered()
    if leaked:
        # advisory sweep: per-structure close() paths already hard-fail
        # on their own workers; this catches structures never closed
        names = sorted({f"{t.structure}:{t.name}" for t in leaked})
        print(f"\n[FMT_RACECHECK] {len(leaked)} registered thread(s) "
              f"still alive at session end: {', '.join(names[:20])}")


@pytest.fixture(scope="session")
def rng():
    import random

    return random.Random(0xFAB)
