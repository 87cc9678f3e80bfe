"""Ask the chip's compiler first: the commit path's device programs,
compiled at their real widths for a DESCRIBED (not attached) TPU v5e.

Nothing here runs on a device, so nothing here is a result or a time
of the chip — only "the TPU compiler accepts this program and it fits
the device's memory".  That is what interpret-mode and CPU tests
cannot see: the Pallas ladder passed every interpret-mode differential
while its window-selection BlockSpec was refused by the TPU lowering.

Rules this file keeps (on-chip-measurement guide, section 2):
* the topology is described inside a module-scoped fixture, never at
  import and never `autouse` — only one process may hold the TPU
  library, and every xdist worker imports every test file;
* all chip compiles live in THIS one file, so one worker loads the
  library;
* the persistent compile cache is off around the compiles (an entry
  written for a described device cannot be read back without one).

The file also pins the one compile-cache helper
(ops/compilecache.py): where the cache lives and what moves it.
"""
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fabric_mod_tpu.ops import compilecache
from fabric_mod_tpu.ops.limbs9 import K

HBM_BYTES = 16 * 10**9          # one TPU v5e chip
BUCKET = 2048                   # a 500-tx block's ~1,500 verifies


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _verify_core_shapes(sharding, batch=BUCKET):
    limb = jax.ShapeDtypeStruct((K, batch), jnp.float32,
                                sharding=sharding)
    flag = jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=sharding)
    return (limb,) * 5 + (flag,)


def _report(name: str, secs: float, compiled) -> dict:
    """The program's bytes on the device, by kind; printed so that
    -s / -rP shows what PERF.md "First chip run" quotes."""
    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k + "_size_in_bytes"))
           for k in ("generated_code", "temp", "argument", "output")}
    mem["total"] = sum(mem.values())
    print(f"[chip-compile] {name}: {secs:.1f}s compile here, {mem}")
    return mem


def test_xla_verify_core_bucket_2048_fits_one_v5e(one_chip,
                                                  no_persistent_cache):
    """THE program of the main path: the default verify core
    (`p256._select_core` with no knob set) at the bucket a 500-tx
    block reaches.  ~107 s alone here; width barely matters to the
    compiler (bucket 8: ~87 s), so no second ladder width."""
    from fabric_mod_tpu.ops import p256
    t0 = time.perf_counter()
    compiled = p256.verify_core.lower(
        *_verify_core_shapes(one_chip)).compile()
    mem = _report("xla verify_core (30, 2048)",
                  time.perf_counter() - t0, compiled)
    assert mem["total"] < HBM_BYTES, mem
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (BUCKET,) and out.dtype == jnp.bool_


@pytest.mark.parametrize("bucket", [8, 64, BUCKET])
def test_xla_verify_core_tables_fits_one_v5e(bucket, one_chip,
                                             no_persistent_cache):
    """The program a lane takes when its public key has a table on the
    device (every lane of every benchmark cell), at the buckets the
    cells warm, over the provider's `KEY_SLOTS` slots.  ~10-18 s each
    here: no Q-table build, a scan body of one addition."""
    from fabric_mod_tpu.bccsp.tpu import KEY_SLOTS
    from fabric_mod_tpu.ops import p256
    limb, _, _, _, _, flag = _verify_core_shapes(one_chip, bucket)
    slot = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct(p256.empty_key_tables(KEY_SLOTS).shape,
                                  jnp.float32, sharding=one_chip)
    t0 = time.perf_counter()
    compiled = p256.verify_core_tables.lower(
        limb, limb, limb, flag, slot, flag, tables).compile()
    mem = _report(f"xla verify_core_tables (30, {bucket})",
                  time.perf_counter() - t0, compiled)
    assert mem["total"] < HBM_BYTES, mem
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (bucket,) and out.dtype == jnp.bool_


def test_sha256_batch_hash_2048x4_blocks(one_chip, no_persistent_cache):
    from fabric_mod_tpu.ops import sha256
    words = jax.ShapeDtypeStruct((BUCKET, 4, 16), jnp.uint32,
                                 sharding=one_chip)
    nblocks = jax.ShapeDtypeStruct((BUCKET,), jnp.int32,
                                   sharding=one_chip)
    t0 = time.perf_counter()
    compiled = sha256.sha256_blocks.lower(words, nblocks).compile()
    mem = _report("sha256_blocks (2048, 4, 16)",
                  time.perf_counter() - t0, compiled)
    assert mem["total"] < HBM_BYTES, mem
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (BUCKET, 8) and out.dtype == jnp.uint32


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="the TPU compiler's verdict on the Pallas ladder, after the "
           "(1, tile) window-selection block was repaired: "
           "'NotImplementedError: Unimplemented primitive in Pallas TPU "
           "lowering for KernelType.TC: scatter' — raised while tracing "
           "limbs9.carried (`hi.at[-1].set(0.0)`), the shared carry "
           "pass of every Montgomery op.  Not a one-line repair; the "
           "kernel is ROADMAP Queue 1 item 2's")
def test_pallas_ladder_batch_2048_tile_128(one_chip, no_persistent_cache):
    """The VMEM-resident Pallas ladder (off the default path, behind
    FABRIC_MOD_TPU_PALLAS) through the very core `_select_core` would
    hand a 2048 bucket.  Interpret mode never lowers for Mosaic, so
    this is the only test that can see what the chip's compiler says.
    When a PR repairs the kernel this xfail turns into a failure
    (strict) and comes off."""
    from fabric_mod_tpu.ops import p256
    t0 = time.perf_counter()
    compiled = p256._pallas_core(128, False, False).lower(
        *_verify_core_shapes(one_chip)).compile()
    mem = _report("pallas verify core (30, 2048) tile 128",
                  time.perf_counter() - t0, compiled)
    assert "tpu_custom_call" in compiled.as_text()
    assert mem["total"] < HBM_BYTES, mem


# ---------------------------------------------------------------------------
# The one compile-cache helper
# ---------------------------------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Record (not apply) what the helper asks of jax.config."""
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: seen.append((name, val)))
    return seen


def _dirs_set(updates):
    return [val for name, val in updates if name.endswith("_cache_dir")]


def test_cache_helper_sets_no_directory_when_env_places_it(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    assert compilecache.enable_compile_cache() == str(tmp_path / "x")
    assert _dirs_set(config_updates) == []
    # the thresholds are still the helper's to set
    assert len(config_updates) == 2
    # and it did not create the operator's directory either
    assert not (tmp_path / "x").exists()


def test_cache_helper_fixed_checkout_path_when_env_unset(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    first = compilecache.enable_compile_cache()
    monkeypatch.chdir(tmp_path)            # a second working directory
    second = compilecache.enable_compile_cache()
    assert first == second == os.path.join(checkout, ".cache", "jax")
    assert _dirs_set(config_updates) == [first, first]
