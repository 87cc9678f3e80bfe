#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.  One process, JAX touched only here, no children.

    python chip_smoke.py              one TPU chip, both phases
    python chip_smoke.py --chips 4    the ("dp",) mesh path, nothing else
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                      CPU rehearsal at bucket 8; every
                                      line says REHEARSAL and no line
                                      ever says "ok": true

It sets no platform in code and has no --cpu: without --rehearse it
exits non-zero at once unless `jax.devices()[0].platform == "tpu"`.

Phase `verify`: 2,048 real P-256 signatures (a known share invalid)
through `TpuVerifier` — verdicts equal to the fixture's expectation
and to `SwCSP().verify_batch` on the same items.

Phase `commit`: the served commit path at the size Fabric operators
run — one channel, three orgs, 2-of-3 endorsement, 500-tx blocks
(Fabric's documented `MaxMessageCount`; 2 MB preferred bytes), three
blocks: endorse -> broadcast -> solo orderer cuts -> deliver ->
TxValidator (one device dispatch of ~1,500 verifies per block, the
2048 bucket) -> MVCC -> durable commit, with the device verifier.  A
seeded few txs carry one endorsement only and a seeded few a corrupted
endorsement signature, so False lanes from the device decide flags.
The plain reference is a second committing peer on
`FakeBatchVerifier(SwCSP())` over the same genesis, pulling the same
blocks from the same orderer: per-block txflags and the state
fingerprint must be equal.

The verifier's fallback RAISES here: the production degrade path
(device error -> software verdict, exit 0) would make a program the
chip refuses look like a pass.  Any exception in any phase ends the
run non-zero; nothing is caught and carried on from.

Earlier lines are observations ("smoke observation, not a
benchmark").  The LAST line of a passing run is exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}`.
"""
import argparse
import dataclasses
import importlib.metadata
import json
import os
import random
import sys
import tempfile
import time

import numpy as np

TOP_BUCKET = 2048
# each process_proposal / block-signature check is one item padded here
SMALL_BUCKET = 8
BATCH_TIMEOUT = "10s"   # Fabric's default is 2s; long enough that only
#                         the count/bytes rules ever close a block here


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def refuse_fallback(items):
    raise SmokeFailure(
        f"the device verifier fell back to software for {len(items)} "
        f"item(s): a device error or an open circuit, on a run whose "
        f"whole point is the device")


def metric_value(name: str) -> float:
    """One sample of the process's own /metrics exposition."""
    from fabric_mod_tpu.observability.metrics import default_provider
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise SmokeFailure(f"metric {name} is not exposed")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phase: verify
# ---------------------------------------------------------------------------

def make_items(n: int, seed: int):
    from fabric_mod_tpu.utils.fixtures import make_verify_items
    items, expect = make_verify_items(
        n, n_keys=64, invalid_every=8,
        seed=b"chip-smoke-%d" % seed)
    return items, np.asarray(expect, bool)


def warm_and_check(say, verifier, items, expect, label: str):
    """First call (compiles) then a warm call, both ended by the host
    pull of the mask; both must equal the expectation.  Returns the
    warm call's mask."""
    cold, t_cold = timed(lambda: verifier.verify_many(items))
    warm, t_warm = timed(lambda: verifier.verify_many(items))
    check(np.array_equal(cold, expect) and np.array_equal(warm, expect),
          f"{label}: device verdicts differ from the expectation in "
          f"{int((np.asarray(warm) != expect).sum())} lane(s)")
    say(f"verify {label}: {len(items)} items, {int((~expect).sum())} "
        f"invalid; first call {t_cold:.2f}s (compile ~"
        f"{t_cold - t_warm:.2f}s), warm call {t_warm:.4f}s")
    return warm


def phase_verify(say, verifier, top_bucket: int, seed: int) -> None:
    from fabric_mod_tpu.bccsp.sw import SwCSP
    items, expect = make_items(top_bucket, seed)
    sw, t_sw = timed(lambda: np.asarray(SwCSP().verify_batch(items), bool))
    check(np.array_equal(sw, expect),
          "SwCSP disagrees with the fixture's expectation")
    say(f"verify reference: SwCSP.verify_batch agrees with the fixture "
        f"on {len(items)} items ({t_sw:.3f}s on the host)")
    warm_and_check(say, verifier, items, expect, f"bucket {top_bucket}")
    if top_bucket != SMALL_BUCKET:
        # the tail slice holds an invalid lane (index % 8 == 7)
        warm_and_check(say, verifier, items[-SMALL_BUCKET:],
                       expect[-SMALL_BUCKET:], f"bucket {SMALL_BUCKET}")


# ---------------------------------------------------------------------------
# phase: commit
# ---------------------------------------------------------------------------

def endorse_all(net, n_txs: int, seed: int):
    """Client-side work: n_txs endorsed envelopes.  A seeded few carry
    ONE endorsement (fails 2-of-3 by count) and a seeded few a
    corrupted second endorsement signature (fails 2-of-3 only if the
    device says False for that lane); broadcast ingress checks the
    creator only, so both reach the validator."""
    from fabric_mod_tpu.protos import protoutil
    rng = random.Random(seed)
    few = max(1, n_txs // 150)
    picked = rng.sample(range(n_txs), 2 * few)
    single, corrupt = set(picked[:few]), set(picked[few:])
    orgs = list(net.endorsers)[:2]
    envs = []
    for i in range(n_txs):
        sp, prop, _ = protoutil.create_chaincode_proposal(
            net.channel_id, "mycc", [b"put", b"k%d" % i, b"v%d" % i],
            net.client)
        responses = [net.endorsers[o].process_proposal(sp)
                     for o in (orgs[:1] if i in single else orgs)]
        if i in corrupt:
            sig = responses[1].endorsement.signature
            responses[1] = dataclasses.replace(
                responses[1], endorsement=dataclasses.replace(
                    responses[1].endorsement,
                    signature=sig[:-1] + bytes([sig[-1] ^ 1])))
        envs.append(protoutil.create_tx_from_responses(
            prop, responses, net.client))
    return envs, single, corrupt


def wait_for_orderer(net, n_blocks: int, timeout_s: float = 60.0) -> None:
    deadline = time.monotonic() + timeout_s
    while net.support.store.height < 1 + n_blocks:
        check(time.monotonic() < deadline,
              f"orderer cut {net.support.store.height - 1} of {n_blocks} "
              f"blocks in {timeout_s:.0f}s")
        time.sleep(0.01)


def bucket_for(n: int) -> int:
    from fabric_mod_tpu.bccsp.tpu import BUCKETS
    return min(b for b in BUCKETS if b >= n)


def closing_rule(block, cfg) -> str:
    """Which of the cutter's rules closed this block, from what the
    block holds (orderer/blockcutter.py; the timer lives in the
    consenter loop and is what is left when neither limit was hit)."""
    n = len(block.data.data)
    if n >= cfg.max_message_count:
        return "count"
    size = sum(len(d) for d in block.data.data)
    biggest = max(len(d) for d in block.data.data)
    if size + biggest > cfg.preferred_max_bytes:
        return "bytes"
    return "timer"


def device_dispatches(spans):
    """(items, bucket) of every device dispatch in the traced window,
    in dispatch order — the `der_marshal` span is opened once per
    dispatch by TpuVerifier._device_dispatch."""
    marshals = sorted((s for s in spans if s["name"] == "der_marshal"),
                      key=lambda s: s["ts"])
    return [(s["attrs"]["items"], s["attrs"]["bucket"]) for s in marshals]


def phase_commit(say, verifier, root: str, n_blocks: int,
                 block_txs: int, seed: int) -> None:
    from fabric_mod_tpu import e2e
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    from fabric_mod_tpu.ledger.kvledger import LedgerManager
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.orderer import DeliverService
    from fabric_mod_tpu.peer.channel import Channel
    from fabric_mod_tpu.peer.deliverclient import DeliverClient
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    n_txs = n_blocks * block_txs
    net = e2e.Network(os.path.join(root, "net"), verifier=verifier,
                      max_message_count=block_txs,
                      batch_timeout=BATCH_TIMEOUT)
    ref_mgr = LedgerManager(os.path.join(root, "ref-peer"))
    try:
        cutter_cfg = net.support.cutter.config
        say(f"commit: one channel, orgs {list(net.endorsers)}, 2-of-3 "
            f"endorsement, blocks of {cutter_cfg.max_message_count} "
            f"messages / {cutter_cfg.preferred_max_bytes} preferred "
            f"bytes, batch timeout {BATCH_TIMEOUT}")

        # -- endorse: two proposal ACL checks per tx, each ONE item
        # padded to bucket 8 on the device (ROADMAP Queue 1 item 5)
        (envs, single, corrupt), t_endorse = timed(
            lambda: endorse_all(net, n_txs, seed))
        say(f"commit endorse: {n_txs} txs, {2 * n_txs - len(single)} "
            f"proposal ACL checks as bucket-{SMALL_BUCKET} device calls, "
            f"{t_endorse:.2f}s; {len(single)} txs single-endorsed, "
            f"{len(corrupt)} with a corrupted endorsement signature")

        # -- broadcast -> the solo orderer cuts
        _, t_submit = timed(
            lambda: [net.broadcast.submit(env) for env in envs])
        wait_for_orderer(net, n_blocks)
        check(net.support.store.height == 1 + n_blocks,
              f"orderer cut {net.support.store.height - 1} data blocks, "
              f"expected {n_blocks}")
        say(f"commit broadcast: {n_txs} envelopes of "
            f"{len(envs[0].encode())} bytes in {t_submit:.2f}s, "
            f"{n_blocks} blocks cut")

        # -- deliver -> validate (device) -> MVCC -> durable commit
        tracing.recorder().reset()
        with tracing.active():
            _, t_commit = timed(
                lambda: net.deliver_client().run(stop_at=n_blocks))
            dispatches = device_dispatches(
                tracing.recorder().recent_spans(limit=4096))
            stages = tracing.substage_totals()
        check(net.ledger.height == 1 + n_blocks,
              f"device peer at height {net.ledger.height}, expected "
              f"{1 + n_blocks}")

        # -- the plain reference: a second committing peer on the
        # software verifier, same genesis, same blocks, same orderer
        csp = SwCSP()
        _, config = config_from_block(net.genesis_block)
        ref_ledger = ref_mgr.create_or_open(net.channel_id)
        ref_channel = Channel(net.channel_id, ref_ledger,
                              FakeBatchVerifier(csp),
                              Bundle(net.channel_id, config, csp), csp)
        ref_channel.init_from_genesis(net.genesis_block)
        _, t_ref = timed(lambda: DeliverClient(
            ref_channel, DeliverService(net.support)).run(stop_at=n_blocks))
        check(ref_ledger.height == net.ledger.height,
              f"reference peer at height {ref_ledger.height}, device "
              f"peer at {net.ledger.height}")

        # -- per block: size, closing rule, bucket, flags vs reference
        # every tx stages its creator signature and two endorsements;
        # the only other dispatch per block is the one-item check of
        # the orderer's block signature
        block_bucket = bucket_for(3 * block_txs)
        validator_calls = [d for d in dispatches if d[0] > 1]
        check(len(validator_calls) == n_blocks,
              f"{len(validator_calls)} block-sized device dispatches "
              f"for {n_blocks} blocks: {dispatches}")
        codes = set()
        for num in range(1, 1 + n_blocks):
            block = net.ledger.get_block_by_number(num)
            flags = bytes(protoutil.block_txflags(block))
            ref_flags = bytes(protoutil.block_txflags(
                ref_ledger.get_block_by_number(num)))
            rule = closing_rule(block, cutter_cfg)
            items, bucket = validator_calls[num - 1]
            n = len(block.data.data)
            say(f"commit block {num}: {n} txs, closed by {rule}, "
                f"{items} verifies -> bucket {bucket}, "
                f"{sum(f != m.TxValidationCode.VALID for f in flags)} "
                f"non-VALID")
            check(rule != "timer",
                  f"block {num} ({n} txs) was closed by the batch timer")
            check(num == n_blocks or bucket == block_bucket,
                  f"block {num} has {n} txs ({items} verifies) and "
                  f"reached bucket {bucket}, not {block_bucket}")
            check(flags == ref_flags,
                  f"block {num}: txflags differ from the software arm's")
            codes.update(flags)
        check(codes - {m.TxValidationCode.VALID},
              "every tx was VALID: no False lane decided a flag")
        seen = {b for _, b in dispatches}
        check(seen <= {SMALL_BUCKET, block_bucket},
              f"the commit phase reached buckets {sorted(seen)} that "
              f"were not warmed")

        fp = net.ledger.state_fingerprint()
        check(fp == ref_ledger.state_fingerprint(),
              "state fingerprint differs from the software arm's")
        check(fp == net.ledger.state_fingerprint_full(),
              "incremental state fingerprint != full rescan")
        say(f"commit result: {n_txs} txs in {n_blocks} blocks, flags "
            f"{sorted(codes)}, fingerprint {fp[:16]} == software arm == "
            f"full rescan")
        say(f"smoke observation, not a benchmark: device peer committed "
            f"{n_txs} txs in {t_commit:.2f}s wall (software-verifier "
            f"peer: {t_ref:.2f}s); spans "
            + json.dumps({k: v["secs"] for k, v in sorted(stages.items())}))
    finally:
        ref_mgr.close()
        net.close()


# ---------------------------------------------------------------------------
# --chips 4: the ("dp",) mesh path and what it is compared with
# ---------------------------------------------------------------------------

def phase_mesh(say, n_chips: int, bucket: int, seed: int) -> None:
    from fabric_mod_tpu import parallel
    from fabric_mod_tpu.bccsp.tpu import TpuVerifier, marshal_items
    from fabric_mod_tpu.ops import p256

    items, expect = make_items(bucket, seed)
    mesh = parallel.data_mesh(n_chips)

    # where the verifier's inputs land: the same marshalling and the
    # same placement function batch_verify runs
    d, r, s, qx, qy, _pre_ok, _msg = marshal_items(items, bucket)
    core_args, _ = p256.marshal_inputs(d, r, s, qx, qy)
    placed = p256.place_core_args(core_args, mesh)
    for arr in placed:
        check(len(arr.sharding.device_set) == n_chips,
              f"an input of shape {arr.shape} spans "
              f"{len(arr.sharding.device_set)} device(s), not {n_chips}")
    limb = placed[0]
    shard_shapes = {sh.data.shape for sh in limb.addressable_shards}
    check(shard_shapes == {(limb.shape[0], bucket // n_chips)},
          f"limb shards are {shard_shapes}")
    say(f"mesh inputs: {len(placed)} arrays over {n_chips} devices, limb "
        f"shards {sorted(shard_shapes)} of {limb.shape}")

    sharded = TpuVerifier(mesh=mesh, cache_size=0,
                          fallback=refuse_fallback)
    single = TpuVerifier(cache_size=0, fallback=refuse_fallback)
    try:
        on_mesh = warm_and_check(
            say, sharded, items, expect,
            f"bucket {bucket} on {n_chips}-device mesh")
        on_one = warm_and_check(say, single, items, expect,
                                f"bucket {bucket} on device 0 alone")
        check(np.array_equal(on_mesh, on_one),
              "mesh and single-device masks differ")
        for v in (sharded, single):
            check(v.breaker.state == "closed",
                  f"breaker {v.breaker.state}")
    finally:
        sharded.close()
        single.close()


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the 4-device mesh verify and the "
                         "single-device run it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at bucket 8 (2-tx blocks); "
                         "never prints \"ok\": true")
    ap.add_argument("--seed", type=int, default=22,
                    help="fixture digests and which txs are made bad")
    args = ap.parse_args()

    tag = "REHEARSAL " if args.rehearse else ""

    def say(msg: str) -> None:
        print(tag + msg, flush=True)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU here (jax reports {device}); this "
              f"script measures nothing off the chip — rehearse with "
              f"JAX_PLATFORMS=cpu python chip_smoke.py --rehearse",
              file=sys.stderr)
        return 1
    check(device["count"] == args.chips,
          f"--chips {args.chips} but jax reports {device['count']} "
          f"device(s)")

    import jaxlib
    from fabric_mod_tpu import concurrency
    from fabric_mod_tpu.bccsp import sw
    from fabric_mod_tpu.bccsp.tpu import TpuVerifier
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.ops.compilecache import enable_compile_cache
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu}, device {device}")
    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache directory in force: {cache_dir} ({n_cached} "
        f"entries at start; 0 means every compile below is cold)")
    say(f"bccsp.sw.HAVE_CRYPTOGRAPHY = {sw.HAVE_CRYPTOGRAPHY}")
    check(sw.HAVE_CRYPTOGRAPHY,
          "the `cryptography` package is missing: the software "
          "reference would be the pure-python fallback")
    tracing.install_compile_counter()

    top_bucket = SMALL_BUCKET if args.rehearse else TOP_BUCKET
    t_start = time.perf_counter()
    if args.chips == 4:
        phase_mesh(say, 4, top_bucket, args.seed)
    else:
        # 500-tx blocks reach the 2048 bucket; the rehearsal's 2-tx
        # blocks (6 verifies) stay inside the one bucket-8 program
        block_txs = 2 if args.rehearse else 500
        verifier = TpuVerifier(cache_size=0, fallback=refuse_fallback)
        try:
            phase_verify(say, verifier, top_bucket, args.seed)
            compiles_warm = tracing.compile_count()
            with tempfile.TemporaryDirectory() as root:
                phase_commit(say, verifier, root, 3, block_txs, args.seed)
            say(f"xla compiles: {compiles_warm} while warming, "
                f"{tracing.compile_count() - compiles_warm} in the "
                f"commit phase")
            check(verifier.breaker.state == "closed",
                  f"breaker is {verifier.breaker.state}")
        finally:
            verifier.close()

    fallbacks = metric_value("fabric_bccsp_sw_fallback_batches_total")
    errors = metric_value("fabric_bccsp_device_errors_total")
    check(fallbacks == 0 and errors == 0,
          f"sw fallback batches {fallbacks}, device errors {errors}")
    leaked = concurrency.live_registered()
    check(not leaked, f"threads still registered: {leaked}")
    say(f"no software fallback, no device error, breaker closed, no "
        f"thread left; {time.perf_counter() - t_start:.1f}s after start-up")
    if args.rehearse:
        say(f"done on {device}: a rehearsal proves the control flow, "
            f"nothing about the chip")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
