#!/usr/bin/env python
"""Benchmark driver: batched ECDSA-P256 verification throughput on device.

Prints ONE JSON line per metric: {"metric", "value", "unit",
"vs_baseline", ..., "platform", "device_kind", "n_devices"}.

Headline metric per BASELINE.md: ECDSA-P256 verifies/sec/chip on the
device batch verifier vs the software CSP (`bccsp.sw`, backed by
OpenSSL via the `cryptography` package — the analog of the reference's
bccsp/sw, bccsp/sw/ecdsa.go:41-57).  The measured path is end-to-end
through TpuVerifier.verify_many: host DER decode + range checks +
limb marshalling + one jitted device program per bucket — the same
path the block validator uses, so the number is honest about host
overheads, not a kernel-only figure.

One process measures: `main()` runs each `--metric` in turn, in this
process, and starts no children.  `--cpu` sets the platform before
JAX is imported and is the only way to the CPU.  Without it, a metric
that drives the device verifier or `ops/` exits non-zero unless
`jax.devices()[0].platform` is "tpu" — a CPU number is never printed
under a device metric's name by accident.  Every JSON line carries
`platform`, `device_kind` and `n_devices` from this process.

Baseline is measured in-process each run (same machine, same OpenSSL)
rather than hard-coded.  Diagnostics go to stderr; stdout carries
exactly the one JSON line the driver parses.
"""
import argparse
import json
import os
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def make_items(n: int, n_keys: int = 64):
    """n real signatures (~0.4% deliberately invalid) as VerifyItems,
    from the shared fixture generator (sw-provider signing, so low-S
    normalized like production)."""
    from fabric_mod_tpu.utils.fixtures import make_verify_items

    return make_verify_items(n, n_keys=n_keys, invalid_every=256,
                             seed=b"bench")


def measure_marshal(n: int, reps: int) -> tuple:
    """Host marshalling microbench: the vectorized batch path
    (bccsp/tpu.marshal_items) vs the pre-overhaul per-item python loop
    (reproduced verbatim below), same items, outputs asserted
    identical.  Pure host work — no device, no jit."""
    import numpy as np

    from fabric_mod_tpu.bccsp import sw as _sw
    from fabric_mod_tpu.bccsp.tpu import _LOW_S_MAX, marshal_items

    items, _ = make_items(n)
    size = n

    def per_item_loop():
        # The old TpuVerifier.verify_many_async marshalling loop,
        # kept as the A/B baseline.
        d = np.zeros((size, 32), np.uint8)
        r = np.zeros((size, 32), np.uint8)
        s = np.zeros((size, 32), np.uint8)
        qx = np.zeros((size, 32), np.uint8)
        qy = np.zeros((size, 32), np.uint8)
        pre_ok = np.zeros(size, bool)
        for i, it in enumerate(items):
            try:
                ri, si = _sw.decode_dss_signature(it.signature)
                if not (len(it.digest) == 32 and len(it.public_xy) == 64):
                    continue
                if si > _LOW_S_MAX:
                    continue
                r[i] = np.frombuffer(ri.to_bytes(32, "big"), np.uint8)
                s[i] = np.frombuffer(si.to_bytes(32, "big"), np.uint8)
                d[i] = np.frombuffer(it.digest, np.uint8)
                qx[i] = np.frombuffer(it.public_xy[:32], np.uint8)
                qy[i] = np.frombuffer(it.public_xy[32:], np.uint8)
                pre_ok[i] = True
            except Exception:
                continue
        return d, r, s, qx, qy, pre_ok

    loop_out = per_item_loop()                   # warm-up + reference
    vec_out = marshal_items(items, size)
    if not np.array_equal(vec_out[5], loop_out[5]):
        raise AssertionError("vectorized marshal diverges on pre_ok")
    # value planes compared on pre_ok rows only: the old loop zeroes
    # rejected rows, the batch path leaves decoded-but-masked bytes
    # (both are discarded — pre_ok gates the verdict)
    okrows = vec_out[5]
    for a, b, name in zip(vec_out, loop_out, ("d", "r", "s", "qx", "qy")):
        if not np.array_equal(a[okrows], b[okrows]):
            raise AssertionError(f"vectorized marshal diverges on {name}")

    # INTERLEAVED min-of-k timing: the two paths alternate windows so
    # noisy-neighbor slowdowns hit both alike, and the fastest window
    # of each stands in for the uncontended cost — the ratio is then
    # a property of the code, not of the machine's mood.
    loop_best = vec_best = float("inf")
    for _ in range(max(reps, 7)):
        t0 = time.perf_counter()
        per_item_loop()
        loop_best = min(loop_best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        marshal_items(items, size)
        vec_best = min(vec_best, time.perf_counter() - t0)
    loop_rate = n / loop_best
    vec_rate = n / vec_best
    backend = "openssl" if _sw.HAVE_CRYPTOGRAPHY else "pure-python-scalar"
    log(f"per-item loop ({backend} DER): {loop_rate:,.0f} items/s; "
        f"vectorized: {vec_rate:,.0f} items/s "
        f"({vec_rate / loop_rate:.1f}x)")
    return vec_rate, loop_rate


def measure_diffverify(n: int) -> tuple:
    """Differential acceptance check: every enabled ladder core must
    produce IDENTICAL verdicts to the projective XLA core on n
    randomized signatures including invalid and edge-case lanes, and
    the fused raw-message path must match host-side hashing.  Chunked
    through one static bucket so each core compiles once.

    Also times each core on the same chunks (interleaved min-of-k) —
    the on-chip mixed-vs-projective A/B the ROADMAP's "measure before
    defaulting on" question needs; the ratio lands in the JSON line.

    Returns (n, mismatches, extras): mismatches totals across every
    core pair INCLUDING the fused-hash differential.
    """
    import numpy as np

    from fabric_mod_tpu.bccsp.tpu import marshal_items
    from fabric_mod_tpu.ops import p256

    items, expect = make_items(n, n_keys=32)
    # the one tested marshalling path; copies because the edge-case
    # lanes below mutate the planes (fast-path outputs are read-only)
    d, r, s, qx, qy, _pre_ok, _msg = (
        a.copy() if isinstance(a, np.ndarray) else a
        for a in marshal_items(items, n))
    # adversarial/edge lanes sprinkled across the batch (mirrors
    # tests/test_p256.py's negatives): tampered digest, wrong key,
    # zero/overrange scalars, off-curve key, (0,0) key, high-s mirror
    N_ORDER = p256.N
    for base in range(0, n - 8, 97):
        d[base][0] ^= 1
        qx[base + 1], qy[base + 1] = qx[base + 2], qy[base + 2]
        s[base + 3][:] = 0
        r[base + 4][:] = np.frombuffer(
            N_ORDER.to_bytes(32, "big"), np.uint8)
        qy[base + 5][31] ^= 1
        qx[base + 6][:] = 0
        qy[base + 6][:] = 0
        s_int = int.from_bytes(bytes(s[base + 7]), "big")
        if 0 < s_int < N_ORDER:
            s[base + 7] = np.frombuffer(
                (N_ORDER - s_int).to_bytes(32, "big"), np.uint8)

    # pad to a whole number of fixed-size chunks so each core compiles
    # ONCE (a remainder chunk would mint a second multi-minute program
    # shape); zero rows fail range_ok identically in every core.
    # Small runs (the CPU smoke target) use one right-sized chunk.
    chunk = 2048 if n >= 2048 else max(8, n + (-n) % 8)
    pad = (-n) % chunk
    if pad:
        z = np.zeros((pad, 32), np.uint8)
        d, r, s = (np.concatenate([a, z]) for a in (d, r, s))
        qx, qy = (np.concatenate([a, z]) for a in (qx, qy))

    # every core the env knobs can select, all compared against the
    # projective XLA reference (PALLAS x MIXED_ADD composition matrix)
    cores = {"projective": p256.verify_core,
             "mixed": p256.verify_core_mixed}
    if p256._use_pallas():
        tile = next((t for t in (128, 64, 32, 16, 8)
                     if chunk % t == 0), None)
        if tile is not None:
            cores["pallas_projective"] = p256._pallas_core(tile)
            cores["pallas_mixed"] = p256._pallas_core(tile, mixed=True)

    # warm-up: compile every core on the first chunk OUTSIDE the
    # timing (a cold first call is a multi-minute XLA compile, which
    # would otherwise dominate `best` whenever the batch is one chunk
    # — i.e. exactly the A/B numbers the JSON line reports)
    warm_args, _ = p256.marshal_inputs(
        d[:chunk], r[:chunk], s[:chunk], qx[:chunk], qy[:chunk])
    for name, core in cores.items():
        t1 = time.perf_counter()
        np.asarray(core(*warm_args))
        log(f"{name}: warm-up (incl. compile) "
            f"{time.perf_counter() - t1:.1f}s")

    mismatches = 0
    best = {name: float("inf") for name in cores}
    t0 = time.perf_counter()
    for lo in range(0, n + pad, chunk):
        hi = lo + chunk
        core_args, range_ok = p256.marshal_inputs(
            d[lo:hi], r[lo:hi], s[lo:hi], qx[lo:hi], qy[lo:hi])
        got = {}
        for name, core in cores.items():        # interleaved timing:
            t1 = time.perf_counter()            # noisy neighbors hit
            out = core(*core_args)              # all cores alike
            verdicts = np.asarray(out) & range_ok
            best[name] = min(best[name], time.perf_counter() - t1)
            got[name] = verdicts
        for name, verdicts in got.items():
            if name != "projective":
                mismatches += int((verdicts != got["projective"]).sum())
    log(f"diffverify: {n} signatures x {len(cores)} cores in "
        f"{time.perf_counter() - t0:.1f}s, {mismatches} verdict "
        f"mismatches")
    rates = {name: round(chunk / b, 1) for name, b in best.items()}
    log(f"per-core best-chunk rates (verifies/s): {rates}")

    fused_mm = _fused_hash_differential(min(n, 256))
    mismatches += fused_mm
    extras = {
        "core_rates_verifies_per_sec": rates,
        "mixed_vs_projective_speedup": round(
            best["projective"] / best["mixed"], 3),
        "fused_hash_mismatches": fused_mm,
    }
    if "pallas_mixed" in best:
        extras["pallas_mixed_vs_projective_speedup"] = round(
            best["projective"] / best["pallas_mixed"], 3)
    return n, mismatches, extras


def _fused_hash_differential(k: int) -> int:
    """Raw-message items vs pre-digested items over the SAME payloads
    and signatures (incl. tampered lanes) through TpuVerifier: the
    fused on-device hash must change no verdict.  Returns mismatches."""
    import hashlib

    import numpy as np

    from fabric_mod_tpu.bccsp.api import VerifyItem
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import TpuVerifier

    k = max(8, k + (-k) % 8)
    csp = SwCSP()
    keys = [csp.key_gen() for _ in range(4)]
    raw, dig = [], []
    for i in range(k):
        m = b"fused-%d|" % i + b"x" * (i % 77)
        kp = keys[i % len(keys)]
        sig = csp.sign(kp, hashlib.sha256(m).digest())
        if i % 9 == 5:
            m += b"!"                      # tampered message lane
        raw.append(VerifyItem(b"", sig, kp.public_xy(), message=m))
        dig.append(VerifyItem(hashlib.sha256(m).digest(), sig,
                              kp.public_xy()))
    v = TpuVerifier(cache_size=0)
    got_raw = np.asarray(v.verify_many(raw))
    got_dig = np.asarray(v.verify_many(dig))
    mm = int((got_raw != got_dig).sum())
    log(f"fused-hash differential: {k} items, {mm} mismatches")
    return mm


def measure_hashverify(n: int, reps: int) -> tuple:
    """Fused on-device hash->verify vs host-hash-then-device-verify,
    same payloads/signatures through the same TpuVerifier front door.

    The baseline pays the per-message host hashlib loop the fused path
    deletes (the reference's hash-then-verify shape,
    msp/identities.go:169); both paths' verdicts are asserted
    identical, so the number can't come from a wrong-answer shortcut.
    Messages are ~200-byte envelope-payload-sized."""
    import hashlib

    import numpy as np

    from fabric_mod_tpu.bccsp.api import VerifyItem
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import TpuVerifier

    csp = SwCSP()
    keys = [csp.key_gen() for _ in range(64)]
    msgs, sigs, pubs, expect = [], [], [], []
    log(f"hashverify: signing {n} messages ...")
    for i in range(n):
        m = (b"hashverify-%d|" % i) + b"p" * (150 + i % 100)
        kp = keys[i % len(keys)]
        sig = csp.sign(kp, hashlib.sha256(m).digest())
        bad = i % 256 == 255
        if bad:
            m += b"!"                      # tampered message lane
        msgs.append(m)
        sigs.append(sig)
        pubs.append(kp.public_xy())
        expect.append(not bad)

    raw_items = [VerifyItem(b"", sg, pb, message=m)
                 for m, sg, pb in zip(msgs, sigs, pubs)]

    def host_hash_pass():
        return [VerifyItem(hashlib.sha256(m).digest(), sg, pb)
                for m, sg, pb in zip(msgs, sigs, pubs)]

    v = TpuVerifier(cache_size=0)
    t0 = time.perf_counter()
    got_dig = v.verify_many(host_hash_pass())
    log(f"baseline warm-up (incl. compile): "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    got_raw = v.verify_many(raw_items)
    log(f"fused warm-up (incl. compile): {time.perf_counter() - t0:.1f}s")
    if list(got_raw) != list(got_dig) or list(got_raw) != expect:
        bad = [i for i, (a, b) in enumerate(zip(got_raw, got_dig))
               if a != b]
        raise AssertionError(
            f"fused verdicts diverge from host hashing at {bad[:10]}")

    # interleaved min-of-k (same reasoning as measure_marshal): the
    # baseline re-hashes on the host every rep — that loop is exactly
    # the cost under test
    base_best = fused_best = float("inf")
    for _ in range(max(reps, 3)):
        t0 = time.perf_counter()
        v.verify_many(host_hash_pass())
        base_best = min(base_best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        v.verify_many(raw_items)
        fused_best = min(fused_best, time.perf_counter() - t0)
    fused_rate = n / fused_best
    base_rate = n / base_best
    log(f"host-hash path: {base_rate:,.0f} verifies/s; fused: "
        f"{fused_rate:,.0f} verifies/s ({fused_rate / base_rate:.2f}x)")
    return fused_rate, base_rate


def measure_sw(items, expect) -> float:
    from fabric_mod_tpu.bccsp.sw import SwCSP

    csp = SwCSP()
    sub = items[:1024]
    t0 = time.perf_counter()
    got = csp.verify_batch(sub)
    dt = time.perf_counter() - t0
    if got != expect[:len(sub)]:
        raise AssertionError("sw baseline verdicts wrong")
    return len(sub) / dt


def measure_device(items, expect, reps: int) -> float:
    import jax

    from fabric_mod_tpu.bccsp.tpu import TpuVerifier

    t0 = time.perf_counter()
    devs = jax.devices()
    log(f"jax platform: {devs[0].platform}, {len(devs)} device(s), "
        f"backend init {time.perf_counter() - t0:.1f}s")
    # memo-cache OFF: reps re-verify identical items, and a cache hit
    # would measure the LRU, not the device (the gossip metric is the
    # cache's honest showcase — its redelivery shape is real)
    v = TpuVerifier(cache_size=0)
    t0 = time.perf_counter()
    got = v.verify_many(items)          # includes compile on cold cache
    log(f"warm-up (incl. compile): {time.perf_counter() - t0:.1f}s")
    if list(got) != expect:
        bad = [i for i, (g, e) in enumerate(zip(got, expect)) if g != e]
        raise AssertionError(f"device verdicts wrong at {bad[:10]}")
    t0 = time.perf_counter()
    for _ in range(reps):
        v.verify_many(items)
    dt = time.perf_counter() - t0
    return len(items) * reps / dt


def _block_world(n_txs: int, under_endorse_every: int = 0):
    """A 1000-tx-style block world: 3 orgs, 2-of-3 endorsement
    (BASELINE config #2; reference: txvalidator/v20/validator.go:182).
    `under_endorse_every` > 0 endorses every k-th tx by one org only —
    ENDORSEMENT_POLICY_FAILURE lanes for differentials that must not
    pass vacuously on an all-VALID block."""
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity
    from fabric_mod_tpu.peer import TxValidator, ValidationInfoProvider
    from fabric_mod_tpu.policy import ApplicationPolicyEvaluator
    from fabric_mod_tpu.protos import protoutil

    csp, cas, mgr, signers, policy = _three_org_world()
    ccert, ckey = cas["Org1"].issue("client@org1", "Org1",
                                    ous=["client"])
    signers["client"] = SigningIdentity(
        "Org1", ccert, calib.key_pem(ckey), csp)

    envs = []
    for i in range(n_txs):
        b = RWSetBuilder()
        b.add_write("mycc", f"key{i}", b"val%d" % i)
        endorsers = [signers["Org1"], signers["Org2"]]
        if under_endorse_every and i % under_endorse_every == \
                under_endorse_every - 1:
            endorsers = [signers["Org1"]]      # 1-of-3 < 2: must fail
        envs.append(protoutil.create_signed_tx(
            "bench", "mycc", b.build().encode(), signers["client"],
            endorsers))
    block = protoutil.new_block(0, b"", envs)

    def make_validator(verifier):
        return TxValidator("bench", mgr,
                           ApplicationPolicyEvaluator(mgr), verifier,
                           ValidationInfoProvider(policy))
    return block, make_validator


def _three_org_world():
    """The shared bench world: 3 orgs, one peer signer each, the
    2-of-3 endorsement policy (BASELINE config #2).  Returns
    (csp, cas, mgr, signers, policy_bytes); _block_world and
    _commitpipe_world both build on it."""
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity
    from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
    from fabric_mod_tpu.policy import from_string
    from fabric_mod_tpu.protos import messages as m

    csp = SwCSP()
    cas, msps, signers = {}, [], {}
    for org in ("Org1", "Org2", "Org3"):
        ca = calib.CA(f"ca.{org.lower()}", org)
        cas[org] = ca
        msps.append(Msp(org, csp, [ca.cert]))
        cert, key = ca.issue(f"peer0.{org.lower()}", org, ous=["peer"])
        signers[org] = SigningIdentity(org, cert, calib.key_pem(key), csp)
    policy = m.ApplicationPolicy(signature_policy=from_string(
        "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')")).encode()
    # the production channel shape: second-chance caches around the
    # manager (peer/channel._install_bundle wraps its bundle manager
    # the same way), so the bench measures the deployed hot path
    from fabric_mod_tpu.msp.cache import CachedMsp
    return csp, cas, CachedMsp(MspManager(msps)), signers, policy


def _commitpipe_world(n_blocks: int, txs_per_block: int):
    """An in-order block stream with MIXED barrier and non-barrier
    blocks: every 6th block carries a VALIDATION_PARAMETER metadata
    write pinning key "pinned" to an alternating single org (a
    `needs_barrier` block), and the NEXT block writes "pinned" under
    endorsements that only sometimes satisfy the pin — so the final
    txflags genuinely depend on barrier-correct ordering, and the
    pipelined/sync differential can't pass by accident.

    Returns (encoded_blocks, make_committer, barrier_count) where
    make_committer builds a fresh (ledger, validator) pair wired for
    key-level policies (state_metadata) against a fresh directory."""
    from fabric_mod_tpu.ledger import KvLedger
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    from fabric_mod_tpu.peer import TxValidator, ValidationInfoProvider
    from fabric_mod_tpu.peer.txvalidator import VALIDATION_PARAMETER
    from fabric_mod_tpu.policy import ApplicationPolicyEvaluator, from_string
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    _csp, _cas, mgr, signers, cc_policy = _three_org_world()

    def org_policy(org):
        return m.ApplicationPolicy(
            signature_policy=from_string(f"'{org}.peer'")).encode()

    def tx(rwset_bytes, endorsers):
        return protoutil.create_signed_tx(
            "bench", "mycc", rwset_bytes, signers["Org1"],
            [signers[o] for o in endorsers])

    log(f"commitpipe: signing {n_blocks} blocks x {txs_per_block} txs ...")
    blocks, prev, barriers = [], b"", 0
    for n in range(n_blocks):
        envs = []
        for j in range(txs_per_block):
            b = RWSetBuilder()
            if n == 0 and j == 0:
                # seed "pinned" so the first VP pin has a key to bind
                # to (statedb drops metadata writes on absent keys —
                # an unseeded pin would be a silent no-op and the
                # first barrier would carry no verdict signal)
                b.add_write("mycc", "pinned", b"v0")
                envs.append(tx(b.build().encode(), ("Org1", "Org2")))
                continue
            if j == 0 and n % 6 == 5:
                # barrier block: re-pin "pinned" to the next org in
                # the alternation.  Metadata-only write (any other
                # key would drag in the cc-wide policy), endorsed by
                # whichever org the STANDING pin requires — changing
                # a pinned key's VP must itself satisfy the current
                # pin, so a 2-of-3 re-pin after the first would fail
                # forever and the alternating signal would be dead
                k = barriers
                pin_orgs = ("Org3", "Org1")
                b.add_metadata_write("mycc", "pinned",
                                     VALIDATION_PARAMETER,
                                     org_policy(pin_orgs[k % 2]))
                endorsers = (("Org1", "Org2") if k == 0
                             else (pin_orgs[(k - 1) % 2],))
                envs.append(tx(b.build().encode(), endorsers))
                barriers += 1
                continue
            if j == 1 and n % 6 == 0 and n > 0:
                # first block AFTER a barrier: write the pinned key.
                # Org1+Org2 endorsements satisfy the Org1 pin but not
                # the Org3 pin (the pins alternate), so the verdict
                # depends on the PREVIOUS block's committed VP — a
                # stage-ahead bug reads the stale pin (or none) and
                # flips this tx's flag
                b.add_write("mycc", "pinned", b"v%d" % n)
                envs.append(tx(b.build().encode(), ("Org1", "Org2")))
                continue
            b.add_write("mycc", f"blk{n}tx{j}", b"v")
            envs.append(tx(b.build().encode(), ("Org1", "Org2")))
        blk = protoutil.new_block(n, prev, envs)
        prev = protoutil.block_header_hash(blk.header)
        blocks.append(blk.encode())

    def make_committer(verifier, root):
        led = KvLedger(root, "bench")

        def state_vp(ns, key):
            meta = led.state.get_metadata(ns, key)
            return meta.get(VALIDATION_PARAMETER) if meta else None
        validator = TxValidator(
            "bench", mgr, ApplicationPolicyEvaluator(mgr), verifier,
            ValidationInfoProvider(cc_policy),
            tx_id_exists=led.tx_id_exists, state_metadata=state_vp)
        return led, validator
    return blocks, make_committer, barriers


def measure_commitpipe(n_blocks: int, txs_per_block: int, depth: int,
                       use_sw: bool) -> dict:
    """Whole-pipeline committed-tx/s A/B: the synchronous Committer vs
    the PipelinedCommitter over the SAME block stream into fresh
    ledgers.  Per-block txflags and the final ledger state fingerprint
    are asserted bit-identical (and depth=1 is additionally asserted
    identical to sync) BEFORE any rate is reported — the number can't
    come from a wrong-answer shortcut."""
    import tempfile

    from fabric_mod_tpu.peer import (Committer, PipelinedCommitter,
                                     ValidatorCommitTarget)
    from fabric_mod_tpu.protos import messages as m

    if use_sw:
        from fabric_mod_tpu.bccsp.sw import SwCSP
        from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
        verifier = FakeBatchVerifier(SwCSP())
    else:
        from fabric_mod_tpu.bccsp.tpu import TpuVerifier
        # memo-cache off: every block's items are distinct anyway, and
        # the A/B must measure the pipeline, not the LRU
        verifier = TpuVerifier(cache_size=0)
    blocks, make_committer, barriers = _commitpipe_world(
        n_blocks, txs_per_block)
    n_txs = n_blocks * txs_per_block

    def run_sync(root):
        led, validator = make_committer(verifier, root)
        committer = Committer(validator, led)
        flags = []
        t0 = time.perf_counter()
        for raw in blocks:
            flags.append(list(committer.store_block(m.Block.decode(raw))))
        dt = time.perf_counter() - t0
        return flags, led.state_fingerprint(), n_txs / dt

    def run_pipe(root, d):
        led, validator = make_committer(verifier, root)
        flags = []
        pipe = PipelinedCommitter(
            ValidatorCommitTarget(validator, led), depth=d,
            on_commit=lambda _b, f: flags.append(list(f)))
        t0 = time.perf_counter()
        for raw in blocks:
            pipe.submit(m.Block.decode(raw))
        pipe.flush()
        dt = time.perf_counter() - t0
        pipe.close()
        secs = {"stage": pipe.stage_secs, "await": pipe.await_secs,
                "commit": pipe.commit_secs}
        return flags, led.state_fingerprint(), n_txs / dt, secs

    from fabric_mod_tpu.observability import tracing
    with tempfile.TemporaryDirectory(prefix="fmt_commitpipe_") as tmp:
        if not use_sw:
            # warm-up: compile the verify bucket outside the timing
            led, validator = make_committer(verifier, tmp + "/warm")
            t0 = time.perf_counter()
            Committer(validator, led).store_block(m.Block.decode(blocks[0]))
            log(f"commitpipe warm-up (incl. compile): "
                f"{time.perf_counter() - t0:.1f}s")
        # baseline arms run with tracing EXPLICITLY off: under
        # --trace-out or an exported FMT_TRACE the whole worker is
        # armed, and an armed baseline would turn the traced-vs-
        # untraced identity gate below into armed-vs-armed — vacuous,
        # and the reported rates would silently include span overhead
        with tracing.active(False):
            sync_flags, sync_fp, sync_rate = run_sync(tmp + "/sync")
            log(f"sync committer: {sync_rate:,.0f} committed tx/s")
            pipe_flags, pipe_fp, pipe_rate, _secs = run_pipe(
                tmp + "/pipe", depth)
            log(f"pipelined (depth={depth}): {pipe_rate:,.0f} "
                f"committed tx/s ({pipe_rate / sync_rate:.2f}x)")
            d1_flags, d1_fp, _, _ = run_pipe(tmp + "/depth1", 1)
        # the TRACED arm: same stream through a pipelined committer
        # with FMT_TRACE armed — verdicts + state fingerprint must be
        # IDENTICAL to the tracing-off arms before any attribution
        # number is reported, and the named sub-span totals must sum
        # to (within tolerance of) the stage/await/commit buckets the
        # engine itself measured
        tracing.recorder().reset()
        with tracing.active():
            tr_flags, tr_fp, _tr_rate, tr_secs = run_pipe(
                tmp + "/traced", depth)
            totals = {k: v["secs"]
                      for k, v in tracing.substage_totals().items()}

    flags_ok = pipe_flags == sync_flags
    state_ok = pipe_fp == sync_fp
    depth1_ok = d1_flags == sync_flags and d1_fp == sync_fp
    if not flags_ok:
        bad = [i for i, (a, b) in enumerate(zip(pipe_flags, sync_flags))
               if a != b]
        raise AssertionError(
            f"pipelined txflags diverge from sync at blocks {bad[:5]}")
    if not state_ok:
        raise AssertionError("pipelined state fingerprint diverges")
    if not depth1_ok:
        raise AssertionError("depth=1 does not match the sync path")
    if tr_flags != sync_flags or tr_fp != sync_fp:
        raise AssertionError(
            "FMT_TRACE-armed run diverges from the tracing-off arms "
            "— tracing must be a pure observer")
    # stage attribution: the named sub-span totals must explain the
    # engine's own stage/await/commit buckets (within 10%, floored at
    # 100 ms so tiny CPU runs don't flake on timer noise)
    attribution = {
        "buckets_secs": {k: round(v, 3) for k, v in tr_secs.items()},
        "substage_secs": {k: round(v, 3) for k, v in sorted(
            totals.items())},
    }
    bucket_parts = {
        "stage": ("unpack", "device_dispatch"),
        "await": ("verdict_await",),
        "commit": ("policy_finish", "mvcc", "ledger_write"),
    }
    for bucket, parts in bucket_parts.items():
        have = sum(totals.get(p, 0.0) for p in parts)
        want = tr_secs[bucket]
        # floor 0.3s: post-r12 the stage/commit buckets are tens of
        # ms per block, and the engine's bucket timers (but not the
        # in-thread spans) absorb GIL-scheduling stalls while the
        # OTHER pipeline thread crunches pure-python ECDSA on the
        # wheel-less arm — sub-noise buckets must not flake the gate
        # (a genuinely unattributed NEW sub-stage at that scale is
        # invisible under any floor; the r09-scale drifts this gate
        # exists for are seconds, not fractions)
        tol = max(0.10 * want, 0.3)
        attribution[f"{bucket}_covered"] = round(
            have / want, 3) if want > 1e-9 else 1.0
        if abs(want - have) > tol:
            raise AssertionError(
                f"stage attribution drifted: {bucket} bucket "
                f"{want:.3f}s vs sub-span sum {have:.3f}s "
                f"({'+'.join(parts)}) — tolerance {tol:.3f}s")
    # how much of the commit bucket is policy evaluation
    policy_secs = totals.get("policy_finish", 0.0)
    commit_secs = max(tr_secs["commit"], 1e-9)
    attribution["commit_policy_share"] = round(
        policy_secs / commit_secs, 3)
    # the interesting flags actually flipped (the stream exercised the
    # barrier-dependent verdicts, not just all-VALID blocks) — an
    # all-VALID stream would let the differential pass vacuously
    distinct = {f for per_block in sync_flags for f in per_block}
    if distinct == {0}:
        raise AssertionError(
            "commitpipe stream produced only VALID flags — the "
            "barrier-dependent verdicts the oracle relies on are gone")
    return {
        "pipelined_tx_per_sec": round(pipe_rate, 1),
        "sync_tx_per_sec": round(sync_rate, 1),
        "blocks": n_blocks,
        "txs_per_block": txs_per_block,
        "barrier_blocks": barriers,
        "depth": depth,
        "distinct_flags": sorted(distinct),
        "flags_identical": flags_ok,
        "state_hash_identical": state_ok,
        "depth1_identical": depth1_ok,
        "traced_identical": True,          # asserted above
        "stage_attribution": attribution,
        "verifier": "sw" if use_sw else "device",
    }


def _sk(i: int) -> str:
    return "sk%07d" % i


def _statescale_world(n_blocks: int, txs_per_block: int,
                      touch_space: int):
    """A signed block stream with REAL MVCC work — fresh reads against
    prefilled state, deliberate stale reads, absent-key probes, narrow
    range queries (some provably phantom-conflicted), deletes, and a
    VALIDATION_PARAMETER pin that flips later writes of the pinned key
    invalid.  Every key it touches lives in the first `touch_space`
    prefilled keys, so ONE stream is valid at EVERY sweep point and
    the txflags must be identical across state sizes as well as
    across arms.  Reads draw from the upper half of the touched
    keyspace and writes from the lower half (disjoint), so a fresh
    read stays fresh for the whole stream and every conflict is one
    the generator placed deliberately.

    Returns (encoded_blocks, make_committer); make_committer builds a
    fresh (ledger, validator) pair — non-durable by default (the sweep
    measures decode+MVCC economics, not log fsync); durable=True runs
    the same sweep on DurableStateDB, whose batched one-buffered-
    write-per-block apply_updates is what makes that arm affordable."""
    import random

    from fabric_mod_tpu.ledger import KvLedger
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    from fabric_mod_tpu.peer import TxValidator, ValidationInfoProvider
    from fabric_mod_tpu.peer.txvalidator import VALIDATION_PARAMETER
    from fabric_mod_tpu.policy import ApplicationPolicyEvaluator, from_string
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    _csp, _cas, mgr, signers, cc_policy = _three_org_world()
    rng = random.Random(1807)
    write_pool = touch_space // 2
    pin_key = _sk(1)

    def tx(rwset_bytes, endorsers):
        return protoutil.create_signed_tx(
            "bench", "mycc", rwset_bytes, signers["Org1"],
            [signers[o] for o in endorsers])

    log(f"statescale: signing {n_blocks} blocks x {txs_per_block} "
        f"txs ...")
    blocks, prev = [], b""
    for n in range(n_blocks):
        envs = []
        for j in range(txs_per_block):
            b = RWSetBuilder()
            endorsers = ("Org1", "Org2")
            if n == 2 and j == 0:
                # pin the (prefilled, so the metadata write sticks)
                # key's VP to Org3-only: every later write of it under
                # Org1+Org2 must flip ENDORSEMENT_POLICY_FAILURE
                b.add_metadata_write("mycc", pin_key,
                                     VALIDATION_PARAMETER,
                                     m.ApplicationPolicy(
                                         signature_policy=from_string(
                                             "'Org3.peer'")).encode())
                envs.append(tx(b.build().encode(), endorsers))
                continue
            if n >= 3 and j == 1:
                b.add_write("mycc", pin_key, b"pinned%d" % n)
                envs.append(tx(b.build().encode(), endorsers))
                continue
            # 28 reads/tx: the conflict-detection work is the sweep's
            # subject — it must dominate span-timer noise, not hide
            # under it (signing cost is per-tx, so this is ~free)
            for _ in range(28):
                k = _sk(write_pool + rng.randrange(
                    touch_space - write_pool))
                if rng.random() < 0.005:
                    b.add_read("mycc", k, (9999, 0))      # stale
                else:
                    b.add_read("mycc", k, (0, 0))         # fresh
            # absent-key probes (valid: no committed version)
            for _ in range(2):
                b.add_read("mycc", "zz%05d" % rng.randrange(1000),
                           None)
            for _ in range(3):
                k = _sk(rng.randrange(write_pool))
                if rng.random() < 0.10:
                    b.add_write("mycc", k, None)          # delete
                else:
                    b.add_write("mycc", k, b"v%d.%d" % (n, j))
            r = rng.random()
            if r < 0.10:
                # prefilled rows exist in-range but none recorded:
                # PHANTOM_READ_CONFLICT in BOTH arms, deterministically
                # (the range sits in the read-only half, so no stream
                # write ever changes what the re-scan sees)
                b.add_range_query("mycc", _sk(write_pool + 50),
                                  _sk(write_pool + 52), True, [])
            elif r < 0.25:
                b.add_range_query("mycc", "zz~0", "zz~9", True, [])
            if rng.random() < 0.08:
                endorsers = ("Org2",)     # under-endorsed: 2-of-3 fails
            envs.append(tx(b.build().encode(), endorsers))
        blk = protoutil.new_block(n, prev, envs)
        prev = protoutil.block_header_hash(blk.header)
        blocks.append(blk.encode())

    def make_committer(verifier, root, durable=False):
        led = KvLedger(root, "bench", durable=durable)

        def state_vp(ns, key):
            meta = led.state.get_metadata(ns, key)
            return meta.get(VALIDATION_PARAMETER) if meta else None
        validator = TxValidator(
            "bench", mgr, ApplicationPolicyEvaluator(mgr), verifier,
            ValidationInfoProvider(cc_policy),
            tx_id_exists=led.tx_id_exists, state_metadata=state_vp)
        return led, validator
    return blocks, make_committer


def measure_statescale(sizes, n_blocks: int = 8,
                       txs_per_block: int = 128,
                       durable: bool = False) -> dict:
    """Vectorized-MVCC differential sweep at real state scale: the
    SAME signed block stream committed into ledgers prefilled at each
    `sizes` point, in two arms: "vector" hands commit_block the planes
    stage decoded (the vectorized MVCC over them), "generic" withholds
    them (rwsets=None: every envelope decoded on the commit side, the
    serial MVCC).  At EVERY point, per-block txflags and the state
    fingerprint are asserted bit-identical across arms (and across
    sizes — the stream only touches the common prefilled keyspace),
    the incremental fingerprint is asserted equal to the full-scan
    oracle on BOTH arms, and the body-decode fallback counter must not
    move on this well-formed stream — all BEFORE any rate is reported.
    Both arms run FMT_TRACE-armed, so the reported stage+mvcc bucket
    seconds are like-for-like (and at >=100k keys the vectorized
    bucket must actually be smaller).  Blocks are kept at or above
    batchdecode.COLUMNAR_MIN_ROWS rows: a smaller block is staged
    without the columnar decode, there are no planes to hand over and
    both arms would run the serial MVCC."""
    import tempfile

    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.ledger.statedb import UpdateBatch
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.peer.txvalidator import _stage_metrics
    from fabric_mod_tpu.protos import messages as m

    sizes = sorted(sizes)
    if len(sizes) < 3:
        raise ValueError("statescale needs >= 3 state sizes")
    verifier = FakeBatchVerifier(SwCSP())
    blocks, make_committer = _statescale_world(
        n_blocks, txs_per_block, min(sizes))
    n_txs = n_blocks * txs_per_block

    def run_arm(root, n_keys, planes):
        led, validator = make_committer(verifier, root, durable)
        t0 = time.perf_counter()
        for lo in range(0, n_keys, 200_000):
            batch = UpdateBatch()
            for i in range(lo, min(lo + 200_000, n_keys)):
                batch.put("mycc", _sk(i), b"seed-%07d" % i, (0, 0))
            led.state.apply_updates(batch, 0)
        prefill_secs = time.perf_counter() - t0
        t0 = time.perf_counter()
        led.state_fingerprint()        # seed the incremental fold
        seed_secs = time.perf_counter() - t0
        fb0 = _stage_metrics()[3].value
        flags = []
        tracing.recorder().reset()
        with tracing.active():
            t0 = time.perf_counter()
            for raw in blocks:
                block = m.Block.decode(raw)
                staged = validator.stage(block)
                flags.append(list(led.commit_block(
                    block, validator.finish(staged),
                    rwsets=staged.rwsets if planes else None)))
            dt = time.perf_counter() - t0
            totals = {k: v["secs"]
                      for k, v in tracing.substage_totals().items()}
        fallbacks = _stage_metrics()[3].value - fb0
        t0 = time.perf_counter()
        fp = led.state_fingerprint()
        incr_secs = time.perf_counter() - t0
        t0 = time.perf_counter()
        full = led.state_fingerprint_full()
        full_secs = time.perf_counter() - t0
        return {
            "flags": flags, "fp": fp, "fp_full": full,
            "tx_per_sec": n_txs / dt,
            "fallbacks": fallbacks,
            # "unpack" contains the stage-side batch body decode,
            # "mvcc" the commit-side rwset materialization + version
            # compares — together the cost the columnar pipeline
            # attacks (verify/dispatch buckets are off-path here)
            "stage_mvcc_secs": totals.get("unpack", 0.0)
                               + totals.get("mvcc", 0.0),
            "buckets_secs": {k: round(totals.get(k, 0.0), 4)
                             for k in ("unpack", "body_decode",
                                       "mvcc", "mvcc_vector")},
            "prefill_secs": prefill_secs, "seed_secs": seed_secs,
            "incr_secs": incr_secs, "full_secs": full_secs,
        }

    points, flags0 = [], None
    with tempfile.TemporaryDirectory(prefix="fmt_statescale_") \
            as tmp:
        for n_keys in sizes:
            gen = run_arm(f"{tmp}/g{n_keys}", n_keys, planes=False)
            vec = run_arm(f"{tmp}/v{n_keys}", n_keys, planes=True)
            # -- gates: every one BEFORE any rate is reported ----
            if vec["flags"] != gen["flags"]:
                bad = [i for i, (a, b) in enumerate(
                    zip(vec["flags"], gen["flags"])) if a != b]
                raise AssertionError(
                    f"statescale@{n_keys}: vectorized txflags "
                    f"diverge from generic at blocks {bad[:5]}")
            if vec["fp"] != gen["fp"]:
                raise AssertionError(
                    f"statescale@{n_keys}: state fingerprint "
                    "diverges across arms")
            for arm_name, arm in (("generic", gen),
                                  ("vector", vec)):
                if arm["fp"] != arm["fp_full"]:
                    raise AssertionError(
                        f"statescale@{n_keys}/{arm_name}: "
                        "incremental fingerprint != full-scan "
                        "oracle")
                if arm["fallbacks"]:
                    raise AssertionError(
                        f"statescale@{n_keys}/{arm_name}: "
                        f"{arm['fallbacks']} body-decode "
                        "fallbacks on the well-formed stream")
            if flags0 is None:
                flags0 = gen["flags"]
                distinct = {f for per in flags0 for f in per}
                if distinct == {0}:
                    raise AssertionError(
                        "statescale stream produced only VALID "
                        "flags — the conflict/policy verdicts "
                        "the oracle relies on are gone")
            elif gen["flags"] != flags0:
                raise AssertionError(
                    f"statescale@{n_keys}: txflags changed with "
                    "state size — the stream must only touch the "
                    "common prefilled keyspace")
            if n_keys >= 100_000 and vec["stage_mvcc_secs"] >= \
                    gen["stage_mvcc_secs"]:
                raise AssertionError(
                    f"statescale@{n_keys}: stage+mvcc "
                    f"{vec['stage_mvcc_secs']:.3f}s vectorized "
                    f"vs {gen['stage_mvcc_secs']:.3f}s generic — "
                    "the vectorized path must not be slower at "
                    "scale")
            point = {"state_keys": n_keys}
            for arm_name, arm in (("generic", gen),
                                  ("vector", vec)):
                point[arm_name] = {
                    "tx_per_sec": round(arm["tx_per_sec"], 1),
                    "stage_mvcc_secs": round(
                        arm["stage_mvcc_secs"], 4),
                    "buckets_secs": arm["buckets_secs"],
                    "fingerprint_secs": {
                        "seed_scan": round(arm["seed_secs"], 4),
                        "incremental": round(arm["incr_secs"], 6),
                        "full_scan": round(arm["full_secs"], 4)},
                    "prefill_secs": round(arm["prefill_secs"], 3),
                }
            point["flags_identical"] = True
            point["fingerprint_identical"] = True
            point["body_decode_fallbacks"] = 0
            point["stage_mvcc_speedup"] = round(
                gen["stage_mvcc_secs"]
                / max(vec["stage_mvcc_secs"], 1e-9), 3)
            log(f"statescale@{n_keys}: generic "
                f"{gen['tx_per_sec']:,.0f} tx/s (stage+mvcc "
                f"{gen['stage_mvcc_secs']:.3f}s), vector "
                f"{vec['tx_per_sec']:,.0f} tx/s (stage+mvcc "
                f"{vec['stage_mvcc_secs']:.3f}s)")
            points.append(point)
    return {
        "points": points,
        "top": {
            "state_keys": sizes[-1],
            "generic_tx_per_sec":
                points[-1]["generic"]["tx_per_sec"],
            "vector_tx_per_sec":
                points[-1]["vector"]["tx_per_sec"],
        },
        "blocks": n_blocks, "txs_per_block": txs_per_block,
        "distinct_flags": sorted({f for per in flags0 for f in per}),
        "verifier": "sw", "durable": durable, "traced_arms": True,
    }


def measure_block(n_txs: int, reps: int) -> tuple:
    """Validated tx/s, device batch verifier vs sw provider.
    validate() mutates only the txflags metadata, so reps re-validate
    the same block object — no copying inside the timed loop."""
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier, TpuVerifier

    block, make_validator = _block_world(n_txs)
    V = 0  # TxValidationCode.VALID

    def run(validator, reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            flags = validator.validate(block)
            if any(f != V for f in flags):
                raise AssertionError("bench block failed validation")
        return n_txs * reps / (time.perf_counter() - t0)

    sw_validator = make_validator(FakeBatchVerifier(SwCSP()))
    sw_rate = run(sw_validator, 1)
    log(f"sw block validation: {sw_rate:,.0f} tx/s")
    # cache off for the same reason as measure_device: reps replay one
    # block, production validates distinct blocks
    dev_validator = make_validator(TpuVerifier(cache_size=0))
    t0 = time.perf_counter()
    run(dev_validator, 1)                   # warm-up/compile
    log(f"block warm-up (incl. compile): {time.perf_counter() - t0:.1f}s")
    dev_rate = run(dev_validator, reps)
    log(f"device block validation: {dev_rate:,.0f} tx/s")
    return dev_rate, sw_rate


def measure_e2e(n_txs: int) -> tuple:
    """End-to-end validated tx/s: endorsed txs -> solo orderer cuts
    blocks -> peer verifies (device batch) + MVCC + commits
    (BASELINE config #3 shape, in-process network).  Returns the
    pipeline stage split too, so the record shows whether throughput
    is bounded by ordering or by crypto (BASELINE's e2e criterion)."""
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier, TpuVerifier
    from fabric_mod_tpu.e2e import run_pipeline
    from fabric_mod_tpu.observability import tracing

    # both timed arms run with tracing armed (the warm-up doesn't):
    # the sub-span totals give the stage-attribution split, and arming
    # BOTH arms keeps the vs_baseline ratio apples-to-apples
    with tracing.active():
        sw_rate = run_pipeline(min(n_txs, 2000),
                               FakeBatchVerifier(SwCSP()))
    log(f"sw e2e: {sw_rate:,.0f} tx/s")
    verifier = TpuVerifier()
    run_pipeline(min(n_txs, 2000), verifier)      # warm-up/compile
    stats = {}
    with tracing.active():
        dev_rate = run_pipeline(n_txs, verifier, stats=stats)
    log(f"device e2e: {dev_rate:,.0f} tx/s  split: {stats}")
    return dev_rate, sw_rate, stats


def measure_idemix(n: int, reps: int) -> tuple:
    """Anonymous-presentation verifies/s, device batched pairing vs the
    host pairing path (BASELINE config #4; reference:
    idemix/signature.go:243 Ver, integration/idemix/idemix_test.go:25).

    Both paths run the SAME batch_verify surface (pairing equation +
    Schnorr/Fiat-Shamir recheck); the delta is where the two pairings
    per presentation execute — batched on device vs sequential host
    Fp12.  One presentation is tampered so the bench proves the
    verdict path, not a constant-True short circuit."""
    from fabric_mod_tpu.idemix import credential as idx

    ik = idx.IssuerKey(["ou", "role"])
    sk = idx._rand_zr()
    cred = idx.issue(ik, sk, [5, 7])
    log(f"idemix: signing {n} presentations ...")
    items = []
    for i in range(n):
        sig = idx.sign(ik, cred, sk, b"msg%d" % i, {0: 5})
        items.append((sig, b"msg%d" % i, {0: 5}))
    # tamper one pairing input: A_bar off by the generator
    from fabric_mod_tpu.idemix.fp256bn import G1, g1_add
    bad = n // 2
    items[bad][0].A_bar = g1_add(items[bad][0].A_bar, G1.generator())
    expect = [i != bad for i in range(n)]

    host_n = min(n, 16)
    t0 = time.perf_counter()
    got = idx.batch_verify(ik, items[:host_n], use_device=False)
    sw_rate = host_n / (time.perf_counter() - t0)
    if got != expect[:host_n]:
        raise AssertionError("idemix host verdicts wrong")
    log(f"sw idemix: {sw_rate:,.1f} presentations/s")

    t0 = time.perf_counter()
    got = idx.batch_verify(ik, items, use_device=True)  # incl. compile
    compile_secs = time.perf_counter() - t0
    log(f"idemix warm-up (incl. compile): {compile_secs:.1f}s — the "
        f"pairing program sits on the persistent XLA cache "
        f"(ops/compilecache.py), so a cached run shows ~steady-state "
        f"time here")
    if got != expect:
        bad_idx = [i for i, (g, e) in enumerate(zip(got, expect)) if g != e]
        raise AssertionError(f"idemix device verdicts wrong at {bad_idx}")
    t0 = time.perf_counter()
    for _ in range(reps):
        idx.batch_verify(ik, items, use_device=True)
    dev_rate = n * reps / (time.perf_counter() - t0)
    steady = n / dev_rate
    log(f"device idemix: {dev_rate:,.1f} presentations/s")
    # compile cost ≈ warm-up minus one steady-state batch; recorded so
    # the artifact shows whether the persistent cache held (a second
    # run must show ~0)
    return dev_rate, sw_rate, max(0.0, compile_secs - steady)


def measure_gossip(n_peers: int, reps: int) -> tuple:
    """Aggregate block verifies/s across a simulated gossip storm:
    `n_peers` peers concurrently verify the same orderer-signed block
    stream through MessageCryptoService (data-hash recompute +
    cert-chain deserialization + BlockValidation policy) — BASELINE
    config #5 (reference: internal/peer/gossip/mcs.go:124,
    gossip/identity/identity.go:176, gossip/comm/comm_impl.go:411).

    The device path routes every peer's signature checks through ONE
    BatchingVerifyService so concurrent small verifies coalesce into
    device batches — the TPU answer to per-connection goroutines."""
    import tempfile
    import threading

    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import (BatchingVerifyService,
                                          FakeBatchVerifier, TpuVerifier)
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    from fabric_mod_tpu.e2e import Network
    from fabric_mod_tpu.peer.mcs import MessageCryptoService

    tmp = tempfile.mkdtemp(prefix="fmt_gossip_bench_")
    net = Network(tmp, batch_timeout="50ms", max_message_count=32)
    try:
        for i in range(96):
            net.invoke([b"put", b"k%d" % i, b"v%d" % i])
        net.pump_committed(96)
        store = net.support.store
        blocks = [store.get_block_by_number(i)
                  for i in range(1, store.height)]
        log(f"gossip: {len(blocks)} orderer-signed blocks, "
            f"{n_peers} peers x {reps} reps")
        _, config = config_from_block(net.genesis_block)
        bundle = Bundle(net.channel_id, config, net.csp)

        def storm(verify_many) -> float:
            svcs = [MessageCryptoService(lambda: bundle,
                                         _VerifierShim(verify_many))
                    for _ in range(n_peers)]
            start = threading.Barrier(n_peers + 1)
            errs = []

            def peer_main(svc):
                start.wait()
                try:
                    for _ in range(reps):
                        for blk in blocks:
                            svc.verify_block(net.channel_id, blk)
                except Exception as e:       # noqa: BLE001
                    errs.append(e)

            threads = [threading.Thread(target=peer_main, args=(s,),
                                        daemon=True) for s in svcs]
            for t in threads:
                t.start()
            start.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            if errs:
                raise errs[0]
            return n_peers * reps * len(blocks) / dt

        sw_rate = storm(FakeBatchVerifier(SwCSP()).verify_many)
        log(f"sw gossip storm: {sw_rate:,.1f} block-verifies/s")
        dev = BatchingVerifyService(TpuVerifier())
        # BOUNDED wait: an unbounded Future wait could only turn a
        # wedged device into a silent hang.  The default matches
        # verify_smoke.sh's export: a COLD CPU compile of the verify
        # cores runs multiple minutes, and the first storm call
        # carries it whole
        budget = float(os.environ.get("FABRIC_MOD_TPU_BENCH_TIMEOUT",
                                      "2400"))
        dev_verify = lambda items: dev.verify_many(items, timeout=budget)
        try:
            storm(dev_verify)                 # warm-up/compile
            dev_rate = storm(dev_verify)
        finally:
            dev.close()
        log(f"device gossip storm: {dev_rate:,.1f} block-verifies/s")
        return dev_rate, sw_rate
    finally:
        net.close()


class _VerifierShim:
    """Adapts a bare verify_many callable to the MCS verifier seam."""

    def __init__(self, verify_many):
        self.verify_many = verify_many


# ---------------------------------------------------------------------------
# broadcast storm: admission control under a many-client overload burst
# ---------------------------------------------------------------------------

def _storm_material(n_clients: int, max_message_count: int,
                    batch_timeout: str) -> dict:
    """Shared crypto + genesis for every storm arm: one org, one solo
    orderer, `n_clients` distinct client identities (one token bucket
    each).  Both arms open fresh channels from the SAME genesis so the
    pre-signed envelopes satisfy both arms' Writers policy.  No peers
    — the storm invariant is about broadcast→order→deliver, and the
    orderer's own store is the deliver source of truth."""
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.channelconfig import genesis
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity

    csp = SwCSP()
    org_ca = calib.CA("ca.org1", "Org1")
    ord_ca = calib.CA("ca.orderer", "OrdererOrg")
    ocert, okey = ord_ca.issue("orderer0", "OrdererOrg", ous=["orderer"])
    orderer_signer = SigningIdentity("OrdererOrg", ocert,
                                     calib.key_pem(okey), csp)
    clients = []
    for i in range(n_clients):
        cert, key = org_ca.issue(f"client{i}@org1", "Org1",
                                 ous=["client"])
        clients.append(SigningIdentity("Org1", cert,
                                       calib.key_pem(key), csp))
    gblock = genesis.standard_network(
        "storm", {"Org1": [calib.cert_pem(org_ca.cert)]},
        {"OrdererOrg": [calib.cert_pem(ord_ca.cert)]},
        max_message_count=max_message_count,
        batch_timeout=batch_timeout)
    return {"csp": csp, "clients": clients, "genesis": gblock,
            "orderer_signer": orderer_signer}


def _storm_channel(root: str, mat: dict, verify_many=None):
    from fabric_mod_tpu.orderer import Registrar
    registrar = Registrar(root, mat["orderer_signer"], mat["csp"],
                          verify_many=verify_many)
    support = registrar.create_channel(mat["genesis"])
    return registrar, support


def _storm_device_verifier(staged_batch: int):
    """Build the device batch verifier for the --storm-verifier=device
    arms: verdict memo-cache OFF (the same pre-signed envelopes replay
    in every arm — a cache hit would fake the batch economics), and
    the 1-item and `staged_batch`-item padding buckets warmed with
    garbage items OUTSIDE any measured window, so arms time dispatch,
    not XLA compiles.  Returns (verify_many, close)."""
    from fabric_mod_tpu.bccsp.api import VerifyItem
    from fabric_mod_tpu.bccsp.tpu import TpuVerifier

    verifier = TpuVerifier(cache_size=0)

    def junk(n):
        # distinct digests: identical items would dedup to one device
        # lane and warm the wrong bucket
        return [VerifyItem((b"storm-warm-%08d" % i).ljust(32, b"\0"),
                           b"\x00" * 8, b"\x00" * 64)
                for i in range(n)]

    log("storm: warming device verify buckets (1 and "
        f"{staged_batch}-item) ...")
    t0 = time.perf_counter()
    verifier.verify_many(junk(1))
    verifier.verify_many(junk(max(2, staged_batch)))
    log(f"storm: device buckets warm in {time.perf_counter() - t0:.1f}s")
    return verifier.verify_many, verifier.close


def _storm_envelopes(clients, per_client: int):
    """Pre-signed envelopes (setup, untimed): one Writers signature
    each, distinct tx ids so commits are countable per envelope."""
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    envs = []                              # [(client_idx, tx_id, env)]
    for ci, signer in enumerate(clients):
        creator = signer.serialize()
        for j in range(per_client):
            tx_id = f"storm-c{ci}-{j}"
            ch = protoutil.make_channel_header(
                m.HeaderType.ENDORSER_TRANSACTION, "storm", tx_id=tx_id)
            sh = protoutil.make_signature_header(creator,
                                                 protoutil.new_nonce())
            payload = protoutil.make_payload(ch, sh,
                                             b"storm-%d-%d" % (ci, j))
            envs.append((ci, tx_id, protoutil.sign_envelope(payload,
                                                            signer)))
    return envs


def _storm_committed_tx_ids(store) -> list:
    from fabric_mod_tpu.protos import protoutil
    tx_ids = []
    for n in range(1, store.height):
        block = store.get_block_by_number(n)
        for env in protoutil.get_envelopes(block):
            ch = protoutil.envelope_channel_header(env)
            tx_ids.append(ch.tx_id)
    return tx_ids


def _storm_arm(root: str, envs_by_client, mat: dict, gated: bool,
               drain_delay_s: float, queue_cap: int,
               staged: int = 0, verify_many=None) -> dict:
    """One storm run: every client thread pushes its envelopes as fast
    as the ingress admits them; a sleep shim on write_block caps the
    drain rate (the controlled overload; `drain_delay_s` <= 0 leaves
    the backend unthrottled, so INGRESS is the binding resource).
    `staged` > 0 arms the staged ingress engine at that coalescing
    depth; `verify_many` overrides the Writers batch verifier (the
    device arms).  Returns stats AFTER asserting the invariant: every
    admitted envelope committed exactly once, every shed answered
    typed."""
    import tempfile
    import threading

    from fabric_mod_tpu.orderer import (Broadcast,
                                        ResourceExhaustedError)

    knobs = {"FABRIC_MOD_TPU_SUBMIT_QUEUE": str(queue_cap)} if gated \
        else {}
    if staged > 0:
        knobs["FABRIC_MOD_TPU_STAGED_BROADCAST"] = str(staged)
    saved = {k: os.environ.pop(k, None)
             for k in ("FABRIC_MOD_TPU_SUBMIT_QUEUE",
                       "FABRIC_MOD_TPU_INGRESS_RATE",
                       "FABRIC_MOD_TPU_SHED_LAT_S",
                       "FABRIC_MOD_TPU_STAGED_BROADCAST")}
    os.environ.update(knobs)
    try:
        with tempfile.TemporaryDirectory(dir=root) as tmp:
            registrar, support = _storm_channel(tmp, mat, verify_many)
            if drain_delay_s > 0:
                # drain throttle: a bounded-rate ordering backend
                orig_write = support.writer.write_block

                def slow_write(block, _orig=orig_write):
                    time.sleep(drain_delay_s)
                    return _orig(block)
                support.writer.write_block = slow_write
            bcast = Broadcast(registrar)

            admitted, shed, errors = [], [], []
            latencies = []
            rec_lock = threading.Lock()
            stop_mon = threading.Event()
            max_depth = [0]

            def monitor():
                while not stop_mon.is_set():
                    q, _cap = support.chain.submit_queue_depth()
                    if q > max_depth[0]:
                        max_depth[0] = q
                    time.sleep(0.002)

            def client_main(my_envs):
                acc, sh, lat, errs = [], [], [], []
                for tx_id, env in my_envs:
                    t0 = time.perf_counter()
                    try:
                        bcast.submit(env)
                        lat.append(time.perf_counter() - t0)
                        acc.append(tx_id)
                    except ResourceExhaustedError as e:
                        sh.append((tx_id, e.reason))
                    except Exception as e:  # noqa: BLE001 — gate fails
                        errs.append((tx_id, repr(e)))
                with rec_lock:
                    admitted.extend(acc)
                    shed.extend(sh)
                    latencies.extend(lat)
                    errors.extend(errs)

            threads = [threading.Thread(target=client_main, args=(ce,),
                                        daemon=True)
                       for ce in envs_by_client]
            mon = threading.Thread(target=monitor, daemon=True)
            mon.start()
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            burst_wall = time.perf_counter() - t0

            # drain: EXACTLY the admitted count must land (the threads
            # have joined, so the target is known); the deadline turns
            # a lost tx into a loud invariant failure below instead of
            # a hang
            want = len(admitted)
            deadline = time.time() + max(
                120.0, 2 * want * drain_delay_s + 30.0)
            store = support.store
            while time.time() < deadline:
                landed = sum(
                    len(store.get_block_by_number(i).data.data)
                    for i in range(1, store.height))
                if landed >= want:
                    break
                time.sleep(0.02)
            drain_wall = time.perf_counter() - t0 - burst_wall
            stop_mon.set()
            mon.join(timeout=2)
            committed = _storm_committed_tx_ids(support.store)
            bcast.close()          # stop any staging lanes
            registrar.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # -- the consistency gate (before ANY rate is reported) --------------
    if errors:
        raise AssertionError(
            f"storm: {len(errors)} untyped failures, e.g. {errors[:3]}")
    from collections import Counter
    commit_counts = Counter(committed)
    dupes = {t: c for t, c in commit_counts.items() if c > 1}
    if dupes:
        raise AssertionError(f"storm: double-committed {dupes}")
    lost = set(admitted) - set(committed)
    if lost:
        raise AssertionError(
            f"storm: {len(lost)} admitted-then-LOST txs, "
            f"e.g. {sorted(lost)[:5]}")
    ghost = set(committed) - set(admitted)
    if ghost:
        raise AssertionError(
            f"storm: {len(ghost)} committed-but-shed txs {sorted(ghost)[:5]}")
    total = len(admitted) + len(shed)
    lat_sorted = sorted(latencies)
    p99 = lat_sorted[int(0.99 * (len(lat_sorted) - 1))] if lat_sorted \
        else 0.0
    shed_reasons = {}
    for _t, reason in shed:
        shed_reasons[reason] = shed_reasons.get(reason, 0) + 1
    wall = burst_wall + max(0.0, drain_wall)
    return {
        "accepted": len(admitted),
        "shed": len(shed),
        "shed_fraction": round(len(shed) / total, 4) if total else 0.0,
        "shed_reasons": shed_reasons,
        "accepted_tx_per_sec": round(len(admitted) / burst_wall, 1),
        # submit-to-committed: the honest throughput once the drain
        # tail (the buffered backlog) is paid
        "sustained_tx_per_sec": round(len(admitted) / wall, 1),
        "p99_admission_ms": round(p99 * 1000, 2),
        "max_queue_depth": max_depth[0],
        "burst_wall_s": round(burst_wall, 2),
        "drain_wall_s": round(max(0.0, drain_wall), 2),
    }


def _multichannel_world(n_channels: int, n_blocks: int,
                        txs_per_block: int):
    """N per-channel block streams over ONE shared 3-org world: every
    4th tx under-endorsed (1-of-3 < 2 -> ENDORSEMENT_POLICY_FAILURE)
    so the differential's flags carry signal, per-channel key content
    so fingerprints differ across channels.  Returns (streams,
    make_target): streams[cid] -> encoded blocks; make_target builds
    a fresh (validator, ledger) commit target for `cid` against
    `verifier` under `root`."""
    from fabric_mod_tpu.ledger import KvLedger
    from fabric_mod_tpu.peer import (TxValidator,
                                     ValidationInfoProvider,
                                     ValidatorCommitTarget)
    from fabric_mod_tpu.policy import ApplicationPolicyEvaluator
    from fabric_mod_tpu.utils.fixtures import make_channel_stream

    _csp, _cas, mgr, signers, cc_policy = _three_org_world()
    log(f"multichannel: signing {n_channels} channels x {n_blocks} "
        f"blocks x {txs_per_block} txs ...")
    # the shared oracle stream generator (utils/fixtures.py): bench
    # and tests/test_sharding.py gate against the SAME streams
    streams = {f"mc{c}": make_channel_stream(
        signers, f"mc{c}", n_blocks, txs_per_block)
        for c in range(n_channels)}

    def make_target(cid, verifier, root):
        led = KvLedger(root, cid)
        validator = TxValidator(
            cid, mgr, ApplicationPolicyEvaluator(mgr), verifier,
            ValidationInfoProvider(cc_policy),
            tx_id_exists=led.tx_id_exists)
        return ValidatorCommitTarget(validator, led)
    return streams, make_target


def _axis3(lo, mid, hi):
    """>=3 distinct monotone points per axis (collapses gracefully
    when the caller passes a tiny maximum)."""
    return sorted({lo, mid, hi})


def measure_multichannel(n_slices: int, n_channels: int, n_peers: int,
                         n_blocks: int, txs_per_block: int,
                         use_sw: bool) -> dict:
    """The channel-sharded scale curve: N channels placed on mesh
    slices by a ChannelShardRouter, blocks driven round-robin through
    the per-channel slice-pinned commit pipes while `peers` gossip-
    storm-style riders push small verifies through the SHARED
    cross-channel service (small channels riding big channels' flush
    windows — the whole point of sharing the front door).

    Per point, BEFORE any rate is reported, every channel's per-block
    txflags and final state fingerprint are asserted BIT-IDENTICAL to
    an independent unsharded synchronous run of the same stream — the
    sharded path may only move work, never change a verdict.

    The sweep holds a base point and varies each axis (slices,
    channels, peers) through >=3 values; the JSON carries the full
    point list: aggregate committed tx/s per (slices x channels x
    peers) — the scale curve MULTICHIP_r*.json records."""
    import tempfile
    import threading

    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil
    from fabric_mod_tpu.sharding import ChannelShardRouter
    from fabric_mod_tpu.utils.fixtures import make_verify_items

    streams, make_target = _multichannel_world(
        n_channels, n_blocks, txs_per_block)
    cids = list(streams)
    csp = SwCSP()

    if use_sw:
        from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
        make_verifier = lambda mesh: FakeBatchVerifier(csp)
        meshes_for = lambda s: None
    else:
        import jax

        from fabric_mod_tpu.bccsp.tpu import TpuVerifier
        from fabric_mod_tpu.parallel import slice_meshes
        n_dev = len(jax.devices())
        # cache off: points replay identical streams, and the curve
        # must measure placement, not the memo LRU
        make_verifier = lambda mesh: TpuVerifier(mesh=mesh,
                                                 cache_size=0)

        def meshes_for(s):
            # a slice count the device set cannot split evenly runs
            # UNMESHED slices (distinct programs, whole device set
            # visible to each) — recorded per point as meshed=False
            return slice_meshes(s) if s <= n_dev and n_dev % s == 0 \
                else None

    # -- the independent-unsharded oracle (and serial baseline rate) -----
    from fabric_mod_tpu.utils.fixtures import independent_baseline
    with tempfile.TemporaryDirectory(prefix="fmt_mc_base_") as tmp:
        if not use_sw:
            # device arm: an untimed warm baseline pass first, so the
            # cold whole-mesh compile never lands in serial_secs — the
            # sweep points each get a warm pass below, and a compile-
            # inflated denominator would bias vs_baseline sharded-ward
            independent_baseline(
                streams,
                lambda cid: make_target(cid, make_verifier(None),
                                        f"{tmp}/warm-{cid}"))
        baseline = independent_baseline(
            streams,
            lambda cid: make_target(cid, make_verifier(None),
                                    f"{tmp}/{cid}"))
    serial_secs = {cid: b[2] for cid, b in baseline.items()}
    distinct = {f for flags, _fp, _dt in baseline.values()
                for blk in flags for f in blk}
    if distinct == {0}:
        raise AssertionError(
            "multichannel streams produced only VALID flags — the "
            "under-endorsed lanes the oracle relies on are gone")

    rider_items, rider_expect = make_verify_items(8, invalid_every=3,
                                                  seed=b"mc-rider")

    def run_point(s, c, p, root) -> dict:
        point_cids = cids[:c]
        c = len(point_cids)                # the committed truth: the
        #                                    axis value may exceed the
        #                                    generated channel set
        router = ChannelShardRouter(
            n_slices=s, meshes=meshes_for(s), depth=2,
            verifier_factory=lambda i, mesh: make_verifier(mesh))
        stop = threading.Event()
        riders = []
        try:
            targets = {}
            for cid in point_cids:
                handle = router.add_channel(cid)
                targets[cid] = make_target(cid, handle,
                                           f"{root}/{cid}")
                router.bind_target(cid, targets[cid])
            rider_counts = [0] * p
            rider_errs = []

            def rider(k):
                i = k
                while not stop.is_set():
                    cid = point_cids[i % len(point_cids)]
                    try:
                        # timeout well under the finally's join budget
                        # so a wedged rider is observed dead, never
                        # left racing router.close()
                        got = router.service.verify_many_for(
                            cid, rider_items, timeout=30)
                    except Exception as e:  # noqa: BLE001 — gate fails
                        # a dying rider must FAIL the point, not
                        # silently deflate its rider rate: the curve
                        # claims the shared front door carried this
                        # traffic
                        rider_errs.append(f"rider {k} died: {e!r}")
                        return
                    if got != rider_expect:
                        rider_errs.append(
                            f"rider {k} verdicts wrong")
                        return
                    rider_counts[k] += 1
                    i += 1
                    # gossip-cadence pacing: riders model redelivery
                    # traffic, not a busy-spin that starves the GIL
                    stop.wait(0.02)

            riders = [threading.Thread(target=rider, args=(k,),
                                       daemon=True) for k in range(p)]
            for t in riders:
                t.start()
            t0 = time.perf_counter()
            for n in range(n_blocks):
                for cid in point_cids:
                    router.submit_block(
                        cid, m.Block.decode(streams[cid][n]))
            if not router.flush(timeout_s=3600):
                raise AssertionError("multichannel flush timed out")
            dt = time.perf_counter() - t0
            if rider_errs:
                raise AssertionError(rider_errs[0])
            # the per-point acceptance gate, BEFORE any rate
            for cid in point_cids:
                led = targets[cid].ledger
                got = [list(protoutil.block_txflags(
                    led.get_block_by_number(nb)))
                    for nb in range(led.height)]
                if got != baseline[cid][0]:
                    raise AssertionError(
                        f"sharded txflags diverge from the "
                        f"independent run on {cid}")
                if led.state_fingerprint() != baseline[cid][1]:
                    raise AssertionError(
                        f"sharded state fingerprint diverges on {cid}")
            txs = c * n_blocks * txs_per_block
            return {
                "slices": s, "channels": c, "peers": p,
                "tx_per_sec": round(txs / dt, 1),
                "rider_verifies_per_sec": round(
                    sum(rider_counts) * len(rider_items) / dt, 1),
                "meshed": meshes_for(s) is not None,
            }
        finally:
            # riders stop BEFORE the router teardown on every exit
            # path — the join budget exceeds the riders' 30 s verify
            # deadline, so even a wedged rider fails typed and exits
            # before the service it rides is closed under it
            stop.set()
            for t in riders:
                t.join(timeout=90)
            router.close()

    # every axis clamped to the user-requested cap (and the channel
    # axis additionally to the GENERATED channel set): a sweep must
    # never run a point the caller asked to exclude — on the device
    # arm an unrequested slice count would also pay an extra
    # per-slice-shape compile.  Small caps collapse below 3 values;
    # the recorded-curve acceptance runs the defaults, which don't.
    s_axis = sorted({min(v, n_slices) for v in
                     (1, max(1, n_slices // 2), max(1, n_slices))})
    c_axis = sorted({min(v, len(cids)) for v in
                     (1, max(2, n_channels // 2), max(1, n_channels))})
    p_axis = sorted({min(v, n_peers) for v in
                     (0, n_peers // 4, n_peers)})
    s_mid, c_mid, p_mid = s_axis[len(s_axis) // 2], \
        c_axis[len(c_axis) // 2], p_axis[len(p_axis) // 2]
    sweep = []
    for s in s_axis:
        sweep.append((s, c_mid, p_mid))
    for c in c_axis:
        sweep.append((s_mid, c, p_mid))
    for p in p_axis:
        sweep.append((s_mid, c_mid, p))
    sweep = sorted(set(sweep))

    points = []
    with tempfile.TemporaryDirectory(prefix="fmt_mc_") as tmp:
        for k, (s, c, p) in enumerate(sweep):
            if not use_sw:
                # device arm: one untimed pass per point absorbs the
                # per-slice-shape XLA compiles, then the timed pass
                run_point(s, c, p, f"{tmp}/warm{k}")
            pt = run_point(s, c, p, f"{tmp}/pt{k}")
            log(f"multichannel point {pt}")
            points.append(pt)

    best = max(points, key=lambda pt: pt["tx_per_sec"])
    # serial-independent rate over the SAME channel set as the best
    # point: the honest scaling denominator (what N separate
    # unsharded processes did, one after another, on this host)
    best_cids = cids[:best["channels"]]
    serial_rate = (best["channels"] * n_blocks * txs_per_block
                   / max(sum(serial_secs[cid] for cid in best_cids),
                         1e-9))
    return {
        "points": points,
        "best": best,
        "agg_tx_per_sec": best["tx_per_sec"],
        "serial_independent_tx_per_sec": round(serial_rate, 1),
        "axes": {"slices": s_axis, "channels": c_axis,
                 "peers": p_axis},
        "blocks_per_channel": n_blocks,
        "txs_per_block": txs_per_block,
        "distinct_flags": sorted(distinct),
        "sharded_vs_independent_identical": True,   # gated per point
        "verifier": "sw" if use_sw else "device",
    }


def measure_soak(seed, n_events, kinds=None) -> dict:
    """Sustained soak-under-churn (host-only): the full SoakHarness
    run — mixed x509+idemix traffic across channels while the seeded
    ChurnPlan joins peers, revokes ACLs, reshapes batches, changes the
    consenter set, kills leaders, hard-crashes + rejoins peers on
    their durable dirs, restarts orderers from their WALs, and
    installs/heals network partitions, with the background fault plan
    permanently armed.  Every invariant (fingerprint convergence
    within the recovery window, admitted => committed exactly once,
    no thread leaks, throughput recovery) gates BEFORE any rate is
    reported; the JSON carries per-event-kind recovery times and the
    replayable seed + schedule.  `kinds` (--soak-kinds, comma list)
    restricts the plan's event catalog."""
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.soak import SoakConfig, SoakHarness
    kind_tuple = None
    if kinds:
        from fabric_mod_tpu.soak import EVENT_KINDS
        kind_tuple = tuple(k.strip() for k in kinds.split(",")
                           if k.strip())
        bad = [k for k in kind_tuple if k not in EVENT_KINDS]
        if bad:
            raise SystemExit(f"--soak-kinds: unknown kind(s) {bad}; "
                             f"catalog: {', '.join(EVENT_KINDS)}")
    cfg = SoakConfig(seed=seed, n_events=n_events, kinds=kind_tuple)
    log(f"soak: seed {cfg.seed}, {cfg.n_events} events, "
        f"{cfg.n_channels} channels, {cfg.n_peers} peers")
    harness = SoakHarness(cfg)
    log(f"soak schedule: {harness.plan.to_json()}")
    # armed: the report carries the run-wide stage attribution, and a
    # SoakError carries the flight-recorder tail next to its replay
    # seed + schedule
    with tracing.active():
        rep = harness.run()
    log(f"soak: PASS — {rep['x509_txs']} x509 + {rep['idemix_txs']} "
        f"idemix txs over {rep['wall_secs']}s, "
        f"{rep['fault_fires']} background faults fired")
    return rep


def _fanout_chain(channel_id: str, n_blocks: int, config_at: int):
    """Deterministic committed chain for the fan-out A/B: endorser txs
    with chaincode events (the filtered projection has real work), a
    multi-action tx per block (exercising the batch scanner's
    fallback), and one mid-chain CONFIG block (exercising the forced
    session re-check)."""
    from fabric_mod_tpu.protos import messages as m
    from fabric_mod_tpu.protos import protoutil

    def tx_bytes(txid, nactions=1):
        actions = []
        for _ in range(nactions):
            ev = m.ChaincodeEvent(chaincode_id="cc", tx_id=txid,
                                  event_name="moved",
                                  payload=b"p" * 64).encode()
            cca = m.ChaincodeAction(results=b"rw" * 32, events=ev)
            prp = m.ProposalResponsePayload(proposal_hash=b"h" * 32,
                                            extension=cca.encode())
            cap = m.ChaincodeActionPayload(
                chaincode_proposal_payload=b"cpp",
                action=m.ChaincodeEndorsedAction(
                    proposal_response_payload=prp.encode(),
                    endorsements=[m.Endorsement(endorser=b"e" * 64,
                                                signature=b"s" * 70)]))
        actions.append(m.TransactionAction(header=b"sh",
                                           payload=cap.encode()))
        return m.Transaction(actions=actions).encode()

    def env(txid, htype=None, data=b""):
        htype = (m.HeaderType.ENDORSER_TRANSACTION
                 if htype is None else htype)
        ch = protoutil.make_channel_header(htype, channel_id, tx_id=txid)
        sh = protoutil.make_signature_header(b"creator", b"\x00" * 24)
        payload = protoutil.make_payload(ch, sh, data)
        return m.Envelope(payload=payload.encode(), signature=b"sig")

    blocks = []
    for b in range(n_blocks):
        if b == config_at:
            envs = [env(f"cfg-{b}", htype=m.HeaderType.CONFIG,
                        data=b"new-config")]
        else:
            envs = [env(f"t{b}-{i}", data=tx_bytes(f"t{b}-{i}"))
                    for i in range(3)]
            envs.append(env(f"t{b}-multi",
                            data=tx_bytes(f"t{b}-multi", nactions=2)))
        blk = protoutil.new_block(b, b"\x00" * 32, envs)
        protoutil.set_block_txflags(
            blk, bytes([m.TxValidationCode.VALID] * len(envs)))
        blocks.append(blk)
    return blocks


class _RevealLedger:
    """ledger-shaped replay source: the pre-built chain revealed block
    by block (the sustained commit traffic), identically for both
    arms — the determinism the byte-identity gate needs."""

    def __init__(self, blocks):
        import threading
        self._blocks = blocks
        self._revealed = 0
        self.height_changed = threading.Condition()

    @property
    def height(self):
        return self._revealed

    def get_block_by_number(self, num):
        if 0 <= num < self._revealed:
            return self._blocks[num]
        return None

    def reveal(self):
        self._revealed += 1
        with self.height_changed:
            self.height_changed.notify_all()


def measure_deliverfanout(n_subscribers: int) -> dict:
    """Shared fan-out vs per-stream materialization (host-only A/B).

    Per swept subscriber count: the SAME revealed-block-by-block chain
    drives (a) the shared FanoutEngine with N mixed full/filtered
    subscribers consuming ring frames over a small worker pool, and
    (b) the historical per-stream arm (every stream re-projects +
    re-encodes every block, batch=False) on a bounded sample of
    streams (the arm's blocks*subs/s is size-invariant — each frame
    costs a full materialization regardless of N).

    Gates, per point, BEFORE any rate is reported:
      * byte-identity — every subscriber's frame-sequence digest equals
        the per-stream arm's digest for its form;
      * one materialization + one encode per (block, form), zero
        ring fallbacks;
      * the batched session ACL fired exactly once per (group, key).
    """
    import hashlib
    import threading as th
    import time as _t

    from fabric_mod_tpu.peer.fanout import FanoutEngine, encode_frame
    from fabric_mod_tpu.protos.protoutil import SignedData

    channel_id = "bench-fanout"
    n_groups = 4

    class _SeqAcl:
        def __init__(self):
            self.seq = 0
            self.checks = 0

        def config_sequence(self):
            return self.seq

        def check_acl(self, resource, sds):
            self.checks += 1

    points = sorted({max(8, n_subscribers // 100),
                     max(32, n_subscribers // 10), n_subscribers})
    results = []
    for n_subs in points:
        n_blocks = max(6, min(24, 200_000 // max(1, n_subs)))
        config_at = n_blocks // 2
        if n_subs >= 100_000:
            # the 100k top point replays a chain that arrived over the
            # DISSEMINATION RELAY (read back from a non-leader peer's
            # ledger) — the fan-out engine's input provably composes
            # with the tree path, not only a leader's own pull.  Real
            # committed blocks carry no mid-chain config tx; the
            # pacer's sequence advance still exercises the standing
            # session re-check (gate 3's lower bound).
            cid, blocks = _relayed_chain(n_blocks)
        else:
            cid, blocks = channel_id, _fanout_chain(
                channel_id, n_blocks, config_at)

        # reference digests: the per-stream sender's exact output
        refs = {}
        for form in ("full", "filtered"):
            h = hashlib.sha256()
            for blk in blocks:
                h.update(encode_frame(cid, form, blk,
                                      batch=False))
            refs[form] = h.hexdigest()

        # -- shared arm ------------------------------------------------
        led = _RevealLedger(blocks)
        acl = _SeqAcl()
        eng = FanoutEngine(cid, led, acl,
                           ring_size=max(128, n_blocks))
        forms = ["full" if i % 2 else "filtered"
                 for i in range(n_subs)]
        sessions = [eng.acl_groups.join(
            "event/Block" if forms[i] == "full"
            else "event/FilteredBlock",
            SignedData(data=b"d", identity=b"id%d" % (i % n_groups),
                       signature=b"s"),
            acl.seq) for i in range(n_subs)]
        for f in forms:
            eng.attach(f)
        digests = [hashlib.sha256() for _ in range(n_subs)]
        nexts = [0] * n_subs
        n_workers = min(8, n_subs)
        slices = [list(range(w, n_subs, n_workers))
                  for w in range(n_workers)]
        errors = []

        def run_slice(idx):
            try:
                waiter = eng.notifier.waiter()
                pending = set(slices[idx])
                while pending:
                    progress = False
                    for s in list(pending):
                        while nexts[s] < n_blocks:
                            fr = eng.get_frame(forms[s], nexts[s])
                            if fr is None:
                                break
                            if fr.is_config:
                                sessions[s].recheck(
                                    force=True, config_mark=fr.num)
                            else:
                                sessions[s].recheck()
                            digests[s].update(fr.payload)
                            nexts[s] += 1
                            progress = True
                        if nexts[s] >= n_blocks:
                            pending.discard(s)
                    if pending and not progress:
                        low = min(nexts[s] for s in pending)
                        if eng.notifier.wait_above(
                                low, waiter, timeout_s=30.0) == "timeout":
                            raise RuntimeError("fanout stall")
                eng.notifier.release(waiter)
            except Exception as e:  # worker failure must fail the gate
                errors.append(e)

        def pace():
            for b in range(n_blocks):
                if b == config_at:
                    acl.seq += 1      # the config commit advances it
                led.reveal()
                _t.sleep(0.001)       # sustained traffic, not a batch

        workers = [th.Thread(target=run_slice, args=(w,), daemon=True)
                   for w in range(n_workers)]
        t0 = _t.perf_counter()
        pacer = th.Thread(target=pace, daemon=True)
        pacer.start()
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=600)
        shared_s = _t.perf_counter() - t0
        pacer.join(timeout=60)
        for f in forms:
            eng.detach(f)
        eng.close()
        if errors:
            raise AssertionError(f"fanout worker failed: {errors[0]}")

        # gate 1: every stream's frame sequence bit-identical to the
        # per-stream arm's output
        for i in range(n_subs):
            assert digests[i].hexdigest() == refs[forms[i]], \
                f"stream {i} ({forms[i]}) diverged from the " \
                f"per-stream materialization at {n_subs} subscribers"
        # gate 2: one materialization + one encode per (block, form),
        # no slow-path fallbacks
        for form in ("full", "filtered"):
            st = eng.stats[form]
            assert st["materialized"] == n_blocks, st
            assert st["encoded"] == n_blocks, st
            assert st["fallbacks"] == 0, st
        # gate 3: the batched session re-check fired once per (group,
        # key) — at most two keys exist per config commit (the
        # standing sequence-advance recheck and the forced config-mark
        # recheck; which members hit first is timing), so N streams
        # produce at most 2 evaluations per group, never one per
        # stream
        n_group_objs = len(eng.acl_groups._groups)
        assert n_group_objs <= acl.checks <= 2 * n_group_objs, \
            (acl.checks, n_group_objs)

        # -- per-stream arm (bounded sample; rate is size-invariant) --
        sample = min(n_subs, 128)
        t0 = _t.perf_counter()
        h_check = [hashlib.sha256() for _ in range(sample)]
        for i in range(sample):
            form = forms[i]
            for blk in blocks:
                h_check[i].update(encode_frame(cid, form, blk,
                                               batch=False))
        per_stream_s = _t.perf_counter() - t0
        for i in range(sample):
            assert h_check[i].hexdigest() == refs[forms[i]]

        shared_rate = n_blocks * n_subs / shared_s
        per_rate = n_blocks * sample / per_stream_s
        log(f"deliverfanout: {n_subs} subs x {n_blocks} blocks — "
            f"shared {shared_rate:,.0f} vs per-stream "
            f"{per_rate:,.0f} blocks*subs/s "
            f"({shared_rate / per_rate:.1f}x, sample {sample})")
        results.append({
            "subscribers": n_subs, "blocks": n_blocks,
            "shared_blocks_subs_per_sec": round(shared_rate, 1),
            "per_stream_blocks_subs_per_sec": round(per_rate, 1),
            "per_stream_sample": sample,
            "identical": True,
            "acl_group_checks": acl.checks,
        })
    top = results[-1]
    ratio = (top["shared_blocks_subs_per_sec"]
             / top["per_stream_blocks_subs_per_sec"])
    assert ratio > 1.0, \
        f"shared fan-out did not beat per-stream at the top point " \
        f"({ratio:.2f}x)"
    return {"points": results, "top": top, "ratio": ratio}


def _build_relay_world(net, fabric, root_dir, n_peers):
    """`n_peers` relay-mode gossip peers over `net`'s channel, wired
    for the dissemination A/B: per-peer ledger + channel + GossipNode
    + RelayService + GossipService, leadership pinned statically to
    the min-(PKI-ID, endpoint) peer — the SAME peer the dynamic
    election and RelayService._elected_leader both derive, so the
    static pin changes nothing about who roots the tree.

    Membership and the tree PARENT's identity are seeded directly
    into discovery/the identity mapper instead of running alive
    broadcast rounds: at 128 peers the N^2 signed heartbeats plus
    N^2 cert validations are minutes of pure-python ECDSA on the
    fallback CSP — warm-up cost, not the dissemination under test.
    The relay path itself stays fully signed and fully verified.

    Returns (peers, leader_i, stream_calls): each peer is a dict
    (node/relay/svc/tap/mgr/channel), `stream_calls` counts deliver-
    source creations — the orderer-stream-economy gate reads its
    length."""
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    from fabric_mod_tpu.dissemination import RelayService
    from fabric_mod_tpu.gossip import GossipNode, GossipService
    from fabric_mod_tpu.ledger.kvledger import LedgerManager
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity
    from fabric_mod_tpu.orderer import DeliverService
    from fabric_mod_tpu.peer.channel import Channel
    from fabric_mod_tpu.protos import messages as m

    _, config = config_from_block(net.genesis_block)
    orgs = ("Org1", "Org2", "Org3")
    peers = []
    for i in range(n_peers):
        org = orgs[i % len(orgs)]
        csp = net.csp
        mgr = LedgerManager(os.path.join(root_dir, f"relay{i}"))
        ledger = mgr.create_or_open(net.channel_id)
        channel = Channel(net.channel_id, ledger,
                          FakeBatchVerifier(csp),
                          Bundle(net.channel_id, config, csp), csp)
        if ledger.height == 0:
            channel.init_from_genesis(net.genesis_block)
        cert, key = net.cas[org].issue(f"dis{i}.{org.lower()}", org,
                                       ous=["peer"])
        signer = SigningIdentity(org, cert, calib.key_pem(key), csp)
        node = GossipNode(f"dis{i}:7051", signer, channel, fabric)
        relay = RelayService(node)
        tap = []
        relay.relay.on_deliver = \
            lambda num, frame, acc=tap: acc.append((num, frame))
        peers.append({"node": node, "relay": relay, "tap": tap,
                      "mgr": mgr, "channel": channel})
    for p in peers:
        node = p["node"]
        for other in peers:
            onode = other["node"]
            if onode is node:
                continue
            node.discovery.handle_alive(onode.pki_id, m.AliveMessage(
                membership=m.GossipMember(endpoint=onode.endpoint,
                                          pki_id=onode.pki_id),
                timestamp=m.PeerTime(inc_num=1, seq_num=1)))
    leader_i = min(range(n_peers),
                   key=lambda i: (peers[i]["node"].pki_id,
                                  peers[i]["node"].endpoint))
    by_ep = {p["node"].endpoint: p["node"] for p in peers}
    tree = peers[leader_i]["relay"].tree()
    for p in peers:
        parent_ep = tree.parent(p["node"].endpoint)
        if parent_ep is not None:
            # the only inbound envelope signer this peer must verify
            p["node"].mapper.put(by_ep[parent_ep]._identity)
    stream_calls = []

    def factory():
        stream_calls.append(1)
        return DeliverService(net.support)

    for i, p in enumerate(peers):
        p["svc"] = GossipService(p["node"], factory,
                                 static_leader=(i == leader_i),
                                 relay=p["relay"])
        # long anti-entropy cadence, pinned BEFORE svc.start()'s
        # idempotent re-start: the quiescent-channel pull hellos are
        # sqrt-N signed messages per peer per tick — at 128 peers
        # that storm measures the fallback CSP, not the relay.  The
        # relay's explicit request_gap prod stays live for repairs.
        p["node"].state.start(interval_s=120.0)
    return peers, leader_i, stream_calls


def _stop_relay_world(peers, leader_i):
    # root first, so no push races the children's teardown
    peers[leader_i]["svc"].stop()
    for i, p in enumerate(peers):
        if i != leader_i:
            p["svc"].stop()
    for p in peers:
        p["node"].stop()
        p["mgr"].close()


def _relayed_chain(n_blocks: int) -> tuple:
    """(channel_id, blocks) for the fan-out sweep's TOP point, read
    back from a relayed NON-leader peer's ledger: a 4-peer
    dissemination tree carries ONE orderer deliver stream to every
    peer, so the chain the 100k-subscriber fan-out replays provably
    arrived over the relay path, not a per-peer pull."""
    import tempfile
    import time as _t

    from fabric_mod_tpu.e2e import Network
    from fabric_mod_tpu.gossip import InProcNetwork

    tmp = tempfile.mkdtemp(prefix="fmt_dissem_chain_")
    net = Network(tmp, batch_timeout="50ms", max_message_count=4)
    try:
        for i in range(4 * n_blocks):
            net.invoke([b"put", b"fk%d" % i, b"fv%d" % i])
        net.pump_committed(4 * n_blocks)
        target_h = net.support.store.height
        assert target_h - 1 >= n_blocks, target_h
        fabric = InProcNetwork()
        peers, leader_i, streams = _build_relay_world(net, fabric,
                                                      tmp, 4)
        try:
            for i, p in enumerate(peers):
                if i != leader_i:
                    p["svc"].start()
            peers[leader_i]["svc"].start()
            deadline = _t.perf_counter() + 120.0
            while _t.perf_counter() < deadline:
                if all(p["channel"].ledger.height >= target_h
                       for p in peers):
                    break
                _t.sleep(0.005)
            src = peers[next(i for i in range(len(peers))
                             if i != leader_i)]
            assert src["channel"].ledger.height >= target_h, \
                [p["channel"].ledger.height for p in peers]
            assert len(streams) == 1, len(streams)
            got = {num for num, _ in src["tap"]}
            assert got == set(range(1, target_h)), sorted(got)
            blocks = [src["channel"].ledger.get_block_by_number(num)
                      for num in range(1, 1 + n_blocks)]
        finally:
            _stop_relay_world(peers, leader_i)
        return net.channel_id, blocks
    finally:
        net.close()


def measure_dissemination(n_peers: int) -> dict:
    """Tree relay vs per-peer orderer pull (host-only A/B).

    Per swept peer count, the SAME pre-committed orderer chain drives
    (a) relay mode — ONE gossip leader pulls the deliver stream and
    the degree-d dissemination tree carries each frame to every other
    peer over the signed gossip comm layer — and (b) all-pull mode —
    every peer dials its own DeliverClient (the pre-forest cost
    model).

    Gates, per point, BEFORE any rate is reported:
      * byte-identity — every relayed frame equals the frame a DIRECT
        orderer pull produces on a peer (the all-pull arm's committed
        ledger is the reference encoder — peer commit sets the
        tx-flags metadata, so the orderer's raw store is NOT the
        right oracle), and every non-leader received the WHOLE chain
        through the tree;
      * convergence — one state fingerprint across all relay-mode
        peers, equal to the all-pull arm's;
      * stream economy — the orderer served exactly ONE deliver
        stream for the whole relay arm (== the number of leaders,
        the forest's headline contract) while the all-pull arm paid
        one stream per peer.
    """
    import tempfile
    import threading as th
    import time as _t

    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    from fabric_mod_tpu.e2e import Network
    from fabric_mod_tpu.gossip import InProcNetwork
    from fabric_mod_tpu.ledger.kvledger import LedgerManager
    from fabric_mod_tpu.orderer import DeliverService
    from fabric_mod_tpu.peer.channel import Channel
    from fabric_mod_tpu.peer.deliverclient import DeliverClient
    from fabric_mod_tpu.peer.fanout import encode_frame

    points = sorted({8, max(8, n_peers // 4), n_peers})
    results = []
    for n in points:
        tmp = tempfile.mkdtemp(prefix="fmt_dissem_bench_")
        net = Network(tmp, batch_timeout="50ms", max_message_count=12)
        try:
            # ~1 block per tx: each pure-python-signed invoke outlasts
            # the batch timeout, and the per-(block, peer) MCS verify
            # + commit (~60ms on the fallback CSP) is what the sweep
            # scales by — 6 blocks keeps the 128-peer point inside the
            # worker budget while still measuring a sustained stream
            n_txs = 6
            for i in range(n_txs):
                net.invoke([b"put", b"dk%d" % i, b"dv%d" % i])
            net.pump_committed(n_txs)
            target_h = net.support.store.height
            n_blocks = target_h - 1
            _, config = config_from_block(net.genesis_block)

            # -- all-pull arm FIRST: its committed ledgers are the
            # byte-identity gate's reference encoders ----------------
            pull_streams = []

            def pull_source():
                pull_streams.append(1)
                return DeliverService(net.support)

            pulls = []
            for i in range(n):
                mgr = LedgerManager(os.path.join(tmp, f"pull{i}"))
                ledger = mgr.create_or_open(net.channel_id)
                channel = Channel(net.channel_id, ledger,
                                  FakeBatchVerifier(net.csp),
                                  Bundle(net.channel_id, config,
                                         net.csp), net.csp)
                if ledger.height == 0:
                    channel.init_from_genesis(net.genesis_block)
                pulls.append({"mgr": mgr, "channel": channel,
                              "client": DeliverClient(channel,
                                                      pull_source())})

            def pull_main(c):
                try:
                    c.run(idle_timeout_s=30.0)
                except Exception:
                    pass    # stopped post-convergence; heights gate

            threads = [th.Thread(target=pull_main,
                                 args=(p["client"],), daemon=True)
                       for p in pulls]
            t0 = _t.perf_counter()
            for t in threads:
                t.start()
            deadline = t0 + 180.0 + 0.5 * n
            while _t.perf_counter() < deadline:
                if all(p["channel"].ledger.height >= target_h
                       for p in pulls):
                    break
                _t.sleep(0.002)
            pull_s = _t.perf_counter() - t0
            heights = [p["channel"].ledger.height for p in pulls]
            assert all(h >= target_h for h in heights), heights
            for p in pulls:
                p["client"].stop()
            for t in threads:
                t.join(timeout=30)
            assert len(pull_streams) == n, len(pull_streams)
            ref_ledger = pulls[0]["channel"].ledger
            refs = {num: encode_frame(net.channel_id, "full",
                                      ref_ledger.get_block_by_number(
                                          num))
                    for num in range(1, target_h)}
            pull_fps = {p["channel"].ledger.state_fingerprint()
                        for p in pulls}
            assert len(pull_fps) == 1, pull_fps

            # -- relay arm -------------------------------------------
            fabric = InProcNetwork()
            peers, leader_i, relay_streams = _build_relay_world(
                net, fabric, tmp, n)
            t0 = _t.perf_counter()
            for i, p in enumerate(peers):    # children accept BEFORE
                if i != leader_i:            # the root starts pushing
                    p["svc"].start()
            peers[leader_i]["svc"].start()
            deadline = t0 + 180.0 + 0.5 * n
            while _t.perf_counter() < deadline:
                if all(p["channel"].ledger.height >= target_h
                       for p in peers):
                    break
                _t.sleep(0.002)
            relay_s = _t.perf_counter() - t0
            heights = [p["channel"].ledger.height for p in peers]
            assert all(h >= target_h for h in heights), heights

            # gate: ONE orderer deliver stream served n peers
            assert len(relay_streams) == 1, len(relay_streams)
            # gate: every non-leader got the WHOLE chain through the
            # tree, every frame byte-identical to the direct pull
            for i, p in enumerate(peers):
                if i == leader_i:
                    assert not p["tap"]      # the root receives nothing
                    continue
                got = dict(p["tap"])
                assert set(got) == set(range(1, target_h)), \
                    (i, sorted(got))
                for num, frame in got.items():
                    assert frame == refs[num], \
                        f"peer {i} frame {num} diverged from the " \
                        f"direct-pull encoding"
            # gate: convergence, and equal to the all-pull arm's state
            relay_fps = {p["channel"].ledger.state_fingerprint()
                         for p in peers}
            assert relay_fps == pull_fps, (relay_fps, pull_fps)
            rstats = {k: sum(p["relay"].stats.get(k, 0) for p in peers)
                      for k in ("pushed", "forwarded", "received",
                                "dropped", "send_failures",
                                "repair_prods", "duplicates")}
            assert rstats["received"] > 0, rstats
            _stop_relay_world(peers, leader_i)
            for p in pulls:
                p["mgr"].close()

            relay_rate = n_blocks * n / relay_s
            pull_rate = n_blocks * n / pull_s
            log(f"dissemination: {n} peers x {n_blocks} blocks — "
                f"relay {relay_rate:,.0f} vs all-pull "
                f"{pull_rate:,.0f} blocks*peers/s "
                f"(streams 1 vs {n})")
            results.append({
                "peers": n, "blocks": n_blocks,
                "relay_blocks_peers_per_sec": round(relay_rate, 1),
                "pull_blocks_peers_per_sec": round(pull_rate, 1),
                "orderer_streams_relay": len(relay_streams),
                "orderer_streams_pull": len(pull_streams),
                "relay_stats": rstats,
                "identical": True,
            })
        finally:
            net.close()
    top = results[-1]
    return {"points": results, "top": top,
            "ratio": (top["relay_blocks_peers_per_sec"]
                      / top["pull_blocks_peers_per_sec"])}


def measure_broadcaststorm(n_txs: int, n_clients: int = 8,
                           staged_batch: int = 64,
                           storm_verifier: str = "sw") -> dict:
    """A/B overload burst through the REAL ingress (Broadcast ->
    SoloChain -> block store): gated arm (bounded queue + overload
    gate) vs the un-gated PR 6 baseline (blocking puts), same
    pre-signed envelopes, a write_block sleep shim pinning the drain
    rate to ~1/4 of the measured submit capacity (a 4x-overload
    burst).

    With `staged_batch` > 0, a SECOND pair runs the staged-vs-unstaged
    A/B with the drain UNTHROTTLED: the throttled pair is about what
    admission does when the backend is the cap, the staged pair about
    what coalescing does when INGRESS is the cap (the tentpole's
    claim) — a throttled staged arm would just re-measure the
    throttle.  `storm_verifier` picks the Writers batch verifier both
    staged arms AND the throttled pair dispatch through: "sw" (host
    ECDSA: per-item cost is flat, so staging shows its queueing win
    only) or "device" (ops/p256 batch verify: real batch economics —
    one padded dispatch per drain vs one per submission; buckets are
    pre-warmed so no arm times an XLA compile).  Every arm must pass
    the consistency gate — every admitted envelope commits exactly
    once, every shed is typed — before any rate is reported."""
    import tempfile

    requested_txs = n_txs
    n_txs = max(n_clients * 4, n_txs)
    if n_txs != requested_txs:
        log(f"storm: raising txs {requested_txs} -> {n_txs} "
            f"(floor: 4 per client x {n_clients} clients)")
    per_client = n_txs // n_clients
    max_message_count = 16

    # scrub ambient admission knobs for the WHOLE measurement,
    # calibration included — a user-set FABRIC_MOD_TPU_INGRESS_RATE
    # would shed calibration submits (crashing the metric) or skew
    # per_submit_s; each arm re-arms exactly what it measures
    scrubbed = {k: os.environ.pop(k, None)
                for k in ("FABRIC_MOD_TPU_SUBMIT_QUEUE",
                          "FABRIC_MOD_TPU_INGRESS_RATE",
                          "FABRIC_MOD_TPU_INGRESS_BURST",
                          "FABRIC_MOD_TPU_SHED_LAT_S",
                          "FABRIC_MOD_TPU_STAGED_BROADCAST")}
    vm_close = None
    try:
        with tempfile.TemporaryDirectory(prefix="fmt_storm_") as root:
            mat = _storm_material(n_clients, max_message_count, "100ms")
            clients = mat["clients"]
            vm = None
            if storm_verifier == "device":
                vm, vm_close = _storm_device_verifier(staged_batch)
            # calibration: the per-submit cost (Writers verify
            # dominates) sets the drain throttle for a ~4x overload
            from fabric_mod_tpu.orderer import Broadcast
            cal_registrar, _sup = _storm_channel(root + "/cal", mat, vm)
            cal_envs = _storm_envelopes(clients[:1], 16)
            cal_bcast = Broadcast(cal_registrar)
            t0 = time.perf_counter()
            for _ci, _tx, env in cal_envs:
                cal_bcast.submit(env)
            per_submit_s = max(
                1e-5, (time.perf_counter() - t0) / len(cal_envs))
            cal_bcast.close()
            cal_registrar.close()
            drain_delay_s = 4.0 * per_submit_s * max_message_count
            offered_rate = 1.0 / per_submit_s
            drain_rate = max_message_count / drain_delay_s
            log(f"storm calibration: {per_submit_s * 1000:.2f} "
                f"ms/submit -> offered ~{offered_rate:,.0f} tx/s, "
                f"drain capped at ~{drain_rate:,.0f} tx/s "
                f"({offered_rate / drain_rate:.1f}x overload)")

            log(f"storm: signing {n_clients} clients x {per_client} "
                f"envelopes ...")
            all_envs = _storm_envelopes(clients, per_client)
            by_client = [[(tx, env) for ci, tx, env in all_envs
                          if ci == i] for i in range(n_clients)]
            # cap well under the burst so the watermarks actually
            # engage at smoke scale too (>= one full block, <= burst/4)
            queue_cap = max(max_message_count,
                            min(4 * max_message_count,
                                len(all_envs) // 4))

            gated = _storm_arm(root, by_client, mat, True,
                               drain_delay_s, queue_cap, verify_many=vm)
            log(f"gated arm: {gated}")
            ungated = _storm_arm(root, by_client, mat, False,
                                 drain_delay_s, queue_cap, verify_many=vm)
            log(f"ungated arm: {ungated}")
            staged = unstaged = None
            if staged_batch > 0:
                # the staged A/B: same gated config, drain UNTHROTTLED
                # (ingress-limited — the resource staging changes)
                unstaged = _storm_arm(root, by_client, mat, True,
                                      0.0, queue_cap, verify_many=vm)
                log(f"unstaged ingress-limited arm: {unstaged}")
                staged = _storm_arm(root, by_client, mat, True,
                                    0.0, queue_cap,
                                    staged=staged_batch, verify_many=vm)
                log(f"staged arm (depth {staged_batch}): {staged}")
    finally:
        if vm_close is not None:
            vm_close()
        for k, v in scrubbed.items():
            if v is not None:
                os.environ[k] = v

    if gated["max_queue_depth"] > queue_cap:
        raise AssertionError(
            f"gated queue depth {gated['max_queue_depth']} exceeded "
            f"the {queue_cap} cap")
    if not gated["shed"]:
        raise AssertionError(
            "gated arm shed nothing under a 4x overload — the "
            "admission knobs did not engage")
    if ungated["shed"]:
        raise AssertionError("ungated arm shed — knob leakage")
    out = {
        "gated": gated,
        "ungated_baseline": ungated,
        "overload_x": round(offered_rate / drain_rate, 2),
        "queue_cap": queue_cap,
        "clients": n_clients,
        "txs": n_clients * per_client,
        "requested_txs": requested_txs,
        "storm_verifier": storm_verifier,
        "consistency": "admitted==committed exactly once, all arms",
    }
    if staged is not None:
        out["staged"] = staged
        out["unstaged_baseline"] = unstaged
        out["staged_batch"] = staged_batch
        out["staged_vs_unstaged"] = round(
            staged["sustained_tx_per_sec"]
            / max(unstaged["sustained_tx_per_sec"], 1e-9), 3)
    return out


# metrics that touch neither TpuVerifier nor ops/
HOST_ONLY_METRICS = ("marshal", "soak", "deliverfanout", "statescale",
                     "dissemination")


def _needs_device(args) -> bool:
    """Does this metric, as configured, drive the device verifier or
    ops/?  The A/B metrics with a `--*-verifier sw` arm are host-only
    in that arm."""
    if args.metric in HOST_ONLY_METRICS:
        return False
    backend = {"commitpipe": args.commitpipe_verifier,
               "multichannel": args.multichannel_verifier,
               "broadcaststorm": args.storm_verifier}
    return backend.get(args.metric, "device") != "sw"


def _emit(out: dict) -> None:
    """Print a metric's JSON line, labelled with the device this
    process ran on."""
    import jax
    devices = jax.devices()
    out["platform"] = devices[0].platform
    out["device_kind"] = devices[0].device_kind
    out["n_devices"] = len(devices)
    print(json.dumps(out), flush=True)


def run_worker(args) -> int:
    """The actual measurement; prints the final JSON line on stdout.
    With --trace-out, the whole run executes FMT_TRACE-armed and the
    span ring is exported as Chrome trace-event JSON (Perfetto-
    loadable; device dispatches as async slices) on the way out."""
    if _needs_device(args) and not args.cpu:
        import jax
        platform = jax.devices()[0].platform
        if platform != "tpu":
            log(f"[bench] --metric {args.metric} measures the device "
                f"path and jax reports platform {platform!r}: refusing "
                f"to print a CPU number under a device metric's name "
                f"(pass --cpu to measure the CPU on purpose)")
            return 2

    trace_mod = None
    if getattr(args, "trace_out", None):
        from fabric_mod_tpu.observability import tracing as trace_mod
        trace_mod.enable(True)
        trace_mod.install_compile_counter()
    try:
        return _worker_metric(args)
    finally:
        if trace_mod is not None:
            # best-effort: a bad --trace-out path must not mask the
            # metric's real result (or failure) from this finally
            try:
                d = os.path.dirname(os.path.abspath(args.trace_out))
                os.makedirs(d, exist_ok=True)
                n = trace_mod.export_chrome_trace(args.trace_out)
                log(f"[trace] {n} chrome trace events -> "
                    f"{args.trace_out} (xla compiles observed: "
                    f"{trace_mod.compile_count()})")
            except OSError as e:
                log(f"[trace] export to {args.trace_out} failed: {e}")


def _worker_metric(args) -> int:
    # A/B knobs for the pipelined front-end (all runtime-read env vars,
    # set before any fabric_mod_tpu construction):
    #   --mixed-add    -> affine-table mixed-addition ladder
    #   --memo-cache   -> verdict memo-cache size (0 disables)
    #   --inflight     -> in-flight dispatch window depth
    #   --precision    -> limb matmul precision (bench-scoped)
    if args.mixed_add is not None:
        os.environ["FABRIC_MOD_TPU_MIXED_ADD"] = str(args.mixed_add)
    if args.memo_cache is not None:
        os.environ["FABRIC_MOD_TPU_VERDICT_CACHE"] = str(args.memo_cache)
    if args.inflight is not None:
        os.environ["FABRIC_MOD_TPU_INFLIGHT"] = str(args.inflight)
    if args.precision == "high":
        from fabric_mod_tpu.ops import limbs9
        limbs9.set_precision_mode("high")

    if args.metric == "marshal":
        from fabric_mod_tpu.bccsp.sw import HAVE_CRYPTOGRAPHY
        vec_rate, loop_rate = measure_marshal(args.batch,
                                              max(3, args.reps))
        out = {
            "metric": f"marshal_items_per_sec_{args.batch}_bucket",
            "value": round(vec_rate, 1),
            "unit": "items/s",
            "vs_baseline": round(vec_rate / loop_rate, 3),
            # the per-item loop decodes DER through whichever scalar
            # parser the platform has — label it so ratios are only
            # compared like-for-like across rounds
            "baseline_der": "openssl" if HAVE_CRYPTOGRAPHY
                            else "pure-python-scalar",
        }
        _emit(out)
        return 0
    if args.metric == "diffverify":
        n, mismatches, extras = measure_diffverify(args.batch)
        out = {
            "metric": "mixed_ladder_verdict_differential",
            "value": float(n),
            "unit": "signatures",
            "vs_baseline": 1.0 if mismatches == 0 else 0.0,
            "mismatches": mismatches,
            **extras,
        }
        _emit(out)
        return 0 if mismatches == 0 else 1
    if args.metric == "hashverify":
        fused_rate, base_rate = measure_hashverify(
            args.batch, max(1, args.reps))
        out = {
            "metric": "fused_hashverify_verifies_per_sec",
            "value": round(fused_rate, 1),
            "unit": "verifies/s",
            "vs_baseline": round(fused_rate / base_rate, 3),
        }
        _emit(out)
        return 0
    if args.metric == "soak":
        # host-only (no device): the churn-soak integration run; the
        # invariants gate inside the harness — reaching here means
        # every convergence/exactly-once/leak/recovery check passed
        rep = measure_soak(args.soak_seed, args.soak_events,
                           kinds=args.soak_kinds)
        out = {
            "metric": "soak_churn_sustained_mixed_tx_per_sec",
            "value": rep["mixed_tx_per_sec"],
            "unit": "tx/s",
            # first soak record: no prior baseline config to compare
            # against — the gate is the invariants, not a ratio
            "vs_baseline": None,
            "x509_tx_per_sec": rep["x509_tx_per_sec"],
            "idemix_tx_per_sec": rep["idemix_tx_per_sec"],
            **{k: rep[k] for k in (
                "seed", "wall_secs", "x509_txs", "idemix_txs",
                "idemix_tamper_rejects", "audited_txs", "fault_fires",
                "submit_errors", "peers_final", "channels")},
            "recovery_s_by_kind": rep["recovery_s_by_kind"],
            "schedule": rep["schedule"],
        }
        if "stage_attribution" in rep:
            out["stage_attribution"] = rep["stage_attribution"]
        _emit(out)
        return 0
    if args.metric == "deliverfanout":
        # host-only (no device): the shared fan-out A/B; every rate is
        # gated by the byte-identity + once-per-(block, form) +
        # once-per-(group, key) assertions inside the measure
        extras = measure_deliverfanout(args.subscribers)
        out = {
            "metric": "deliverfanout_blocks_subscribers_per_sec",
            "value": extras["top"]["shared_blocks_subs_per_sec"],
            "unit": "blocks*subs/s",
            "vs_baseline": round(extras["ratio"], 3),
            "subscribers": extras["top"]["subscribers"],
            "points": extras["points"],
        }
        _emit(out)
        return 0
    if args.metric == "dissemination":
        # host-only (no device): the relay-vs-all-pull A/B; every rate
        # is gated by the frame byte-identity, all-peer fingerprint
        # convergence, and one-deliver-stream-per-leader assertions
        # inside the measure
        extras = measure_dissemination(
            max(8, args.peers if args.peers is not None else 128))
        out = {
            "metric": "dissemination_blocks_peers_per_sec",
            "value": extras["top"]["relay_blocks_peers_per_sec"],
            "unit": "blocks*peers/s",
            # relay vs the all-pull arm at the top point: on the CPU
            # fallback CSP the relay ALSO pays one pure-python
            # envelope verify per hop, so the honest headline here is
            # stream economy (1 orderer stream vs n), not the ratio
            "vs_baseline": round(extras["ratio"], 3),
            "peers": extras["top"]["peers"],
            "orderer_streams_relay":
                extras["top"]["orderer_streams_relay"],
            "orderer_streams_pull":
                extras["top"]["orderer_streams_pull"],
            "points": extras["points"],
        }
        _emit(out)
        return 0
    if args.metric == "statescale":
        # host-only (no device): the vectorized-MVCC state-scale
        # sweep; every rate is gated by the arm/size flag+fingerprint
        # identity, the incremental-vs-full fingerprint oracle, and
        # the zero-fallback assertion inside the measure
        sizes = sorted({int(s) for s in args.state_keys.split(",")
                        if s})
        extras = measure_statescale(sizes, durable=args.state_durable)
        top = extras["top"]
        out = {
            "metric": "statescale_committed_tx_per_sec_vector",
            "value": top["vector_tx_per_sec"],
            "unit": "tx/s",
            "vs_baseline": round(
                top["vector_tx_per_sec"]
                / max(top["generic_tx_per_sec"], 1e-9), 3),
            **extras,
        }
        _emit(out)
        return 0
    if args.metric == "broadcaststorm":
        # host-only (no device): the admission A/B under a 4x-overload
        # burst plus the staged-vs-unstaged ingress A/B.  The batch is
        # honored as requested up to a LOUD drain-tail wall-time cap
        # (the old silent min(batch, 512) hid that the requested scale
        # never ran); any cap is logged and recorded in the extras
        storm_cap = 4096
        n_storm = min(args.batch, storm_cap)
        if n_storm < args.batch:
            log(f"broadcaststorm: capping txs {args.batch} -> "
                f"{n_storm} (un-gated drain tail must fit the worker "
                f"budget)")
        n_clients = max(2, args.clients) if args.clients is not None \
            else 8
        staged_batch = args.staged_batch if args.staged_batch \
            is not None else 64
        extras = measure_broadcaststorm(n_storm, n_clients,
                                        staged_batch,
                                        args.storm_verifier)
        if n_storm < args.batch:
            extras["batch_capped"] = {"requested": args.batch,
                                      "ran": n_storm,
                                      "cap": storm_cap}
        g = extras["gated"]
        u = extras["ungated_baseline"]
        out = {
            # the client count rides the metric name (like gossip's
            # peer count): rates only ever compare like-for-like
            "metric": f"broadcaststorm_sustained_tx_per_sec_"
                      f"{n_clients}client",
            "value": g["sustained_tx_per_sec"],
            "unit": "tx/s",
            # ~1.0 = shedding lost no committed throughput while the
            # gated arm kept queue depth and p99 bounded (the extras)
            "vs_baseline": round(
                g["sustained_tx_per_sec"]
                / max(u["sustained_tx_per_sec"], 1e-9), 3),
            **extras,
        }
        _emit(out)
        return 0
    if args.metric == "multichannel":
        # blocks-per-channel scale with --batch at 4 txs/block,
        # floor 4 / cap 32 (the sweep multiplies by channels x points)
        n_blocks = max(4, min(32, args.batch // 16))
        extras = measure_multichannel(
            max(1, args.slices), max(1, args.channels),
            max(0, args.peers if args.peers is not None else 16),
            n_blocks, 4, use_sw=args.multichannel_verifier == "sw")
        rate = extras.pop("agg_tx_per_sec")
        out = {
            "metric": "multichannel_agg_committed_tx_per_sec",
            "value": rate,
            "unit": "tx/s",
            # scaling efficiency vs N independent unsharded runs done
            # serially on this host (the pre-sharding reality)
            "vs_baseline": round(
                rate / max(extras["serial_independent_tx_per_sec"],
                           1e-9), 3),
            **extras,
        }
        _emit(out)
        return 0
    if args.metric == "commitpipe":
        # blocks scale with --batch at 8 txs/block, floor 32 blocks
        # (the acceptance stream); barrier cadence is fixed inside
        n_blocks = max(32, args.batch // 8)
        extras = measure_commitpipe(
            n_blocks, 8, max(1, args.pipeline_depth),
            use_sw=args.commitpipe_verifier == "sw")
        pipe_rate = extras.pop("pipelined_tx_per_sec")
        out = {
            "metric": "commitpipe_committed_tx_per_sec",
            "value": pipe_rate,
            "unit": "tx/s",
            "vs_baseline": round(pipe_rate / extras["sync_tx_per_sec"], 3),
            **extras,
        }
        _emit(out)
        return 0
    if args.metric == "block":
        dev_rate, sw_rate = measure_block(min(args.batch, 1000), args.reps)
        out = {
            "metric": "validated_tx_per_sec_1k_block_2of3",
            "value": round(dev_rate, 1),
            "unit": "tx/s",
            "vs_baseline": round(dev_rate / sw_rate, 3),
        }
    elif args.metric == "idemix":
        # n presentations bounded: host signing dominates setup
        dev_rate, sw_rate, compile_secs = measure_idemix(
            min(args.batch, 64), max(1, min(args.reps, 2)))
        out = {
            "metric": "idemix_presentations_per_sec",
            "value": round(dev_rate, 1),
            "unit": "presentations/s",
            "vs_baseline": round(dev_rate / sw_rate, 3),
            # ~0 on a warm persistent cache
            "compile_secs": round(compile_secs, 1),
        }
    elif args.metric == "gossip":
        # --peers grows the storm (50-peer default preserved); the
        # metric name carries the count so rates are only ever
        # compared like-for-like
        n_peers = max(1, args.peers if args.peers is not None else 50)
        dev_rate, sw_rate = measure_gossip(n_peers, max(1, args.reps))
        out = {
            "metric": f"gossip_storm_block_verifies_per_sec_"
                      f"{n_peers}peer",
            "value": round(dev_rate, 1),
            "unit": "block-verifies/s",
            "vs_baseline": round(dev_rate / sw_rate, 3),
        }
    elif args.metric == "e2e":
        # the batch IS the tx count (the consenter's batch timeout
        # cuts partial blocks, so small counts still flow)
        dev_rate, sw_rate, stats = measure_e2e(args.batch)
        out = {
            "metric": "e2e_validated_tx_per_sec",
            "value": round(dev_rate, 1),
            "unit": "tx/s",
            "vs_baseline": round(dev_rate / sw_rate, 3),
            "pipeline_split": stats,
        }
    else:
        from fabric_mod_tpu.bccsp.sw import HAVE_CRYPTOGRAPHY
        items, expect = make_items(args.batch)
        sw_rate = measure_sw(items, expect)
        log(f"sw baseline: {sw_rate:,.0f} verifies/s")
        dev_rate = measure_device(items, expect, args.reps)
        log(f"device: {dev_rate:,.0f} verifies/s "
            f"({dev_rate / sw_rate:.2f}x sw)")
        out = {
            "metric": "ecdsa_p256_verifies_per_sec",
            "value": round(dev_rate, 1),
            "unit": "verifies/s",
            "vs_baseline": round(dev_rate / sw_rate, 3),
            # the ratio is only comparable across rounds when the sw
            # baseline ran the same backend — label it
            "sw_backend": "openssl" if HAVE_CRYPTOGRAPHY
                          else "pure-python-fallback",
        }
    _emit(out)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--metric", action="append",
                    choices=("verify", "block", "e2e", "idemix", "gossip",
                             "marshal", "diffverify", "hashverify",
                             "commitpipe", "broadcaststorm", "soak",
                             "multichannel",
                             "deliverfanout", "statescale",
                             "dissemination"),
                    default=None,
                    help="repeatable: each metric runs in sequence and "
                         "prints its own JSON line (the smoke target "
                         "passes --metric diffverify --metric hashverify)")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    # pipelined-front-end A/B knobs (see run_worker)
    ap.add_argument("--mixed-add", type=int, choices=(0, 1), default=None,
                    help="1: affine-table mixed-addition ladder "
                         "(FABRIC_MOD_TPU_MIXED_ADD)")
    ap.add_argument("--memo-cache", type=int, default=None,
                    help="verdict memo-cache capacity, 0 disables "
                         "(FABRIC_MOD_TPU_VERDICT_CACHE)")
    ap.add_argument("--inflight", type=int, default=None,
                    help="in-flight dispatch window depth "
                         "(FABRIC_MOD_TPU_INFLIGHT)")
    ap.add_argument("--precision", choices=("highest", "high"),
                    default=None,
                    help="limb matmul precision — bench-scoped A/B only")
    ap.add_argument("--pipeline-depth", type=int, default=4,
                    help="commitpipe: staged-but-uncommitted block "
                         "bound (1 = the synchronous path)")
    ap.add_argument("--commitpipe-verifier", choices=("device", "sw"),
                    default="device",
                    help="commitpipe: signature backend for BOTH arms "
                         "(sw = no XLA compile; the CPU smoke target)")
    ap.add_argument("--peers", type=int, default=None,
                    help="gossip: storm peer count (default 50; the "
                         "metric name carries it); multichannel: the "
                         "top of the rider-peer axis (default 16)")
    ap.add_argument("--clients", type=int, default=None,
                    help="broadcaststorm: client thread count "
                         "(default 8; the metric name carries it)")
    ap.add_argument("--staged-batch", type=int, default=None,
                    help="broadcaststorm: staged-arm coalescing depth "
                         "(FABRIC_MOD_TPU_STAGED_BROADCAST; default "
                         "64, 0 skips the staged arm)")
    ap.add_argument("--storm-verifier", choices=("sw", "device"),
                    default="sw",
                    help="broadcaststorm: Writers batch verifier the "
                         "arms dispatch through — sw (host ECDSA, "
                         "flat per-item cost) or device (ops/p256 "
                         "batch verify: real batch economics, buckets "
                         "pre-warmed outside the timed windows)")
    ap.add_argument("--slices", type=int, default=4,
                    help="multichannel: top of the mesh-slice axis "
                         "(the sweep runs 1, slices/2, slices)")
    ap.add_argument("--channels", type=int, default=4,
                    help="multichannel: top of the channel axis")
    ap.add_argument("--multichannel-verifier", choices=("device", "sw"),
                    default="device",
                    help="multichannel: signature backend (sw = no "
                         "XLA compile; the CPU smoke target)")
    ap.add_argument("--soak-seed", type=int, default=None,
                    help="soak: churn schedule seed (default "
                         "FMT_SOAK_SEED or 8) — a failed run prints "
                         "the seed to replay it here")
    ap.add_argument("--soak-events", type=int, default=None,
                    help="soak: churn events per run (default "
                         "FMT_SOAK_EVENTS or 6)")
    ap.add_argument("--soak-kinds", default=None,
                    help="soak: comma list restricting the churn-kind "
                         "pool (e.g. peer_crash_rejoin,orderer_restart)"
                         " — default is the full 9-kind catalog")
    ap.add_argument("--subscribers", type=int, default=10000,
                    help="deliverfanout: top of the subscriber-count "
                         "sweep (>=3 points up to this)")
    ap.add_argument("--state-keys", default="10000,100000,1000000",
                    help="statescale: comma list of prefilled statedb "
                         "sizes to sweep (>=3; the stream only "
                         "touches the smallest, so flags are "
                         "comparable across points)")
    ap.add_argument("--state-durable", action="store_true",
                    help="statescale: run the sweep on DurableStateDB "
                         "(batched one-buffered-write-per-block log) "
                         "instead of the in-memory statedb")
    ap.add_argument("--trace-out", default=None,
                    help="run FMT_TRACE-armed and export the span "
                         "ring as Chrome trace-event JSON "
                         "(Perfetto-loadable) to this path")
    args, _ = ap.parse_known_args()
    if args.cpu:
        # before anything imports jax
        os.environ["JAX_PLATFORMS"] = "cpu"
    metrics = args.metric or ["verify"]
    rc = 0
    for metric in metrics:
        args.metric = metric
        rc |= run_worker(args)
    return rc


if __name__ == "__main__":
    sys.exit(main())
