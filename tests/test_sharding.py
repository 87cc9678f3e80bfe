"""Channel-sharding subsystem tests: deterministic placement +
rebalance, the shared cross-channel verify service's tagged per-slice
routing, CROSS-CHANNEL ISOLATION (a fault or tamper on channel A's
batch never perturbs channel B's txflags or fingerprint; a poisoned
per-channel pipe never wedges the shared flusher), and the acceptance
differential: an N-channel sharded run is bit-identical — per-channel
txflags AND state fingerprints — to N independent unsharded runs.

Host-mode slices (FakeBatchVerifier per slice) keep the routing
machinery fully real without XLA compiles; the REAL multi-device
slice-mesh path runs in test_parallel.py on the virtual 8-device CPU
mesh."""
import threading

import numpy as np
import pytest

from fabric_mod_tpu import faults
from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
from fabric_mod_tpu.ledger import KvLedger
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
from fabric_mod_tpu.peer import (Committer, TxValidator,
                                 ValidationInfoProvider,
                                 ValidatorCommitTarget)
from fabric_mod_tpu.policy import ApplicationPolicyEvaluator, from_string
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from fabric_mod_tpu.sharding import (ChannelShardRouter,
                                     CrossChannelVerifyService, ShardMap,
                                     multihost_spec)
from fabric_mod_tpu.sharding.multihost import initialize_multihost
from fabric_mod_tpu.utils.fixtures import (independent_baseline,
                                           make_channel_stream,
                                           make_verify_items)

V = m.TxValidationCode


# --------------------------------------------------------------------------
# ShardMap: placement policy as a pure function of the join/leave seq
# --------------------------------------------------------------------------

def test_shardmap_least_loaded_assignment_is_deterministic():
    a = ShardMap(3)
    b = ShardMap(3)
    for mp in (a, b):
        got = [mp.assign(f"ch{i}") for i in range(7)]
        assert got == [0, 1, 2, 0, 1, 2, 0]
    assert a.loads() == [3, 2, 2]
    # idempotent: re-assign keeps the slice
    assert a.assign("ch1") == 1
    assert len(a) == 7 and "ch3" in a


def test_shardmap_release_rebalances_newest_first():
    mp = ShardMap(2)
    for i in range(4):
        mp.assign(f"ch{i}")                    # [ch0, ch2], [ch1, ch3]
    moves = mp.release("ch0")
    assert mp.loads() == [1, 2] or mp.loads() == [2, 1]
    # spread 1 <-> 2 is within tolerance: no move yet
    assert moves == []
    moves = mp.release("ch2")                  # slice0 empty, spread 2
    assert moves == [("ch3", 1, 0)]            # newest of the loaded
    assert mp.slice_of("ch3") == 0
    assert mp.loads() == [1, 1]


def test_shardmap_rebalance_off_and_unknown_channels():
    mp = ShardMap(2, rebalance=False)
    for i in range(4):
        mp.assign(f"ch{i}")
    assert mp.release("ch0") == []
    assert mp.release("ch2") == []             # no plan when off
    assert mp.loads() == [0, 2]
    assert mp.release("ghost") == []           # unknown: no-op
    with pytest.raises(KeyError):
        mp.slice_of("ghost")
    assert mp.slice_of("ghost", default=0) == 0
    with pytest.raises(ValueError):
        ShardMap(0)


# --------------------------------------------------------------------------
# CrossChannelVerifyService: one flusher, per-slice groups, isolation
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def csp():
    return SwCSP()


def _service(csp, n_slices=2):
    mp = ShardMap(n_slices)
    verifiers = {i: FakeBatchVerifier(csp) for i in range(n_slices)}
    svc = CrossChannelVerifyService(
        verifiers, lambda tag: mp.slice_of(tag, default=0),
        deadline_s=0.005)
    return svc, mp


def test_tagged_items_route_per_slice_and_verdicts_come_back(csp):
    svc, mp = _service(csp)
    mp.assign("big")                           # slice 0
    mp.assign("small")                         # slice 1
    items, expect = make_verify_items(6, invalid_every=3)
    try:
        futs = ([svc.submit_for("big", it) for it in items]
                + [svc.submit_for("small", it) for it in items])
        got = [f.result(timeout=60) for f in futs]
        assert got == expect + expect
        # the verify_many_for surface gives the same verdicts
        assert svc.verify_many_for("small", items, timeout=60) == expect
    finally:
        svc.close()


def test_untagged_and_unknown_tags_ride_the_default_slice(csp):
    svc, mp = _service(csp)
    items, expect = make_verify_items(4, invalid_every=2)
    try:
        # untagged (the base-service surface) and a tag the map never
        # placed both route to the default slice instead of raising —
        # one stray tag must never fail a coalesced batch
        assert svc.verify_many(items, timeout=60) == expect
        assert svc.verify_many_for("never-placed", items,
                                   timeout=60) == expect
    finally:
        svc.close()


def test_one_channels_injected_fault_never_touches_the_other(csp):
    """The flush-group isolation contract: an injected fault on one
    slice's dispatch group fails exactly that group's futures, typed;
    the other channel's riders in the SAME flush window resolve."""
    svc, mp = _service(csp)
    mp.assign("victim")                        # slice 0
    mp.assign("bystander")                     # slice 1
    items, expect = make_verify_items(4, invalid_every=2)
    plan = faults.FaultPlan().add("sharding.dispatch", nth=1, times=1)
    try:
        with faults.active(plan):
            # one batch, two groups: victim's group dispatches first
            # (slice order is sorted) and eats the nth=1 fault
            vf = [svc.submit_for("victim", it) for it in items]
            bf = [svc.submit_for("bystander", it) for it in items]
            got_b = [f.result(timeout=60) for f in bf]
            assert got_b == expect             # untouched
            for f in vf:
                with pytest.raises(faults.InjectedFault):
                    f.result(timeout=60)
        # after the plan's times cap, the victim heals
        assert svc.verify_many_for("victim", items, timeout=60) == expect
    finally:
        svc.close()


# --------------------------------------------------------------------------
# Router + commit engines: the block-path worlds
# --------------------------------------------------------------------------

CC_POLICY = "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')"


@pytest.fixture(scope="module")
def world(csp):
    msps, signers = [], {}
    for org in ("Org1", "Org2", "Org3"):
        ca = calib.CA(f"ca.{org.lower()}", org)
        msps.append(Msp(org, csp, [ca.cert]))
        cert, key = ca.issue(f"peer0.{org.lower()}", org, ous=["peer"])
        signers[org] = SigningIdentity(org, cert, calib.key_pem(key),
                                       csp)
    policy = m.ApplicationPolicy(
        signature_policy=from_string(CC_POLICY)).encode()
    return dict(csp=csp, mgr=MspManager(msps), signers=signers,
                policy=policy)


def _stream(world, cid: str, n_blocks: int = 3, txs: int = 3):
    """The SHARED oracle stream generator (utils/fixtures.py — same
    under-endorsed cadence and per-channel keys bench --metric
    multichannel gates against, so the two differentials can never
    drift apart)."""
    return make_channel_stream(world["signers"], cid, n_blocks, txs)


@pytest.fixture(scope="module")
def streams(world):
    return {f"ch{i}": _stream(world, f"ch{i}") for i in range(3)}


def _target(world, cid: str, verifier, root) -> ValidatorCommitTarget:
    led = KvLedger(str(root), cid)
    validator = TxValidator(
        cid, world["mgr"], ApplicationPolicyEvaluator(world["mgr"]),
        verifier, ValidationInfoProvider(world["policy"]),
        tx_id_exists=led.tx_id_exists)
    return ValidatorCommitTarget(validator, led)


def _independent_baseline(world, streams, root):
    """N unsharded runs through the SHARED oracle helper
    (fixtures.independent_baseline): per channel, its own verifier +
    sync Committer into a fresh ledger — what the sharded run must
    match bit-for-bit."""
    return independent_baseline(
        streams,
        lambda cid: _target(world, cid, FakeBatchVerifier(world["csp"]),
                            root / f"base-{cid}"))


def test_sharded_run_bit_identical_to_independent_runs(
        world, streams, tmp_path):
    """THE acceptance differential: 3 channels placed on 2 host-mode
    slices behind one router + shared verify service, blocks submitted
    round-robin across channels (real interleaving through the
    per-channel pipes), per-channel txflags and state fingerprints
    asserted identical to 3 independent unsharded sync runs."""
    baseline = _independent_baseline(world, streams, tmp_path)
    router = ChannelShardRouter(
        n_slices=2, depth=2,
        verifier_factory=lambda i, mesh: FakeBatchVerifier(world["csp"]))
    flags = {cid: [] for cid in streams}
    targets = {}
    try:
        for cid in streams:
            handle = router.add_channel(cid)
            targets[cid] = _target(world, cid, handle,
                                   tmp_path / f"shard-{cid}")
            router.bind_target(cid, targets[cid])
        # round-robin interleave: every channel's pipe is live at once
        max_len = max(len(s) for s in streams.values())
        for n in range(max_len):
            for cid, raws in streams.items():
                if n < len(raws):
                    router.submit_block(cid, m.Block.decode(raws[n]))
        assert router.flush(timeout_s=120)
        for cid, raws in streams.items():
            led = targets[cid].ledger
            assert led.height == len(raws)
            for n in range(len(raws)):
                blk = led.get_block_by_number(n)
                flags[cid].append(list(protoutil.block_txflags(blk)))
            assert flags[cid] == baseline[cid][0], cid
            assert led.state_fingerprint() == baseline[cid][1], cid
        # the flags carried signal (under-endorsed lanes flipped)
        distinct = {f for per in flags.values()
                    for blk in per for f in blk}
        assert V.ENDORSEMENT_POLICY_FAILURE in distinct
        assert V.VALID in distinct
    finally:
        router.close()


def test_poisoned_channel_pipe_never_wedges_the_rest(
        world, streams, tmp_path):
    """Channel A's commit pipe is poisoned mid-stream (its target
    crashes on commit); B keeps committing through the shared router
    AND the shared verify service keeps answering riders; A's next
    store_block rebuilds a fresh pipe from the committed height and
    the channel recovers — bit-identical to its baseline."""
    baseline = _independent_baseline(world, streams, tmp_path)
    router = ChannelShardRouter(
        n_slices=2, depth=2,
        verifier_factory=lambda i, mesh: FakeBatchVerifier(world["csp"]))
    cid_a, cid_b = "ch0", "ch1"
    boom = {"armed": False}

    class CrashingTarget:
        def __init__(self, inner):
            self._inner = inner
            self.validator = inner.validator
            self.ledger = inner.ledger

        def stage_block(self, block):
            return self._inner.stage_block(block)

        def commit_staged(self, staged):
            if boom["armed"]:
                boom["armed"] = False
                raise RuntimeError("injected commit crash")
            return self._inner.commit_staged(staged)

    try:
        ta = CrashingTarget(_target(world, cid_a,
                                    router.add_channel(cid_a),
                                    tmp_path / "iso-a"))
        router.bind_target(cid_a, ta)
        tb = _target(world, cid_b, router.add_channel(cid_b),
                     tmp_path / "iso-b")
        router.bind_target(cid_b, tb)

        raws_a = streams[cid_a]
        raws_b = streams[cid_b]
        # poison A on its first block
        boom["armed"] = True
        with pytest.raises(Exception):
            pipe = router.pipeline_for(cid_a)
            pipe.submit(m.Block.decode(raws_a[0]))
            pipe.flush(timeout_s=60)
        assert router.pipeline_for(cid_a) is not pipe  # rebuilt

        # B commits its whole stream while A is (was) poisoned
        for raw in raws_b:
            router.store_block(cid_b, m.Block.decode(raw))
        assert tb.ledger.state_fingerprint() == baseline[cid_b][1]

        # the shared flusher still answers riders from every channel
        items, expect = make_verify_items(4, invalid_every=2)
        assert router.service.verify_many_for(cid_b, items,
                                              timeout=60) == expect
        assert router.service.verify_many_for(cid_a, items,
                                              timeout=60) == expect

        # A recovers through a fresh pipe, bit-identical
        for raw in raws_a:
            router.store_block(cid_a, m.Block.decode(raw))
        assert ta.ledger.state_fingerprint() == baseline[cid_a][1]
    finally:
        router.close()


def test_tampered_channel_batch_never_perturbs_the_other(
        world, streams, tmp_path):
    """Channel A validates a block whose signatures are all garbage
    (every tx flagged invalid) CONCURRENTLY with channel B's clean
    stream — B's flags and fingerprint must equal its solo baseline."""
    baseline = _independent_baseline(world, streams, tmp_path)
    router = ChannelShardRouter(
        n_slices=2, depth=2,
        verifier_factory=lambda i, mesh: FakeBatchVerifier(world["csp"]))
    cid_a, cid_b = "ch0", "ch1"
    try:
        ta = _target(world, cid_a, router.add_channel(cid_a),
                     tmp_path / "tam-a")
        router.bind_target(cid_a, ta)
        tb = _target(world, cid_b, router.add_channel(cid_b),
                     tmp_path / "tam-b")
        router.bind_target(cid_b, tb)
        # tamper every envelope signature of A's first block
        blk_a = m.Block.decode(streams[cid_a][0])
        for i, raw_env in enumerate(blk_a.data.data):
            env = m.Envelope.decode(raw_env)
            env.signature = bytes(len(env.signature))
            blk_a.data.data[i] = env.encode()

        done = threading.Event()
        a_flags = []

        def run_a():
            try:
                a_flags.append(router.store_block(cid_a, blk_a))
            finally:
                done.set()

        t = threading.Thread(target=run_a, daemon=True)
        t.start()
        for raw in streams[cid_b]:
            router.store_block(cid_b, m.Block.decode(raw))
        assert done.wait(timeout=120) and t is not None
        t.join(timeout=10)
        # A's garbage flagged invalid, not crashed
        assert a_flags and all(f != V.VALID for f in a_flags[0])
        # B untouched, bit-identical to its solo baseline
        led_b = tb.ledger
        got_b = [list(protoutil.block_txflags(led_b.get_block_by_number(n)))
                 for n in range(led_b.height)]
        assert got_b == baseline[cid_b][0]
        assert led_b.state_fingerprint() == baseline[cid_b][1]
    finally:
        router.close()


def test_rebalance_on_leave_moves_and_rebuilds_pipes(
        world, streams, tmp_path):
    """Four channels on two slices -> removing a spread-1 neighbor
    forces no move (spread <= 1 is balanced); stranding one slice
    entirely moves the other slice's NEWEST channel over, and the
    moved channel's next pipe is consumer-labeled for its NEW slice
    while still committing correctly."""
    router = ChannelShardRouter(
        n_slices=2, depth=1,
        verifier_factory=lambda i, mesh: FakeBatchVerifier(world["csp"]))
    try:
        tgts = {}
        for cid in ("ch0", "ch1", "ch2", "chX"):
            handle = router.add_channel(cid)
            tgts[cid] = _target(world, cid, handle,
                                tmp_path / f"reb-{cid}")
            router.bind_target(cid, tgts[cid])
        assert router.map.loads() == [2, 2]    # ch0+ch2 / ch1+chX
        # place a pipe on chX so the move (below) has one to rebuild;
        # chX replays ch1's stream (same channel id inside the blocks
        # is irrelevant to routing — the ledger key-space is its own)
        router.store_block("chX", m.Block.decode(streams["ch1"][0]))
        p1 = router.pipeline_for("chX")
        assert p1.consumer == "shard1"
        # a spread-1 leave rebalances nothing...
        assert router.remove_channel("ch0") == []
        # ...stranding slice 0 moves the newest of slice 1 (chX)
        moves = router.remove_channel("ch2")
        assert moves == [("chX", 1, 0)]
        assert router.slice_of("chX") == 0
        # the old pipe was drained+closed; the fresh one is pinned to
        # the new slice and the channel keeps committing in order
        router.store_block("chX", m.Block.decode(streams["ch1"][1]))
        p1b = router.pipeline_for("chX")
        assert p1b is not p1 and p1.closed
        assert p1b.consumer == "shard0"
        assert tgts["chX"].ledger.height == 2
    finally:
        router.close()


def test_router_rejects_unplaced_and_closed_use(world, tmp_path):
    router = ChannelShardRouter(
        n_slices=1,
        verifier_factory=lambda i, mesh: FakeBatchVerifier(world["csp"]))
    with pytest.raises(KeyError):
        router.pipeline_for("nope")
    router.add_channel("t")                    # no target bound
    with pytest.raises(RuntimeError):
        router.pipeline_for("t")
    router.close()
    with pytest.raises(RuntimeError):
        router.add_channel("late")
    router.close()                             # idempotent


def test_sharded_commit_on_real_slice_meshes(world, tmp_path):
    """The acceptance differential on the REAL multi-device path: two
    channels pinned to the two 4-device slice meshes of the virtual
    8-device CPU mesh, whole commit stack (validator staging ->
    slice-pinned device dispatch -> pipelined commit) — per-channel
    txflags + fingerprints bit-identical to independent unsharded
    device runs.  Tiny blocks on purpose: the batches stay in the
    bucket-8 program shapes test_parallel already compiles."""
    from fabric_mod_tpu.bccsp.tpu import TpuVerifier
    from fabric_mod_tpu.parallel import slice_meshes

    streams = {cid: _stream(world, cid, n_blocks=2, txs=2)
               for cid in ("dev0", "dev1")}
    baseline = {}
    for cid, raws in streams.items():
        t = _target(world, cid, TpuVerifier(cache_size=0),
                    tmp_path / f"devbase-{cid}")
        flags = [list(Committer(t.validator, t.ledger).store_block(
            m.Block.decode(raw))) for raw in raws]
        baseline[cid] = (flags, t.ledger.state_fingerprint())

    router = ChannelShardRouter(
        n_slices=2, meshes=slice_meshes(2), depth=2,
        verifier_factory=lambda i, mesh: TpuVerifier(mesh=mesh,
                                                     cache_size=0))
    try:
        targets = {}
        for cid in streams:
            handle = router.add_channel(cid)
            targets[cid] = _target(world, cid, handle,
                                   tmp_path / f"devsh-{cid}")
            router.bind_target(cid, targets[cid])
        for n in range(2):
            for cid in streams:
                router.submit_block(cid,
                                    m.Block.decode(streams[cid][n]))
        assert router.flush(timeout_s=600)
        for cid in streams:
            led = targets[cid].ledger
            got = [list(protoutil.block_txflags(
                led.get_block_by_number(n))) for n in range(led.height)]
            assert got == baseline[cid][0], cid
            assert led.state_fingerprint() == baseline[cid][1], cid
    finally:
        router.close()


# --------------------------------------------------------------------------
# Multi-host spec: shape pinned, bring-up stubbed
# --------------------------------------------------------------------------

def test_multihost_spec_partitions_slices_round_robin():
    spec = multihost_spec(n_hosts=2, n_slices=8)
    assert spec["hosts"] == 2 and spec["slices"] == 8
    groups = {g["process_index"]: g["slices"]
              for g in spec["process_groups"]}
    assert groups == {0: [0, 2, 4, 6], 1: [1, 3, 5, 7]}
    # every slice exactly once across hosts
    flat = sorted(s for g in groups.values() for s in g)
    assert flat == list(range(8))
    with pytest.raises(ValueError):
        multihost_spec(n_hosts=3, n_slices=8)


def test_multihost_initialize_is_a_stub_behind_the_knob(monkeypatch):
    monkeypatch.delenv("FABRIC_MOD_TPU_SHARD_HOSTS", raising=False)
    initialize_multihost()                     # single host: no-op
    monkeypatch.setenv("FABRIC_MOD_TPU_SHARD_HOSTS", "2")
    with pytest.raises(NotImplementedError):
        initialize_multihost()


def test_shard_knob_defaults_route_single_slice(world, tmp_path,
                                                monkeypatch):
    from fabric_mod_tpu.peer import commitpipe
    from fabric_mod_tpu.sharding.router import shard_count
    monkeypatch.delenv("FABRIC_MOD_TPU_SHARDS", raising=False)
    assert shard_count() == 0                  # sharding off by default
    monkeypatch.setenv("FABRIC_MOD_TPU_SHARDS", "4")
    assert shard_count() == 4
    # the engines' depth is no knob: a router that is told none
    # builds every channel's pipe at the one constant
    router = ChannelShardRouter(
        verifier_factory=lambda i, mesh: FakeBatchVerifier(world["csp"]))
    try:
        assert router.n_slices == 4
        router.add_channel("d", _target(world, "d",
                                        FakeBatchVerifier(world["csp"]),
                                        tmp_path / "d"))
        assert router.pipeline_for("d").depth == commitpipe.DEPTH
    finally:
        router.close()
