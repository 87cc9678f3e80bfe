"""Fault-injection scenario tier: tolerance mechanisms under injected
faults.

Every mechanism PR 5 added (deliver failover + typed disconnect,
device-verifier circuit breaker + sw fallback, broadcast NOT_LEADER
retry, gossip send retry, commit-pipeline crash-resume) is exercised
by the deterministic fault that kills the un-mechanized path — same
proof shape as Raft's leader-crash evaluation (Ongaro & Ousterhout,
ATC '14): inject the failure at a chosen point, assert recovery.

Determinism contract: triggers are Nth-call or seeded; retry sleeps
are captured or drive a ManualClock; the raft scenario runs on the
fake-clock tier (tests/_clocksteps).  Real time only SETTLES threads,
never decides outcomes.
"""
import random
import threading
import time

import numpy as np
import pytest

from fabric_mod_tpu import faults
from fabric_mod_tpu.bccsp.breaker import CircuitBreaker
from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.bccsp.tpu import (BatchingVerifyService, TpuVerifier,
                                      VerifyDeadlineExceeded, VerifyItem,
                                      verify_deadline_s)
from fabric_mod_tpu.utils.fakeclock import ManualClock
from fabric_mod_tpu.utils.retry import Retrier
from tests._clocksteps import advance_until, leader_known_by_all, settle


# ---------------------------------------------------------------------------
# framework: triggers, spec grammar, arming
# ---------------------------------------------------------------------------

def test_point_unarmed_is_noop():
    assert not faults.armed()
    assert faults.point("no.such.point") is False


def test_nth_trigger_fires_exactly_once():
    plan = faults.FaultPlan().add("a.b", nth=3)
    with faults.active(plan):
        for i in range(1, 6):
            if i == 3:
                with pytest.raises(faults.InjectedFault) as ei:
                    faults.point("a.b")
                assert ei.value.point == "a.b"
            else:
                assert faults.point("a.b") is False
    assert plan.fires("a.b") == 1
    assert plan.calls("a.b") == 5


def test_seeded_probability_is_reproducible():
    def pattern(seed):
        plan = faults.FaultPlan().add("p.q", mode="drop", p=0.4,
                                      seed=seed)
        with faults.active(plan):
            return [faults.point("p.q") for _ in range(64)]
    a, b = pattern(7), pattern(7)
    assert a == b                          # same seed, same run
    assert any(a) and not all(a)           # it actually mixes
    assert pattern(8) != a                 # seed matters


def test_drop_mode_times_cap_and_kind():
    plan = faults.FaultPlan()
    plan.add("d.e", mode="drop", p=1.0, times=2)
    plan.add("k.l", kind="device")
    with faults.active(plan):
        assert faults.point("d.e") and faults.point("d.e")
        assert faults.point("d.e") is False      # times=2 exhausted
        with pytest.raises(faults.InjectedFault) as ei:
            faults.point("k.l")
        assert ei.value.kind == "device"


def test_fmt_faults_spec_grammar():
    plan = faults.FaultPlan.from_spec(
        "x.y:error@n=2;a.b:drop@p=1.0,seed=3,times=1;c.d:error@once,"
        "kind=device")
    with faults.active(plan):
        assert faults.point("x.y") is False
        with pytest.raises(faults.InjectedFault):
            faults.point("x.y")
        assert faults.point("a.b") is True
        with pytest.raises(faults.InjectedFault) as ei:
            faults.point("c.d")
        assert ei.value.kind == "device"
    with pytest.raises(ValueError, match="bad FMT_FAULTS rule"):
        faults.FaultPlan.from_spec("x.y:error@wat=1")


def test_fired_counter_exported():
    from fabric_mod_tpu.observability.metrics import default_provider
    plan = faults.FaultPlan().add("metric.pt", nth=1)
    with faults.active(plan):
        with pytest.raises(faults.InjectedFault):
            faults.point("metric.pt")
    text = default_provider().render_prometheus()
    assert 'fabric_faults_injected_total{point="metric.pt"} 1' in text


# ---------------------------------------------------------------------------
# Retrier: deterministic backoff, deadlines
# ---------------------------------------------------------------------------

def test_retrier_schedule_and_success():
    sleeps = []
    r = Retrier(base_s=0.1, max_s=0.35, multiplier=2.0, jitter=0.0,
                max_attempts=5, sleep=sleeps.append, name="t-sched")
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] < 4:
            raise OSError("transient")
        return "ok"
    assert r.call(flaky) == "ok"
    assert state["n"] == 4
    assert sleeps == [0.1, 0.2, 0.35]      # exponential, capped


def test_retrier_jitter_seeded_and_bounded():
    r = Retrier(base_s=1.0, max_s=1.0, jitter=0.5,
                rng=random.Random(42), name="t-jit")
    seq = [r.delay_for(0) for _ in range(32)]
    r2 = Retrier(base_s=1.0, max_s=1.0, jitter=0.5,
                 rng=random.Random(42), name="t-jit")
    assert seq == [r2.delay_for(0) for _ in range(32)]
    assert all(0.5 <= d <= 1.5 for d in seq)
    assert len(set(seq)) > 1


def test_retrier_deadline_on_manual_clock():
    clock = ManualClock()
    r = Retrier(base_s=1.0, max_s=1.0, jitter=0.0, deadline_s=2.5,
                clock=clock, sleep=clock.advance, name="t-dead")
    calls = []

    def always_fails():
        calls.append(clock.monotonic())
        raise ValueError("still down")
    with pytest.raises(ValueError, match="still down"):
        r.call(always_fails)
    # attempts at t=0, 1, 2; the t=3 retry would cross the deadline
    assert calls == [0.0, 1.0, 2.0]


def test_retrier_unretryable_raises_immediately():
    r = Retrier(base_s=0.0, retry_on=(OSError,), max_attempts=5,
                sleep=lambda s: None, name="t-filter")
    calls = []

    def boom():
        calls.append(1)
        raise KeyError("not transient")
    with pytest.raises(KeyError):
        r.call(boom)
    assert calls == [1]


# ---------------------------------------------------------------------------
# device-verifier circuit breaker + sw fallback
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def verify_world():
    csp = SwCSP()
    key = csp.key_gen("P256")
    items = []
    for i in range(3):
        d = csp.hash(b"faults-msg-%d" % i)
        items.append(VerifyItem(d, csp.sign(key, d), key.public_xy()))
    # one wrong-digest item and one junk-DER item: the verdict vector
    # must mix True/False so "identical" is a real assertion
    items.append(VerifyItem(csp.hash(b"other"), items[0].signature,
                            key.public_xy()))
    items.append(VerifyItem(items[0].digest, b"\x00\x01junk",
                            key.public_xy()))
    truth = [bool(x) for x in csp.verify_batch(items)]
    assert True in truth and False in truth
    return {"csp": csp, "items": items, "truth": truth}


def _wire_fake_device(v, csp):
    """Stand-in for the XLA path: real sw verdicts, but routed through
    the REAL device seams (dispatch/resolve fault points) so injected
    device errors exercise the production classifier/fallback/breaker
    code, without a multi-minute CPU XLA compile in tier-1."""
    def fake_device(items):
        faults.point("bccsp.device.dispatch")
        mask = np.asarray(csp.verify_batch(items), bool)

        def done():
            faults.point("bccsp.device.resolve")
            return mask
        return done
    v._device_dispatch = fake_device
    return v


def test_nondevice_fault_still_fails_the_batch(verify_world):
    """The pre-breaker behavior is PRESERVED for host bugs: the same
    injection point, non-device kind -> the caller sees the error (no
    silent masking) — this is the 'fault that kills it today' half of
    the pair; the device-kind test below survives it."""
    v = _wire_fake_device(
        TpuVerifier(cache_size=0,
                    breaker=CircuitBreaker(k=3, interval_s=0)),
        verify_world["csp"])
    plan = faults.FaultPlan().add("bccsp.device.dispatch", nth=1)
    with faults.active(plan):
        with pytest.raises(faults.InjectedFault):
            v.verify_many(verify_world["items"])
    assert plan.fires() == 1


def test_device_fault_degrades_to_sw_bit_identical(verify_world):
    """A device-classified error at dispatch OR resolve falls back
    per-batch to the sw verifier with verdicts BIT-IDENTICAL to the
    healthy device run."""
    csp, items = verify_world["csp"], verify_world["items"]
    for point in ("bccsp.device.dispatch", "bccsp.device.resolve"):
        v = _wire_fake_device(
            TpuVerifier(cache_size=0,
                        breaker=CircuitBreaker(k=3, interval_s=0)),
            csp)
        healthy = [bool(x) for x in v.verify_many(items)]
        assert healthy == verify_world["truth"]
        plan = faults.FaultPlan().add(point, nth=1, kind="device")
        with faults.active(plan):
            degraded = [bool(x) for x in v.verify_many(items)]
        assert plan.fires() == 1, point
        assert degraded == healthy, point
        assert v.breaker.state == "closed"   # 1 < K: no trip


def test_breaker_opens_after_k_and_probe_recloses(verify_world):
    """K consecutive device failures open the circuit (device skipped
    entirely); the background prober re-closes it once a probe
    succeeds — event-driven via probe_soon(), no wall-clock waits."""
    csp, items = verify_world["csp"], verify_world["items"]
    v = _wire_fake_device(TpuVerifier(cache_size=0, breaker=None), csp)
    # rebind the breaker tight: K=2, prober armed but on a huge
    # interval (only probe_soon() advances it)
    v.breaker.stop()
    v.breaker = CircuitBreaker(k=2, probe=v._probe_device,
                               interval_s=3600.0, name="faults-test")
    try:
        # p=1.0 with times=2: deterministically fail the first TWO
        # dispatches (two nth rules would count calls independently)
        plan = (faults.FaultPlan()
                .add("bccsp.device.dispatch", p=1.0, times=2,
                     kind="device")
                .add("bccsp.device.probe", nth=1, kind="device"))
        with faults.active(plan):
            assert [bool(x) for x in v.verify_many(items)] == \
                verify_world["truth"]
            assert v.breaker.state == "closed"     # 1 failure
            assert [bool(x) for x in v.verify_many(items)] == \
                verify_world["truth"]
            assert v.breaker.state == "open"       # K=2 reached
            # open: the device path is not consulted at all
            before = plan.calls("bccsp.device.dispatch")
            assert [bool(x) for x in v.verify_many(items)] == \
                verify_world["truth"]
            assert plan.calls("bccsp.device.dispatch") == before
            # first probe is injected to FAIL: circuit stays open
            v.breaker.probe_soon()
            assert settle(lambda: plan.fires("bccsp.device.probe") >= 1)
            assert v.breaker.state == "open"
            # second probe succeeds: the prober re-closes the circuit
            v.breaker.probe_soon()
            assert settle(lambda: v.breaker.state == "closed"), \
                v.breaker.state
            # healed: the device serves again (rules exhausted, so the
            # dispatch seam counts the call without firing)
            before = plan.calls("bccsp.device.dispatch")
            assert [bool(x) for x in v.verify_many(items)] == \
                verify_world["truth"]
            assert plan.calls("bccsp.device.dispatch") == before + 1
        from fabric_mod_tpu.observability.metrics import default_provider
        text = default_provider().render_prometheus()
        assert "fabric_bccsp_breaker_state" in text
        assert "fabric_bccsp_breaker_recovery_seconds_count" in text
        assert "fabric_bccsp_sw_fallback_batches_total" in text
    finally:
        v.breaker.stop()


def test_batching_service_survives_device_fault(verify_world):
    """Service-level degradation: a device error mid-service resolves
    callers' Futures with sw verdicts instead of exceptions."""
    csp, items = verify_world["csp"], verify_world["items"]
    v = _wire_fake_device(
        TpuVerifier(cache_size=0,
                    breaker=CircuitBreaker(k=3, interval_s=0)),
        csp)
    svc = BatchingVerifyService(v, deadline_s=0.001)
    try:
        plan = faults.FaultPlan().add("bccsp.device.resolve", nth=1,
                                      kind="device")
        with faults.active(plan):
            got = svc.verify_many(items, timeout=30)
        assert plan.fires() == 1
        assert [bool(x) for x in got] == verify_world["truth"]
    finally:
        svc.close()


def test_verify_deadline_knob_and_typed_timeout(monkeypatch):
    """Satellite: the service deadline comes from
    FABRIC_MOD_TPU_VERIFY_DEADLINE (shared by verify/verify_many) and
    expiry surfaces the TYPED VerifyDeadlineExceeded — stragglers
    included — so callers can tell a deadline from a device failure."""
    monkeypatch.delenv("FABRIC_MOD_TPU_VERIFY_DEADLINE", raising=False)
    assert verify_deadline_s() == 30.0
    monkeypatch.setenv("FABRIC_MOD_TPU_VERIFY_DEADLINE", "0.15")
    assert verify_deadline_s() == 0.15
    monkeypatch.setenv("FABRIC_MOD_TPU_VERIFY_DEADLINE", "0")
    assert verify_deadline_s() is None     # 0 = wait forever
    monkeypatch.setenv("FABRIC_MOD_TPU_VERIFY_DEADLINE", "0.15")

    release = threading.Event()

    class StuckVerifier:
        def verify_many_async(self, items):
            def resolve():
                release.wait(20)
                return [True] * len(items)
            return resolve

    svc = BatchingVerifyService(StuckVerifier(), deadline_s=0.001)
    try:
        item = VerifyItem(b"\x00" * 32, b"sig", b"k" * 64)
        with pytest.raises(VerifyDeadlineExceeded) as ei:
            svc.verify(item)
        assert ei.value.deadline_s == 0.15
        futs = [svc.submit(item) for _ in range(3)]
        with pytest.raises(VerifyDeadlineExceeded):
            svc.verify_many([item, item])
        # stragglers fail typed too (no caller parks forever), and the
        # error is NOT a device-failure type
        assert not isinstance(ei.value, faults.InjectedFault)
        for f in futs:
            del f                          # stragglers of prior submits
    finally:
        release.set()
        svc.close()


# ---------------------------------------------------------------------------
# deliver: typed disconnect (sync mode) + failover + crash-resume
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deliver_net(tmp_path_factory):
    from fabric_mod_tpu.e2e import Network
    net = Network(str(tmp_path_factory.mktemp("faults_net")),
                  batch_timeout="100ms", max_message_count=2)
    for i in range(8):
        net.invoke([b"put", b"fk%d" % i, b"fv%d" % i])
    # let the orderer cut everything before the scenarios pull
    deadline = time.time() + 20
    while time.time() < deadline and net.support.store.height < 5:
        time.sleep(0.05)
    assert net.support.store.height >= 5
    yield net
    net.close()


def _fresh_peer_channel(net, root, verifier=None):
    """A second committing peer for the same channel: fresh ledger,
    same genesis — the uninterrupted differential arm."""
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    from fabric_mod_tpu.ledger import KvLedger
    from fabric_mod_tpu.peer.channel import Channel
    _, config = config_from_block(net.genesis_block)
    led = KvLedger(str(root), net.channel_id)
    chan = Channel(net.channel_id, led,
                   verifier or FakeBatchVerifier(net.csp),
                   Bundle(net.channel_id, config, net.csp), net.csp)
    if led.height == 0:
        chan.init_from_genesis(net.genesis_block)
    return chan


def test_sync_stream_drop_is_typed_and_resumable(deliver_net, tmp_path):
    """The satellite pair: a dropped stream in single-endpoint mode
    surfaces DeliverDisconnected (typed, with the committed height —
    not a bare exception, not a silent stop), and a fresh client
    resumes from that height to a state fingerprint identical to an
    uninterrupted pull — re-seek from ledger height, no double
    commit."""
    from fabric_mod_tpu.peer.deliverclient import (DeliverClient,
                                                   DeliverDisconnected)
    net = deliver_net
    tip = net.support.store.height
    chan = _fresh_peer_channel(net, tmp_path / "dropped")
    client = DeliverClient(chan, net.deliver)
    # nth=4: the stream dies after ~3 blocks yielded — mid-stream
    plan = faults.FaultPlan().add("deliver.stream", nth=4)
    with faults.active(plan):
        with pytest.raises(DeliverDisconnected) as ei:
            client.run(stop_at=tip - 1, idle_timeout_s=5.0)
    assert plan.fires() == 1
    assert ei.value.height == chan.ledger.height   # the resume point
    assert 0 < chan.ledger.height < tip            # genuinely mid-stream
    # resume: a FRESH client re-seeks from the ledger height
    DeliverClient(chan, net.deliver).run(stop_at=tip - 1,
                                         idle_timeout_s=5.0)
    assert chan.ledger.height == tip
    # differential: identical to an uninterrupted sync pull
    ref = _fresh_peer_channel(net, tmp_path / "uninterrupted")
    DeliverClient(ref, net.deliver).run(stop_at=tip - 1,
                                        idle_timeout_s=5.0)
    assert ref.ledger.height == tip
    assert chan.ledger.state_fingerprint() == \
        ref.ledger.state_fingerprint()


def test_failover_source_survives_the_same_drop(deliver_net, tmp_path):
    """The tentpole pair to the test above: the SAME mid-stream death,
    but through FailoverDeliverSource — the client never sees an
    error; the source rotates to the other orderer, re-seeks from the
    next needed block, and the peer commits the whole chain exactly
    once (heights contiguous, fingerprint matches sync)."""
    pytest.importorskip("grpc")
    from fabric_mod_tpu.orderer.server import OrdererServer
    from fabric_mod_tpu.peer.blocksprovider import (Endpoint,
                                                    FailoverDeliverSource)
    from fabric_mod_tpu.peer.deliverclient import DeliverClient
    net = deliver_net
    tip = net.support.store.height
    srv_a = OrdererServer(net.registrar, "127.0.0.1:0")
    srv_b = OrdererServer(net.registrar, "127.0.0.1:0")
    srv_a.start()
    srv_b.start()
    try:
        source = FailoverDeliverSource(
            [Endpoint(f"127.0.0.1:{srv_a.port}"),
             Endpoint(f"127.0.0.1:{srv_b.port}")],
            net.channel_id, base_backoff_s=0.05,
            retrier=Retrier(base_s=0.05, max_s=0.2, jitter=0.0,
                            name="test-failover"))
        chan = _fresh_peer_channel(net, tmp_path / "failover")
        client = DeliverClient(chan, source)
        plan = faults.FaultPlan().add("deliver.failover.stream", nth=4)
        with faults.active(plan):
            client.run(stop_at=tip - 1, idle_timeout_s=10.0)
        assert plan.fires() == 1               # the drop DID happen
        assert source.rotations >= 1           # and was failed over
        assert chan.ledger.height == tip       # no gap, no double commit
        ref = _fresh_peer_channel(net, tmp_path / "failover_ref")
        DeliverClient(ref, net.deliver).run(stop_at=tip - 1,
                                            idle_timeout_s=5.0)
        assert chan.ledger.state_fingerprint() == \
            ref.ledger.state_fingerprint()
    finally:
        srv_a.stop()
        srv_b.stop()


def test_snapshot_joined_peer_pulls_the_tail(deliver_net, tmp_path):
    """A ledger bootstrapped from a snapshot holds no block below its
    height: the deliver client's chain check starts from the store's
    record of the tip's hash (it read the tip block, and a
    snapshot-joined peer that won deliver leadership died at every
    start: the soak's peer_join, now and then)."""
    from fabric_mod_tpu.ledger.snapshot import bootstrap_from_snapshot
    from fabric_mod_tpu.peer.deliverclient import DeliverClient
    net = deliver_net
    tip = net.support.store.height
    src = _fresh_peer_channel(net, tmp_path / "snap_src")
    DeliverClient(src, net.deliver).run(stop_at=2, idle_timeout_s=5.0)
    assert src.ledger.height == 3
    src.ledger.snapshot_to(str(tmp_path / "snap"))
    bootstrap_from_snapshot(str(tmp_path / "snap"),
                            str(tmp_path / "snap_joined")).close()
    joined = _fresh_peer_channel(net, tmp_path / "snap_joined")
    assert joined.ledger.height == 3
    assert joined.ledger.get_block_by_number(2) is None
    client = DeliverClient(joined, net.deliver)
    client.run(stop_at=tip - 1, idle_timeout_s=5.0)
    assert client.rejected == []
    assert joined.ledger.height == tip
    DeliverClient(src, net.deliver).run(stop_at=tip - 1,
                                        idle_timeout_s=5.0)
    assert joined.ledger.state_fingerprint() == \
        src.ledger.state_fingerprint()


# ---------------------------------------------------------------------------
# commit pipeline: crash mid-stream, resume from ledger height
# ---------------------------------------------------------------------------

def test_commitpipe_crash_resume_fingerprint(deliver_net, tmp_path):
    """Satellite: kill a PipelinedCommitter mid-stream (injected crash
    between verdict await and ledger write), rebuild, resume from the
    ledger height — flags and state fingerprint identical to an
    uninterrupted synchronous run, every block committed exactly
    once."""
    from fabric_mod_tpu.ledger.kvledger import LedgerError
    from fabric_mod_tpu.peer.commitpipe import PipelinedCommitter
    net = deliver_net
    blocks = [net.support.store.get_block_by_number(n)
              for n in range(1, net.support.store.height)]
    # reference arm: synchronous commits
    ref = _fresh_peer_channel(net, tmp_path / "cp_sync")
    for blk in blocks:
        ref.store_block(blk)
    ref_fp = ref.ledger.state_fingerprint()

    chan = _fresh_peer_channel(net, tmp_path / "cp_crash")
    pipe = PipelinedCommitter(chan, depth=2)
    plan = faults.FaultPlan().add("commitpipe.commit", nth=2)
    with faults.active(plan):
        with pytest.raises(faults.InjectedFault):
            for blk in blocks:
                pipe.submit(blk)
            pipe.flush(timeout_s=60.0)
        pipe.close()
    assert plan.fires() == 1
    assert pipe.error is not None
    crashed_at = chan.ledger.height
    assert 0 < crashed_at < len(blocks) + 1    # genuinely mid-stream
    # resume: a fresh engine picks up from the DURABLE height
    pipe2 = PipelinedCommitter(chan, depth=2)
    for blk in blocks[chan.ledger.height - 1:]:
        pipe2.submit(blk)
    assert pipe2.flush(timeout_s=120.0)
    pipe2.close()
    assert chan.ledger.height == len(blocks) + 1
    assert chan.ledger.state_fingerprint() == ref_fp
    # double-commit is structurally rejected, not silently absorbed
    pipe3 = PipelinedCommitter(chan, depth=2)
    with pytest.raises(LedgerError, match="out of order"):
        pipe3.submit(blocks[0])
    pipe3.close()


def _router_bound_channel(net, root):
    """A peer channel committing through a one-slice shard router (the
    one engine a `Channel` hands out); returns (channel, router)."""
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.sharding import ChannelShardRouter
    router = ChannelShardRouter(
        n_slices=1,
        verifier_factory=lambda i, mesh: FakeBatchVerifier(net.csp))
    chan = _fresh_peer_channel(net, root,
                               verifier=router.add_channel(net.channel_id))
    chan.use_shard_router(router)
    return chan, router


def test_channel_commit_pipeline_is_the_routers_engine_or_none(
        deliver_net, tmp_path):
    """An unbound channel has no engine of its own (`store_block` is
    the synchronous body); a router-bound one hands out the router's,
    built at the one depth."""
    from fabric_mod_tpu.peer import commitpipe
    net = deliver_net
    blocks = [net.support.store.get_block_by_number(n)
              for n in range(1, net.support.store.height)]
    ref = _fresh_peer_channel(net, tmp_path / "unbound")
    assert ref.commit_pipeline() is None
    for blk in blocks:
        ref.store_block(blk)
    assert ref.commit_pipeline() is None
    chan, router = _router_bound_channel(net, tmp_path / "bound")
    try:
        pipe = chan.commit_pipeline()
        assert pipe is router.pipeline_for(net.channel_id)
        assert chan.commit_pipeline() is pipe           # one engine
        assert pipe.depth == commitpipe.DEPTH
        for blk in blocks:
            chan.store_block(blk)
        assert chan.commit_pipeline() is pipe           # still healthy
    finally:
        router.close()
    assert chan.ledger.state_fingerprint() == \
        ref.ledger.state_fingerprint()


def test_router_bound_channel_retries_through_fresh_pipe(
        deliver_net, tmp_path):
    """Channel.store_block's retry under an injected engine crash, on
    the engine the router owns: the caller's block still commits (one
    retry through the pipe `pipeline_for` rebuilt), the channel is
    not bricked, state matches the synchronous path."""
    net = deliver_net
    blocks = [net.support.store.get_block_by_number(n)
              for n in range(1, net.support.store.height)]
    chan, router = _router_bound_channel(net, tmp_path / "chan_crash")
    try:
        first_pipe = chan.commit_pipeline()
        plan = faults.FaultPlan().add("commitpipe.commit", nth=2)
        with faults.active(plan):
            for blk in blocks:
                chan.store_block(blk)          # no exception surfaces
        assert plan.fires() == 1
        assert chan.commit_pipeline() is not first_pipe   # rebuilt
        assert first_pipe.closed and first_pipe.error is not None
        assert chan.ledger.height == len(blocks) + 1
    finally:
        router.close()
    from fabric_mod_tpu.observability.metrics import default_provider
    text = default_provider().render_prometheus()
    assert any(line.startswith("fabric_sharding_pipe_rebuilds_total ")
               and float(line.split()[-1]) >= 1
               for line in text.splitlines()), "rebuild not counted"
    ref = _fresh_peer_channel(net, tmp_path / "chan_sync")
    for blk in blocks:
        ref.store_block(blk)
    assert chan.ledger.state_fingerprint() == \
        ref.ledger.state_fingerprint()


# ---------------------------------------------------------------------------
# gossip comm: bounded send retries
# ---------------------------------------------------------------------------

@pytest.fixture()
def gossip_pair():
    pytest.importorskip("grpc")
    from fabric_mod_tpu.gossip.comm import GRPCGossipNetwork
    nets = []

    def make(**kw):
        net = GRPCGossipNetwork("127.0.0.1:0", **kw)
        net.start()
        nets.append(net)
        return net
    yield make
    for net in nets:
        net.stop()


def test_gossip_send_retry_survives_transient_fault(gossip_pair):
    """One injected send failure must cost a retry, not the message:
    the payload arrives after the transient fault clears."""
    a = gossip_pair(retrier=Retrier(base_s=0.01, max_s=0.02, jitter=0.0,
                                    max_attempts=3, name="test-gsend"))
    b = gossip_pair()
    got = []
    b.register(b.listen_endpoint, lambda pki, env: got.append(env))
    plan = faults.FaultPlan().add("gossip.comm.send", nth=1)
    with faults.active(plan):
        assert a.send("a-ep", b"pki-a", b.listen_endpoint, b"hello")
        assert settle(lambda: got == [b"hello"], timeout=10.0), got
    assert plan.fires() == 1


def test_gossip_send_without_retries_drops(gossip_pair):
    """The paired kill: same fault, retries disabled — the message is
    gone (the pre-PR behavior, now opt-in via the knob)."""
    a = gossip_pair(send_retries=0)
    b = gossip_pair()
    got = []
    b.register(b.listen_endpoint, lambda pki, env: got.append(env))
    plan = faults.FaultPlan().add("gossip.comm.send", nth=1)
    with faults.active(plan):
        assert a.send("a-ep", b"pki-a", b.listen_endpoint, b"dropped")
        assert settle(lambda: plan.fires() == 1, timeout=10.0)
        # the sender gave up (no retry attempt followed the fault) —
        # send a SECOND message to prove the drain advanced past it
        assert a.send("a-ep", b"pki-a", b.listen_endpoint, b"after")
        assert settle(lambda: got == [b"after"], timeout=10.0), got
    assert plan.calls("gossip.comm.send") == 2      # no retry happened


# ---------------------------------------------------------------------------
# broadcast: NOT_LEADER is typed, retried, and survives a leader crash
# ---------------------------------------------------------------------------

def test_broadcast_retries_not_leader_then_succeeds():
    """Unit pair: without the retrier (budget 1) a leaderless window
    kills the submission; with it, the same window costs retries."""
    from fabric_mod_tpu.orderer.broadcast import Broadcast
    from fabric_mod_tpu.orderer.consensus import NotLeaderError
    from fabric_mod_tpu.protos import messages as m

    class FlakyChain:
        def __init__(self, fail_n):
            self.fail_n = fail_n
            self.orders = []

        def order(self, env, seq):
            if self.fail_n > 0:
                self.fail_n -= 1
                raise NotLeaderError("election in progress",
                                     leader_hint="o2")
            self.orders.append(env)

    class FakeSupport:
        def __init__(self, chain):
            self.chain = chain
            self.processor = self

        def process_normal_msg(self, env):
            return 0

    class FakeRegistrar:
        def __init__(self, support):
            self._support = support

        def broadcast_channel_support(self, env):
            return self._support, False

    env = m.Envelope(payload=b"p", signature=b"s")
    chain = FlakyChain(fail_n=2)
    bcast = Broadcast(FakeRegistrar(FakeSupport(chain)),
                      retrier=Retrier(base_s=0.0, jitter=0.0,
                                      max_attempts=5,
                                      retry_on=(NotLeaderError,),
                                      sleep=lambda s: None,
                                      name="test-bcast"))
    bcast.submit(env)                      # survives the window
    assert len(chain.orders) == 1

    chain2 = FlakyChain(fail_n=2)
    no_retry = Broadcast(FakeRegistrar(FakeSupport(chain2)),
                         retrier=Retrier(base_s=0.0, jitter=0.0,
                                         max_attempts=1,
                                         retry_on=(NotLeaderError,),
                                         sleep=lambda s: None,
                                         name="test-bcast0"))
    with pytest.raises(NotLeaderError) as ei:
        no_retry.submit(env)               # the pre-PR fate, typed
    assert ei.value.leader_hint == "o2"
    assert chain2.orders == []


def test_raft_leader_crash_broadcast_retry_manualclock(tmp_path):
    """The tentpole scenario on the deterministic clock tier: the raft
    leader crashes; a broadcast submitted during the leaderless window
    is REJECTED typed (NotLeaderError — the old path silently dropped
    it), retried on a schedule whose sleeps ADVANCE the fake clock,
    and lands once the re-election completes.  No wall-clock timing
    decides the outcome."""
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.channelconfig import genesis
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    from fabric_mod_tpu.msp import ca as calib
    from fabric_mod_tpu.msp.identities import SigningIdentity
    from fabric_mod_tpu.orderer.broadcast import Broadcast
    from fabric_mod_tpu.orderer.consensus import NotLeaderError
    from fabric_mod_tpu.orderer.raft import RaftTransport
    from fabric_mod_tpu.orderer.raftchain import RaftChain
    from fabric_mod_tpu.orderer.registrar import Registrar
    from fabric_mod_tpu.protos import protoutil

    csp = SwCSP()
    org_ca = calib.CA("ca.org1", "Org1")
    ord_ca = calib.CA("ca.orderer", "OrdererOrg")
    blk = genesis.standard_network(
        "faultchan", {"Org1": [calib.cert_pem(org_ca.cert)]},
        {"OrdererOrg": [calib.cert_pem(ord_ca.cert)]},
        consensus_type="etcdraft", batch_timeout="100ms",
        max_message_count=1)
    clock = ManualClock()
    transport = RaftTransport()
    ids = ["f0", "f1", "f2"]
    registrars = {}
    for idx, i in enumerate(ids):
        oc, ok = ord_ca.issue(f"{i}.orderer", "OrdererOrg",
                              ous=["orderer"])
        signer = SigningIdentity("OrdererOrg", oc, calib.key_pem(ok),
                                 csp)

        def factory(support, i=i, idx=idx):
            return RaftChain(i, ids, transport,
                             str(tmp_path / f"{i}.wal"), support,
                             clock=clock,
                             rng=random.Random(idx + 1))
        reg = Registrar(str(tmp_path / i), signer, csp,
                        chain_factory=factory)
        reg.create_channel(blk)
        registrars[i] = reg
    try:
        supports = {i: registrars[i].get_chain("faultchan")
                    for i in ids}
        chains = {i: s.chain for i, s in supports.items()}
        assert advance_until(clock,
                             lambda: leader_known_by_all(chains))
        leader_id = next(i for i, c in chains.items() if c.is_leader)
        # crash the leader AND cut one follower: the survivor cannot
        # win an election alone (1 of 3 votes), so the leaderless
        # window is STABLE — no race against a fast re-election when
        # we assert the typed rejection below
        followers = [i for i in ids if i != leader_id]
        survivor, healed_later = followers[0], followers[1]
        transport.partitioned.update(
            {leader_id, f"{leader_id}:chain",
             healed_later, f"{healed_later}:chain"})
        # step into the leaderless window: the survivor campaigns,
        # clearing its leader_id — and stays there (no quorum)
        assert advance_until(
            clock, lambda: chains[survivor].leader_id is None)

        ccert, ckey = org_ca.issue("client@org1", "Org1",
                                   ous=["client"])
        client = SigningIdentity("Org1", ccert, calib.key_pem(ckey),
                                 csp)
        b = RWSetBuilder()
        b.add_write("cc", "crashkey", b"survives")
        env = protoutil.create_signed_tx("faultchan", "cc",
                                         b.build().encode(), client,
                                         [client])

        # submitting WITHOUT retry during the window: typed rejection
        # (the fault that kills the old path — which silently lost it)
        with pytest.raises(NotLeaderError):
            Broadcast(registrars[survivor],
                      retrier=Retrier(max_attempts=1,
                                      retry_on=(NotLeaderError,),
                                      sleep=lambda s: None,
                                      name="t-noretry")).submit(env)

        # heal the second follower: a 2/3 quorum is possible again,
        # but only retry-loop clock advances can complete the election
        transport.partitioned.difference_update(
            {healed_later, f"{healed_later}:chain"})

        # with the retrier, each backoff ADVANCES the fake clock, so
        # the election completes inside the retry loop
        def sleep_and_settle(s):
            for _ in range(max(1, int(s / 0.02))):
                clock.advance(0.02)
                settle(lambda: False, timeout=0.01, poll=0.005)

        bcast = Broadcast(
            registrars[survivor],
            retrier=Retrier(base_s=0.1, max_s=0.2, jitter=0.0,
                            max_attempts=200, clock=clock,
                            retry_on=(NotLeaderError,),
                            sleep=sleep_and_settle, name="t-bretry"))
        bcast.submit(env)                  # survives the crash window
        live = [i for i in ids if i != leader_id]
        assert settle(
            lambda: all(supports[i].store.height >= 2 for i in live),
            timeout=20.0), {i: supports[i].store.height for i in live}

        # the IN-FLIGHT window: a submit that passed admission while a
        # leader was alive but is dequeued by the run loop during the
        # leaderless window must be PARKED and ordered once a leader
        # exists again — the old loop dropped it silently after the
        # caller had already been told "accepted"
        from fabric_mod_tpu.orderer.raftchain import _Submit
        leader2 = next(i for i in live if chains[i].is_leader)
        other = next(i for i in live if i != leader2)
        transport.partitioned.update({leader2, f"{leader2}:chain"})
        assert advance_until(
            clock, lambda: chains[other].leader_id is None)
        b2 = RWSetBuilder()
        b2.add_write("cc", "parkedkey", b"held")
        env2 = protoutil.create_signed_tx(
            "faultchan", "cc", b2.build().encode(), client, [client])
        # inject straight into the run-loop queue: the post-admission,
        # pre-dispatch envelope the crash raced
        chains[other]._q.put(_Submit(env2.encode(), False, 0))
        for _ in range(10):                # dequeued while leaderless
            clock.advance(0.02)
            settle(lambda: False, timeout=0.02, poll=0.01)
        assert supports[other].store.height == 2   # parked, not ordered
        # the FIRST crashed leader rejoins: quorum again.  Keep
        # ADVANCING until the parked submit commits — the rejoining
        # node's partition-inflated term forces several election
        # rounds (each needs fake time), and `other`'s longer log
        # means only it can win; the winner flushes the park
        transport.partitioned.difference_update(
            {leader_id, f"{leader_id}:chain"})
        assert advance_until(
            clock, lambda: supports[other].store.height >= 3,
            max_steps=600), supports[other].store.height
    finally:
        for reg in registrars.values():
            reg.close()
