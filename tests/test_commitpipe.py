"""Commit-pipeline tests: the pipelined committer must be verdict- and
state-identical to the synchronous path over streams that interleave
barrier blocks (config txs, VALIDATION_PARAMETER writes, lifecycle-ns
writes) with ordinary blocks — the FastFabric/StreamChain overlap is
only legal because `needs_barrier` drains the pipeline at exactly the
blocks whose commit changes what staging reads.  Plus: depth=1 ≡
serial, barrier/overlap ordering properties, error propagation, the
observability surface, and the event-driven gossip drain.

Expensive arms (signing + pure-python verification on wheel-less
containers) run ONCE via module-scoped fixtures and are shared."""
import threading
import time

import pytest

from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
from fabric_mod_tpu.ledger import KvLedger
from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
from fabric_mod_tpu.peer import (Committer, PipelinedCommitter,
                                 TxValidator, ValidationInfoProvider,
                                 ValidatorCommitTarget, commitpipe)
from fabric_mod_tpu.peer.lifecycle import LIFECYCLE_NS
from fabric_mod_tpu.peer.txvalidator import VALIDATION_PARAMETER
from fabric_mod_tpu.policy import ApplicationPolicyEvaluator, from_string
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil

V = m.TxValidationCode
CHANNEL = "pipech"


@pytest.fixture(scope="module")
def world():
    csp = SwCSP()
    msps, signers = [], {}
    for org in ("Org1", "Org2", "Org3"):
        ca = calib.CA(f"ca.{org.lower()}", org)
        msps.append(Msp(org, csp, [ca.cert]))
        cert, key = ca.issue(f"peer0.{org.lower()}", org, ous=["peer"])
        signers[org] = SigningIdentity(org, cert, calib.key_pem(key), csp)
    return dict(csp=csp, mgr=MspManager(msps), signers=signers)


def _policy(dsl: str) -> bytes:
    return m.ApplicationPolicy(signature_policy=from_string(dsl)).encode()


CC_POLICY = "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')"


def _tx(world, rwset: bytes, endorsers=("Org1", "Org2")):
    s = world["signers"]
    return protoutil.create_signed_tx(
        CHANNEL, "mycc", rwset, s["Org1"],
        [s[o] for o in endorsers])


def _write(ns, key, val=b"v"):
    b = RWSetBuilder()
    b.add_write(ns, key, val)
    return b.build().encode()


def _vp_write(key, policy_bytes):
    b = RWSetBuilder()
    b.add_metadata_write("mycc", key, VALIDATION_PARAMETER, policy_bytes)
    return b.build().encode()


def _config_tx(world, tag):
    s = world["signers"]
    ch = protoutil.make_channel_header(m.HeaderType.CONFIG, CHANNEL,
                                       tx_id=f"cfg-{tag}")
    sh = protoutil.make_signature_header(s["Org1"].serialize(), b"n%d" % tag)
    payload = protoutil.make_payload(ch, sh, b"config-%d" % tag)
    return protoutil.sign_envelope(payload, s["Org1"])


def _mixed_stream(world):
    """12 blocks interleaving every barrier flavor with ordinary
    blocks; the stream's final flags DEPEND on barrier-correct
    ordering (stage-ahead across a barrier flips a verdict)."""
    blocks, prev = [], b""

    def blk(envs):
        b = protoutil.new_block(len(blocks), prev, envs)
        blocks.append(b.encode())
        return protoutil.block_header_hash(b.header)

    prev = blk([_tx(world, _write("mycc", "k0")),
                _tx(world, _write("mycc", "pinned", b"v0"))])
    # VALIDATION_PARAMETER barrier: pin "pinned" to Org3 only
    prev = blk([_tx(world, _vp_write("pinned", _policy("'Org3.peer'")))])
    # the very next block writes "pinned" with Org1+Org2: under the
    # committed pin -> ENDORSEMENT_POLICY_FAILURE; a stage-ahead bug
    # sees no pin and wrongly passes the cc-wide 2-of-3
    prev = blk([_tx(world, _write("mycc", "pinned", b"v1")),
                _tx(world, _write("mycc", "k1"))])
    prev = blk([_tx(world, _write("mycc", "k2"))])
    # re-pin to Org1 (endorsed by Org3: changing a pinned key's VP
    # must itself satisfy the CURRENT pin — fail-closed)
    prev = blk([_tx(world, _vp_write("pinned", _policy("'Org1.peer'")),
                    endorsers=("Org3",))])
    # under the new Org1 pin this write is VALID again
    prev = blk([_tx(world, _write("mycc", "pinned", b"v2"))])
    # lifecycle-namespace write: barrier via written_ns
    prev = blk([_tx(world, _write(LIFECYCLE_NS, "mycc#def", b"d"))])
    prev = blk([_tx(world, _write("mycc", "k3"))])
    # CONFIG barrier: the applier (wired per-arm below) flips the
    # default policy for namespace "cfgcc" to Org3-only
    prev = blk([_config_tx(world, len(blocks))])
    # next block's cfgcc tx endorsed Org1+Org2: EPF under the new
    # config, VALID if staged before the config applied
    b = RWSetBuilder()
    b.add_write("cfgcc", "ck", b"v")
    prev = blk([protoutil.create_signed_tx(
        CHANNEL, "cfgcc", b.build().encode(), world["signers"]["Org1"],
        [world["signers"][o] for o in ("Org1", "Org2")])])
    prev = blk([_tx(world, _write("mycc", "k4")),
                _tx(world, _write("mycc", "k5"))])
    prev = blk([_tx(world, _write("mycc", "k6"))])
    return blocks


@pytest.fixture(scope="module")
def stream(world):
    return _mixed_stream(world)


def _make_target(world, root):
    """Fresh (ledger, validator) wired for key-level VPs, per-ns
    validation info, and a config applier that mutates what staging
    reads (the barrier hazards under test)."""
    led = KvLedger(str(root), CHANNEL)
    vinfo = ValidationInfoProvider(_policy(CC_POLICY))

    def state_vp(ns, key):
        meta = led.state.get_metadata(ns, key)
        return meta.get(VALIDATION_PARAMETER) if meta else None

    def config_apply(_env):
        vinfo.set_policy("cfgcc", _policy("'Org3.peer'"))

    validator = TxValidator(
        CHANNEL, world["mgr"], ApplicationPolicyEvaluator(world["mgr"]),
        FakeBatchVerifier(world["csp"]), vinfo,
        tx_id_exists=led.tx_id_exists, config_apply=config_apply,
        state_metadata=state_vp)
    return led, validator


def _run_sync(world, blocks, root):
    led, validator = _make_target(world, root)
    committer = Committer(validator, led)
    flags = [list(committer.store_block(m.Block.decode(raw)))
             for raw in blocks]
    return flags, led.state_fingerprint()


def _run_pipelined(world, blocks, root, depth, target_wrap=None):
    led, validator = _make_target(world, root)
    target = ValidatorCommitTarget(validator, led)
    if target_wrap is not None:
        target = target_wrap(target)
    flags = []
    pipe = PipelinedCommitter(target, depth=depth,
                              on_commit=lambda _b, f: flags.append(list(f)))
    for raw in blocks:
        pipe.submit(m.Block.decode(raw))
    pipe.flush(timeout_s=120.0)
    pipe.close()
    return flags, led.state_fingerprint(), pipe


@pytest.fixture(scope="module")
def sync_ref(world, stream, tmp_path_factory):
    return _run_sync(world, stream,
                     tmp_path_factory.mktemp("cp_sync"))


@pytest.fixture(scope="module")
def pipe_ref(world, stream, tmp_path_factory):
    return _run_pipelined(world, stream,
                          tmp_path_factory.mktemp("cp_pipe"), depth=4)


def test_differential_mixed_barrier_stream(sync_ref, pipe_ref):
    """Pipelined flags + state are bit-identical to sync over a stream
    whose verdicts depend on barrier-correct ordering."""
    sync_flags, sync_fp = sync_ref
    pipe_flags, pipe_fp, pipe = pipe_ref
    assert pipe_flags == sync_flags
    assert pipe_fp == sync_fp
    assert pipe.error is None
    # the stream exercised real signal: the Org3-pin violation and the
    # post-config cfgcc tx both failed; everything else committed
    flat = [f for per in sync_flags for f in per]
    assert flat.count(V.ENDORSEMENT_POLICY_FAILURE) == 2
    assert flat.count(V.VALID) == len(flat) - 2


def _mixed_verdicts(world):
    """Three blocks of sixteen: valid, under-endorsed, duplicate-
    endorser and tampered-creator-signature transactions, so the
    flags carry three codes."""
    blocks, prev = [], b""
    for n in range(3):
        envs = []
        for j in range(16):
            i = 16 * n + j
            if i % 7 == 3:
                endorsers = ("Org1",)                 # under 2-of-3
            elif i % 7 == 5:
                endorsers = ("Org1", "Org1")          # one org twice
            else:
                endorsers = ("Org1", "Org2")
            env = _tx(world, _write("mycc", f"m{i}"), endorsers)
            if i % 11 == 9:
                env.signature = bytes(reversed(env.signature))
            envs.append(env)
        b = protoutil.new_block(n, prev, envs)
        prev = protoutil.block_header_hash(b.header)
        blocks.append(b.encode())
    return blocks


def _inblock_override(world):
    """A VALIDATION_PARAMETER pin and writes of the pinned key in the
    SAME block (the candidates `finish` resolves in block order), then
    a write under the committed pin."""
    pin = _policy("'Org3.peer'")
    blocks, prev = [], b""
    for envs in (
            [_tx(world, _write("mycc", "pinned", b"v0")),
             _tx(world, _write("mycc", "o0"))],
            [_tx(world, _vp_write("pinned", pin)),
             _tx(world, _write("mycc", "pinned", b"v1")),       # EPF
             _tx(world, _write("mycc", "pinned", b"v2"),
                 endorsers=("Org3",)),                           # VALID
             _tx(world, _write("mycc", "o1"))],
            [_tx(world, _write("mycc", "pinned", b"v3")),       # EPF
             _tx(world, _write("mycc", "o2"))]):
        b = protoutil.new_block(len(blocks), prev, envs)
        prev = protoutil.block_header_hash(b.header)
        blocks.append(b.encode())
    return blocks


_CORPORA = {
    "barrier-stream": (_mixed_stream, {V.VALID,
                                       V.ENDORSEMENT_POLICY_FAILURE}),
    "mixed-verdicts": (_mixed_verdicts, {V.VALID,
                                         V.ENDORSEMENT_POLICY_FAILURE,
                                         V.BAD_CREATOR_SIGNATURE}),
    "inblock-override": (_inblock_override, {V.VALID,
                                             V.ENDORSEMENT_POLICY_FAILURE}),
}


@pytest.fixture(scope="module")
def corpora(world, stream, sync_ref, tmp_path_factory):
    """name -> (blocks, flags and state fingerprint of `validate` +
    commit, block after block: the synchronous Committer)."""
    got = {"barrier-stream": (stream,) + tuple(sync_ref)}
    for name in ("mixed-verdicts", "inblock-override"):
        blocks = _CORPORA[name][0](world)
        got[name] = (blocks,) + _run_sync(
            world, blocks, tmp_path_factory.mktemp("cp_" + name))
    return got


@pytest.mark.parametrize("depth", [1, commitpipe.DEPTH])
@pytest.mark.parametrize("corpus", sorted(_CORPORA))
def test_pipelined_matches_sync(world, corpora, tmp_path, corpus, depth):
    """Every corpus through PipelinedCommitter at depth 1 (the serial
    differential) and at DEPTH (what every engine is built at): flags
    and state fingerprint are those of `validate` + commit."""
    blocks, sync_flags, sync_fp = corpora[corpus]
    flags, fp, pipe = _run_pipelined(world, blocks, tmp_path / "p",
                                     depth=depth)
    assert pipe.error is None
    assert flags == sync_flags
    assert fp == sync_fp
    assert {f for per in sync_flags for f in per} == _CORPORA[corpus][1]


def test_every_engine_is_built_at_the_one_depth(world, tmp_path):
    """The deliver client's engine, the shard router's and a bare
    PipelinedCommitter all take commitpipe.DEPTH when no caller says
    otherwise: one number, one place."""
    from fabric_mod_tpu.peer import DeliverClient
    from fabric_mod_tpu.sharding import ChannelShardRouter
    led, validator = _make_target(world, tmp_path / "one")
    target = ValidatorCommitTarget(validator, led)
    bare = PipelinedCommitter(target)
    client = DeliverClient(target, source=None)
    router = ChannelShardRouter(
        n_slices=1,
        verifier_factory=lambda i, mesh: FakeBatchVerifier(world["csp"]))
    try:
        router.add_channel(CHANNEL, target)
        assert commitpipe.DEPTH == 2
        assert bare.depth == client._pipe.depth == \
            router.pipeline_for(CHANNEL).depth == commitpipe.DEPTH
    finally:
        router.close()
        client._pipe.close()
        bare.close()


@pytest.mark.parametrize("name,value", [
    ("FABRIC_MOD_TPU_TENSOR_POLICY", "1"),
    ("FABRIC_MOD_TPU_COMMIT_PIPELINE", "3"),
    ("FABRIC_MOD_TPU_SHARD_DEPTH", "3"),
    ("FABRIC_MOD_TPU_PRECISION", "high"),
])
def test_retired_knob_is_declared_nowhere_and_read_by_nothing(
        world, corpora, tmp_path, monkeypatch, name, value):
    """The four names the chip ruled on: gone from the registry, and a
    three-block chain validated with the name set gives the flags and
    the fingerprint of the run without it, through the engine at the
    depth it always has."""
    from fabric_mod_tpu.utils import knobs
    assert name not in knobs.declared()
    blocks, want_flags, want_fp = corpora["mixed-verdicts"]
    assert len(blocks) == 3
    monkeypatch.setenv(name, value)
    assert _run_sync(world, blocks, tmp_path / "s") == (want_flags,
                                                        want_fp)
    led, validator = _make_target(world, tmp_path / "p")
    flags = []
    pipe = PipelinedCommitter(
        ValidatorCommitTarget(validator, led),
        on_commit=lambda _b, f: flags.append(list(f)))
    assert pipe.depth == commitpipe.DEPTH
    for raw in blocks:
        pipe.submit(m.Block.decode(raw))
    assert pipe.flush(timeout_s=120.0)
    pipe.close()
    assert (flags, led.state_fingerprint()) == (want_flags, want_fp)


class _Recorder:
    """Wraps a commit target recording stage STARTS and commit ENDS —
    the two timestamps the pipeline's ordering contracts speak to."""

    def __init__(self, target, commit_delay=0.0):
        self._target = target
        self.ledger = target.ledger
        self.events = []
        self._lock = threading.Lock()
        self._delay = commit_delay

    def _mark(self, kind, num):
        with self._lock:
            self.events.append((kind, num))

    def stage_block(self, block):
        self._mark("stage", block.header.number)
        return self._target.stage_block(block)

    def commit_staged(self, staged):
        if self._delay:
            time.sleep(self._delay)
        flags = self._target.commit_staged(staged)
        self._mark("commit", staged.block.header.number)
        return flags


def _simple_blocks(world, n, txs=1):
    blocks, prev = [], b""
    for i in range(n):
        envs = [_tx(world, _write("mycc", f"s{i}-{j}"))
                for j in range(txs)]
        b = protoutil.new_block(i, prev, envs)
        prev = protoutil.block_header_hash(b.header)
        blocks.append(b.encode())
    return blocks


@pytest.fixture(scope="module")
def simple4(world):
    return _simple_blocks(world, 4)


def test_overlap_and_depth1_ordering(world, simple4, tmp_path):
    """depth>1 stages N+1 while commit(N) is still running; depth=1
    never does (the synchronous contract)."""
    def slow(target):
        return _Recorder(target, commit_delay=0.5)
    _, _, pipe = _run_pipelined(world, simple4, tmp_path / "deep",
                                depth=4, target_wrap=slow)
    ev = pipe._channel.events
    overlapped = any(
        ev.index(("stage", n + 1)) < ev.index(("commit", n))
        for n in range(len(simple4) - 1))
    assert overlapped, ev

    _, _, pipe1 = _run_pipelined(world, simple4, tmp_path / "serial",
                                 depth=1, target_wrap=slow)
    ev1 = pipe1._channel.events
    for n in range(len(simple4) - 1):
        assert ev1.index(("stage", n + 1)) > ev1.index(("commit", n)), ev1


def test_barrier_blocks_drain_the_pipeline(world, tmp_path):
    """stage(B+1) must wait for commit(B) when B needs a barrier, even
    at depth 4."""
    blocks, prev = [], b""
    for i in range(5):
        if i == 2:
            envs = [_tx(world, _vp_write("pinned",
                                         _policy("'Org3.peer'")))]
        else:
            envs = [_tx(world, _write("mycc", f"b{i}"))]
        b = protoutil.new_block(i, prev, envs)
        prev = protoutil.block_header_hash(b.header)
        blocks.append(b.encode())
    _, _, pipe = _run_pipelined(world, blocks, tmp_path / "bar",
                                depth=4, target_wrap=_Recorder)
    ev = pipe._channel.events
    assert ev.index(("stage", 3)) > ev.index(("commit", 2)), ev


class _BombTarget:
    """Commit target whose commit always fails (stage is fine)."""

    def __init__(self, target):
        self._target = target
        self.ledger = target.ledger

    def stage_block(self, block):
        return self._target.stage_block(block)

    def commit_staged(self, _staged):
        raise RuntimeError("commit bomb")


def test_commit_error_propagates_to_producer(world, simple4, tmp_path):
    """A failed commit surfaces on flush() and poisons submit()."""
    led, validator = _make_target(world, tmp_path / "err")
    pipe = PipelinedCommitter(
        _BombTarget(ValidatorCommitTarget(validator, led)), depth=2)
    pipe.submit(m.Block.decode(simple4[0]))
    with pytest.raises(RuntimeError, match="commit bomb"):
        pipe.flush(timeout_s=30.0)
    with pytest.raises(RuntimeError, match="commit bomb"):
        pipe.submit(m.Block.decode(simple4[1]))
    assert pipe.error is not None
    pipe.close()


def test_misordered_submit_rejected_without_poisoning(world, simple4,
                                                      tmp_path):
    """Stale redeliveries AND too-early (gap) blocks fail THEIR caller
    at the submit gate (sync-path arbitration) — neither reaches the
    commit loop to poison the shared pipe for unrelated callers."""
    from fabric_mod_tpu.ledger.kvledger import LedgerError
    led, validator = _make_target(world, tmp_path / "stale")
    pipe = PipelinedCommitter(ValidatorCommitTarget(validator, led),
                              depth=2)
    with pytest.raises(LedgerError, match="out of order"):
        pipe.submit(m.Block.decode(simple4[1]))        # gap (expects 0)
    assert pipe.error is None
    assert pipe.store_block(m.Block.decode(simple4[0])) == [V.VALID]
    with pytest.raises(LedgerError, match="out of order"):
        pipe.store_block(m.Block.decode(simple4[0]))   # stale duplicate
    assert pipe.error is None                          # not poisoned
    assert pipe.store_block(m.Block.decode(simple4[1])) == [V.VALID]
    assert led.height == 2
    pipe.close()


def test_store_block_facade_returns_final_flags(world, tmp_path):
    blocks = _simple_blocks(world, 2, txs=2)
    led, validator = _make_target(world, tmp_path / "sf")
    pipe = PipelinedCommitter(ValidatorCommitTarget(validator, led),
                              depth=2)
    for raw in blocks:
        flags = pipe.store_block(m.Block.decode(raw))
        assert flags == [V.VALID, V.VALID]
    assert led.height == 2
    pipe.close()


def test_pipeline_metrics_exported(pipe_ref):
    """The opsserver /metrics surface (render_prometheus of the
    default provider — what OperationsServer serves) carries the
    commitpipe histograms/gauge/counters after a pipelined run."""
    from fabric_mod_tpu.observability.metrics import default_provider
    text = default_provider().render_prometheus()
    for name in ("fabric_commitpipe_stage_seconds_bucket",
                 "fabric_commitpipe_await_seconds_bucket",
                 "fabric_commitpipe_commit_seconds_bucket",
                 "fabric_commitpipe_occupancy",
                 "fabric_commitpipe_barriers_total",
                 "fabric_commitpipe_blocks_total"):
        assert name in text, name
    # the mixed stream crossed >= 4 barriers (2 vp, 1 lifecycle,
    # 1 config); other tests in this process may add more
    barriers = [line for line in text.splitlines()
                if line.startswith("fabric_commitpipe_barriers_total ")]
    assert barriers and float(barriers[0].split()[-1]) >= 4


# -- the gossip drain consumer -------------------------------------------

class _StubChannel:
    """Channel-shaped stub for GossipStateProvider: a ledger, the sync
    store_block, and optionally a shared commit pipeline."""

    def __init__(self, world, root, depth=0):
        self.ledger, validator = _make_target(world, root)
        self._target = ValidatorCommitTarget(validator, self.ledger)
        self._pipe = (PipelinedCommitter(self._target, depth=depth)
                      if depth > 0 else None)

    def commit_pipeline(self):
        return self._pipe

    def store_block(self, block):
        return self._target.commit_staged(self._target.stage_block(block))


def test_gossip_drain_through_pipeline(world, simple4, tmp_path):
    """The drain loop feeds the channel's shared pipeline when one is
    enabled; out-of-order arrivals still commit, in order."""
    from fabric_mod_tpu.gossip.state import GossipStateProvider
    chan = _StubChannel(world, tmp_path / "gp", depth=3)
    prov = GossipStateProvider(chan)
    decoded = [m.Block.decode(raw) for raw in simple4]
    # arrive out of order: evens then odds
    for b in decoded[::2]:
        prov.add_block(b)
    for b in decoded[1::2]:
        prov.add_block(b)
    assert prov.drain() == len(simple4)
    assert prov.flush(timeout_s=120.0)
    assert chan.ledger.height == len(simple4)
    for i in range(len(simple4)):
        blk = chan.ledger.get_block_by_number(i)
        assert list(protoutil.block_txflags(blk)) == [V.VALID]
    chan.commit_pipeline().close()


def test_drain_resyncs_buffer_after_commit_failure(world, simple4,
                                                   tmp_path):
    """A block popped into a failing committer must stay requestable:
    drain() rewinds the buffer to the committed height, so redelivery
    is accepted instead of rejected as stale (no permanent stall)."""
    from fabric_mod_tpu.gossip.state import GossipStateProvider
    chan = _StubChannel(world, tmp_path / "rs", depth=0)
    orig, armed = chan.store_block, [True]

    def flaky(block):
        if block.header.number == 1 and armed[0]:
            armed[0] = False
            raise RuntimeError("transient commit failure")
        return orig(block)
    chan.store_block = flaky
    prov = GossipStateProvider(chan)
    for raw in simple4:
        prov.add_block(m.Block.decode(raw))
    with pytest.raises(RuntimeError, match="transient"):
        prov.drain()
    # block 1 failed after being popped; the rewind re-admits it and
    # the gap stays visible to anti-entropy (heap holds 2 and 3)
    assert prov.buffer.next_seq == chan.ledger.height == 1
    assert prov.buffer.missing_range() == range(1, 2)
    assert prov.add_block(m.Block.decode(simple4[1]))
    assert prov.drain() == 3
    assert chan.ledger.height == len(simple4)
    assert prov.buffer.missing_range() is None

    # empty-heap variant: a known-but-lost block (popped, committer
    # failed, resync'd, nothing else buffered) must still be reported
    from fabric_mod_tpu.gossip.state import PayloadsBuffer
    buf = PayloadsBuffer(0)
    assert buf.push(m.Block.decode(simple4[0]))
    assert buf.pop_in_order() is not None
    buf.resync(0)
    assert buf.missing_range() == range(0, 1)


def test_event_driven_drain_wakeup(world, tmp_path):
    """start()'s drain loop commits on the add_block SIGNAL: with the
    anti-entropy interval cranked to 30 s, only the event path can
    commit this fast (the old 50 ms poll is gone; a signal-free loop
    at this interval would sit idle for 30 s)."""
    from fabric_mod_tpu.gossip.state import GossipStateProvider
    blocks = _simple_blocks(world, 2)
    chan = _StubChannel(world, tmp_path / "ev", depth=0)
    prov = GossipStateProvider(chan)
    prov.start(interval_s=30.0)
    try:
        for raw in blocks:
            prov.add_block(m.Block.decode(raw))
        deadline = time.monotonic() + 10.0
        while (chan.ledger.height < len(blocks)
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert chan.ledger.height == len(blocks)
    finally:
        prov.stop()
