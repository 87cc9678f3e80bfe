"""ChannelShardRouter: pin N channels' commit engines to mesh slices
behind one shared cross-channel verify service.

The router is the ONLY stateful layer of the sharding subsystem; it
composes the three pieces:

* a :class:`~fabric_mod_tpu.sharding.shardmap.ShardMap` deciding
  which slice each channel lives on (least-loaded, rebalance on
  leave);
* one verifier PER SLICE (production: ``TpuVerifier(mesh=slice)``
  over ``parallel.slice_meshes``; host mode: whatever
  `verifier_factory` returns) — each channel's validator stages its
  whole-block dispatches against its slice's verifier, so N channels'
  block programs run side by side on disjoint devices;
* one :class:`~fabric_mod_tpu.sharding.verifyservice.
  CrossChannelVerifyService` over those verifiers — the shared
  small-verify front door every channel's gossip/MCS/config checks
  coalesce through;
* one :class:`~fabric_mod_tpu.peer.commitpipe.PipelinedCommitter`
  per channel, consumer-labeled by slice, with the
  rebuild-on-poison contract: a failed pipe surfaces its error to
  the caller that hit it, then the next `pipeline_for` drains the
  corpse and rebuilds from the committed height — one bad block
  never bricks a channel, and (the sharding-specific half) never
  touches any OTHER channel's pipe or the shared flusher.

Channel join/leave goes through `add_channel`/`remove_channel`; a
leave may return the map's rebalance plan, which the router executes
by draining the moving channel's pipe and rebuilding it pinned to the
new slice (its verify handle re-resolves the slice verifier on every
call, so in-flight small verifies need no coordination).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

from fabric_mod_tpu.bccsp.api import VerifyItem
from fabric_mod_tpu.concurrency import RegisteredLock
from fabric_mod_tpu.observability.logging import get_logger
from fabric_mod_tpu.observability.metrics import (MetricOpts,
                                                  default_provider)
from fabric_mod_tpu.peer.commitpipe import DEPTH, PipelinedCommitter
from fabric_mod_tpu.sharding.shardmap import ShardMap
from fabric_mod_tpu.sharding.verifyservice import CrossChannelVerifyService
from fabric_mod_tpu.utils import knobs

log = get_logger("sharding.router")

_CHANNELS_OPTS = MetricOpts(
    "fabric", "sharding", "channels",
    help="Channels currently placed on each mesh slice.",
    label_names=("slice",))
_MOVES_OPTS = MetricOpts(
    "fabric", "sharding", "rebalance_moves_total",
    help="Channels moved between slices by leave-time rebalancing.")
_REBUILD_OPTS = MetricOpts(
    "fabric", "sharding", "pipe_rebuilds_total",
    help="Poisoned per-channel commit pipelines discarded and rebuilt "
         "by the router (the channel-scoped recovery event; the "
         "shared verify service is untouched).")


def shard_count(default: int = 0) -> int:
    """The FABRIC_MOD_TPU_SHARDS knob: mesh slices the router carves;
    0/unset = sharding disabled (single-slice behavior)."""
    return max(0, knobs.get_int("FABRIC_MOD_TPU_SHARDS", default))


class ChannelVerifyHandle:
    """The per-channel verifier facade a Channel/TxValidator holds.

    The whole-block lane (`verify_many_async`, the validator's
    staging seam) goes STRAIGHT to the channel's slice verifier: it is
    already a full dispatch, pinned to the slice mesh.  The
    small-verify lane (`verify_many`, `submit` — MCS block checks,
    config signature sets) rides the SHARED cross-channel service,
    tagged, so it coalesces with every other channel's traffic.

    Slice resolution is per-call through the router, so a rebalance
    move retargets the handle with no handshake.
    """

    def __init__(self, router: "ChannelShardRouter", channel_id: str):
        self._router = router
        self.channel_id = channel_id

    @property
    def slice_index(self) -> int:
        return self._router.slice_of(self.channel_id)

    def _slice_verifier(self):
        return self._router.slice_verifier(self.channel_id)

    # -- whole-block lane (slice-pinned) ---------------------------------
    def verify_many_async(self, items: Sequence[VerifyItem]):
        return self._slice_verifier().verify_many_async(items)

    # -- small-verify lane (shared, coalesced, tagged) -------------------
    def verify_many(self, items: Sequence[VerifyItem]):
        return self._router.service.verify_many_for(
            self.channel_id, items)

    def submit(self, item: VerifyItem):
        return self._router.service.submit_for(self.channel_id, item)


class _Binding:
    __slots__ = ("channel_id", "target", "handle", "pipe",
                 "rebuild_lock")

    def __init__(self, channel_id: str, handle: ChannelVerifyHandle):
        self.channel_id = channel_id
        self.target = None                  # stage_block/commit_staged
        self.handle = handle
        self.pipe = None
        self.rebuild_lock = RegisteredLock(
            f"sharding.rebuild[{channel_id}]")


class ChannelShardRouter:
    """Placement + aggregation over `n_slices` mesh slices.

    `meshes`: per-slice meshes (`parallel.slice_meshes(n)`), or None
    for HOST mode (no jax — tests, CPU soak, TPU-less deployments);
    `verifier_factory(slice_index, mesh)` builds each slice's
    verifier (default: ``TpuVerifier(mesh=mesh)``).  The router owns
    the verifiers it builds and the shared service; `close()` tears
    all of it down after draining every channel's pipe.
    """

    def __init__(self, n_slices: Optional[int] = None, meshes=None,
                 verifier_factory: Optional[Callable] = None,
                 depth: int = DEPTH, rebalance: bool = True,
                 max_batch: int = 2048, deadline_s: float = 0.002):
        if n_slices is None:
            n_slices = max(1, shard_count())
        if meshes is not None and len(meshes) != n_slices:
            raise ValueError(
                f"{len(meshes)} meshes for {n_slices} slices")
        self.map = ShardMap(n_slices, rebalance=rebalance)
        self._depth = depth
        self._lock = RegisteredLock("sharding.router")
        self._bindings: Dict[str, _Binding] = {}
        self._closed = False
        if verifier_factory is None:
            from fabric_mod_tpu.bccsp.tpu import TpuVerifier
            verifier_factory = lambda i, mesh: TpuVerifier(mesh=mesh)
        self.verifiers = {
            i: verifier_factory(i, meshes[i] if meshes else None)
            for i in range(n_slices)}
        self.service = CrossChannelVerifyService(
            self.verifiers,
            lambda tag: self.map.slice_of(tag, default=0),
            max_batch=max_batch, deadline_s=deadline_s)
        prov = default_provider()
        self._m_channels = prov.gauge(_CHANNELS_OPTS)
        self._m_moves = prov.counter(_MOVES_OPTS)
        self._m_rebuilds = prov.counter(_REBUILD_OPTS)

    @property
    def n_slices(self) -> int:
        return self.map.n_slices

    # -- placement --------------------------------------------------------
    def slice_of(self, channel_id: str) -> int:
        with self._lock:
            return self.map.slice_of(channel_id)

    def slice_verifier(self, channel_id: str):
        return self.verifiers[self.slice_of(channel_id)]

    def _export_loads(self) -> None:
        for i, n in enumerate(self.map.loads()):
            self._m_channels.with_labels(str(i)).set(n)

    def add_channel(self, channel_id: str,
                    target=None) -> ChannelVerifyHandle:
        """Place a channel and return its verify handle.  `target`
        (stage_block/commit_staged/.ledger — a peer.Channel or a
        ValidatorCommitTarget) may be bound now or later via
        `bind_target` (a Channel needs the handle BEFORE it can be
        constructed)."""
        with self._lock:
            if self._closed:
                raise RuntimeError("shard router is closed")
            b = self._bindings.get(channel_id)
            if b is None:
                self.map.assign(channel_id)
                b = _Binding(channel_id,
                             ChannelVerifyHandle(self, channel_id))
                self._bindings[channel_id] = b
                self._export_loads()
            if target is not None:
                b.target = target
            return b.handle

    def bind_target(self, channel_id: str, target) -> None:
        with self._lock:
            self._bindings[channel_id].target = target

    def remove_channel(self, channel_id: str,
                       timeout_s: Optional[float] = None) -> List:
        """Drain + close the channel's pipe, free its slot, and
        execute the map's rebalance plan (each moved channel's pipe
        drains and rebuilds pinned to its new slice).  Returns the
        executed move list."""
        with self._lock:
            b = self._bindings.pop(channel_id, None)
            if b is None:
                return []
            moves = self.map.release(channel_id)
            self._export_loads()
        if b.pipe is not None:
            b.pipe.close(timeout_s)
        for cid, src, dst in moves:
            with self._lock:
                mb = self._bindings.get(cid)
            if mb is not None:
                # under the channel's rebuild lock: a concurrent
                # pipeline_for(cid) must not build a fresh engine
                # while the old one is still draining into the same
                # ledger — two engines never run against one ledger
                with mb.rebuild_lock:
                    with self._lock:
                        old, mb.pipe = mb.pipe, None
                    if old is not None:
                        old.close(timeout_s)   # drain on the OLD slice
            self._m_moves.add(1)
            log.info("sharding: channel %s moved slice %d -> %d",
                     cid, src, dst)
        return moves

    # -- per-channel commit engines --------------------------------------
    def pipeline_for(self, channel_id: str):
        """The channel's slice-pinned PipelinedCommitter, with the
        rebuild-on-poison contract: a healthy pipe is
        returned lock-free-ish; a poisoned/closed one is drained and
        replaced (two engines never run against one ledger at once).
        """
        def healthy():
            with self._lock:
                b = self._bindings.get(channel_id)
                if b is None:
                    raise KeyError(f"unplaced channel {channel_id!r}")
                pipe = b.pipe
            return b, (pipe if (pipe is not None and pipe.error is None
                                and not pipe.closed) else None)
        b, pipe = healthy()
        if pipe is not None:
            return pipe
        with b.rebuild_lock:
            b, pipe = healthy()
            if pipe is not None:
                return pipe                # another caller rebuilt
            with self._lock:
                if self._closed:
                    # a submit racing close(): rebuilding here would
                    # spawn workers over torn-down verifiers that
                    # nothing would ever join
                    raise RuntimeError("shard router is closed")
            if b.target is None:
                raise RuntimeError(
                    f"channel {channel_id!r} has no commit target")
            with self._lock:
                old, b.pipe = b.pipe, None
            if old is not None:
                old.close()                # drain the poisoned engine
                self._m_rebuilds.add(1)
            with self._lock:
                slice_idx = self.map.slice_of(channel_id, 0)
            pipe = PipelinedCommitter(
                b.target, depth=self._depth,
                consumer=f"shard{slice_idx}")
            with self._lock:
                b.pipe = pipe
            return pipe

    def submit_block(self, channel_id: str, block) -> None:
        self.pipeline_for(channel_id).submit(block)

    def store_block(self, channel_id: str, block):
        """Synchronous commit through the channel's pipe, with the
        one-retry-through-a-fresh-pipe arbitration of
        peer.Channel.store_block (an inherited poison fails over; an
        own-error block fails again with its real cause)."""
        pipe = self.pipeline_for(channel_id)
        try:
            return pipe.store_block(block)
        except Exception:
            retry = self.pipeline_for(channel_id)
            if retry is pipe:
                raise
            return retry.store_block(block)

    # -- lifecycle --------------------------------------------------------
    def flush(self, timeout_s: Optional[float] = None) -> bool:
        ok = True
        with self._lock:
            pipes = [b.pipe for b in self._bindings.values()
                     if b.pipe is not None]
        for p in pipes:
            ok = p.flush(timeout_s) and ok
        return ok

    def close(self, timeout_s: Optional[float] = None) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            bindings = list(self._bindings.values())
        for b in bindings:
            # under the binding's rebuild lock: a pipeline_for rebuild
            # racing this close either finished (its fresh pipe is in
            # b.pipe and gets closed here) or blocks until we release
            # and then sees _closed and raises — no engine can be
            # built over the torn-down service/verifiers below
            with b.rebuild_lock:
                pipe, b.pipe = b.pipe, None
            if pipe is not None:
                try:
                    pipe.close(timeout_s)
                except Exception as e:     # noqa: BLE001
                    # teardown best-effort: the pipe's error already
                    # surfaced to its callers; log and keep closing
                    # the rest of the fleet
                    log.warning("sharding: pipe close for %s "
                                "raised: %r", b.channel_id, e)
        self.service.close()
        for v in self.verifiers.values():
            vclose = getattr(v, "close", None)
            if vclose is not None:
                vclose()
