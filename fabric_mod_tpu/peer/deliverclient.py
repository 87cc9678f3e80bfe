"""Peer deliver client: pull ordered blocks, verify, commit — pipelined.

(reference: internal/pkg/peer/blocksprovider/blocksprovider.go
`DeliverBlocks` — the pull loop with `VerifyBlock` at :227 — feeding
gossip/state/state.go:583's `deliverPayloads` commit loop through the
in-order payload buffer.)

Three pipeline stages — the double buffer of SURVEY §2.9 row 2:

  stage 1 (this thread / `run`):   pull block N+2; the HOST half of
                                   the MCS gate (hashes, the signature
                                   metadata parsed into SignedData);
                                   it awaits no signature check
  stage 2 (pipeline stage loop):   host unpack + policy staging of
                                   block N+1, its orderer signatures
                                   under the BlockValidation policy
                                   in force NOW staged after them,
                                   then DISPATCH of the one device
                                   verify batch without awaiting it
  stage 3 (pipeline commit loop):  await block N's device verdicts;
                                   FIRST the block signature's (not
                                   satisfied: reject, commit nothing
                                   at or after N); resolve flags,
                                   MVCC + commit

Stages 2+3 are peer/commitpipe.PipelinedCommitter — the shared
commit-pipeline engine (bounded depth, `needs_barrier` drains,
per-stage histograms); this client owns stage 1 and the MCS gate's
two outcomes.  A block the gate refuses, at once (stage 1) or with its
verdict (stage 3), goes into `rejected`; a single-endpoint source then
stops, `run()` returns and nothing at or after the block is in the
ledger; a source with `report_bad_block` is told the number, the
engine is rebuilt from the ledger's height and the pull goes on: the
source re-fetches from another orderer, and blocks pulled past the
rejected one are discarded, never re-used.

Block N+1's host unmarshalling overlaps block N's device execution:
the device batch is in flight between stage 2's dispatch and stage
3's resolve.  Commit order is block-number order by construction
(single puller).  Staging must not run ahead of a block that changes
what staging reads — config txs (the BlockValidation policy among
what they can change), VALIDATION_PARAMETER writes, lifecycle
definitions — so such blocks set `needs_barrier` and the engine waits
for their commit before staging the next block (the reference's
serialization points: validator.go:400 config,
validator_keylevel.go waits).
"""
from __future__ import annotations

import threading
from typing import Callable, List, Optional

from fabric_mod_tpu.concurrency import CancellationEvent, OwnedState
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.observability.metrics import (MetricOpts,
                                                  default_provider)
from fabric_mod_tpu.peer.channel import Channel
from fabric_mod_tpu.peer.commitpipe import DEPTH, PipelinedCommitter
from fabric_mod_tpu.peer.mcs import BlockVerificationError
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos import protoutil
from fabric_mod_tpu.observability.logging import get_logger

log = get_logger("peer.deliverclient")

_DEFERRED_REJECTIONS_OPTS = MetricOpts(
    "fabric", "mcs", "deferred_rejections_total",
    help="Blocks whose orderer signature set, verified inside the "
         "block's own batch, failed the BlockValidation policy at "
         "commit time: rejected with every block pulled after them.")


def _deferred_rejections():
    return default_provider().counter(_DEFERRED_REJECTIONS_OPTS)


class DeliverDisconnected(Exception):
    """The deliver stream died mid-pull (source raised) in a
    single-endpoint (non-failover) configuration.

    Typed, and carries `height` — the last committed ledger height —
    so a supervisor can resume a fresh client from exactly the next
    needed block instead of parsing a bare transport exception.  A
    FailoverDeliverSource never surfaces this: it rotates to another
    orderer internally (reference: blocksprovider.go:141/:227 — the
    retry path this error marks the absence of)."""

    def __init__(self, msg: str, height: Optional[int] = None):
        super().__init__(msg)
        self.height = height


class DeliverClient:
    """Pulls blocks from a deliver source into a channel's commit path.

    `source` must provide `blocks(start, stop=None, stop_event=None,
    timeout_s=...)` — the in-process DeliverService now, the gRPC
    deliver stream later (same generator shape).
    """

    def __init__(self, channel: Channel, source,
                 queue_size: int = 8,
                 on_error: Optional[Callable[[Exception], None]] = None,
                 on_commit: Optional[Callable[[m.Block], None]] = None,
                 depth: int = DEPTH):
        """`on_commit(block)` fires after each commit — the gossip
        service uses it to fan committed blocks out to non-leader
        peers (reference: the leader's gossip of deliver payloads).
        `depth` bounds staged-but-uncommitted blocks."""
        self._channel = channel
        self._source = source
        # a failover source re-fetches a refused block from another
        # orderer; a single-endpoint source has no such thing
        self._report = getattr(source, "report_bad_block", None)
        self._on_commit = on_commit
        # CancellationEvent so an in-process DeliverService tip wait
        # parks tickless: stop() both flags the loop AND (via the
        # service's on_set hook) notifies the writer's condition
        self._stop = CancellationEvent()
        self._depth = depth
        self._queue_size = queue_size
        self._on_error = on_error
        # stage/commit seconds of pipes already closed (run() builds a
        # fresh engine per invocation — the client is reusable)
        self._secs_base = [0.0, 0.0, 0.0]  # stage, await, commit
        self._pipe = self._make_pipe()
        self.rejected: List[int] = []      # block numbers that failed MCS
        # stage-1 exclusivity: run() claims this state for its thread;
        # a SECOND concurrent run() on one client would double-pull
        # and double-submit — under FMT_RACECHECK the second claim
        # raises instead (sequential re-runs re-claim freely)
        self._runner = OwnedState("deliverclient-runner")

    def _make_pipe(self) -> PipelinedCommitter:
        def fail(e: Exception) -> None:
            if isinstance(e, BlockVerificationError):
                # a block's signature verdict, from the commit thread:
                # a rejection, not an error (`_run_claimed` records
                # it).  A failover source is told at once, so that it
                # rewinds even while the puller is parked at the tip
                if self._report is not None:
                    self._report(e.number)
                else:
                    self._stop.set()       # single endpoint: fail closed
                return
            # stop the pull promptly: the source generator honors the
            # stop event, so a dead pipeline doesn't pull until idle
            self._stop.set()
            if self._on_error is not None:
                self._on_error(e)

        return PipelinedCommitter(
            self._channel, depth=self._depth,
            in_queue=self._queue_size,
            on_commit=self._handle_commit, on_error=fail,
            consumer="deliver")

    def _handle_commit(self, block: m.Block, _flags) -> None:
        if self._on_commit is not None:
            try:
                self._on_commit(block)
            except Exception as e:         # gossip fan-out is advisory
                log.debug("gossip fan-out for block %d raised: "
                          "%r", block.header.number, e)

    # cumulative wall seconds per stage (the e2e bench reports these
    # to show the verify-vs-commit overlap); commit_secs keeps the old
    # meaning — everything after dispatch: verdict await + resolve +
    # MVCC + ledger commit
    @property
    def stage_secs(self) -> float:
        return self._secs_base[0] + self._pipe.stage_secs

    @property
    def await_secs(self) -> float:
        return self._secs_base[1] + self._pipe.await_secs

    @property
    def commit_secs(self) -> float:
        return (self._secs_base[1] + self._secs_base[2]
                + self._pipe.await_secs + self._pipe.commit_secs)

    # -- stage 1: pull + verify ------------------------------------------
    def run(self, stop_at: Optional[int] = None,
            idle_timeout_s: float = 30.0) -> None:
        """Pull from the ledger's current height until `stop_at` (block
        number, inclusive) or the source goes idle.  Blocking; callers
        wanting a background client wrap this in a thread.  One run()
        at a time: a concurrent second run() is a race (double pull,
        interleaved submits) and is rejected under FMT_RACECHECK."""
        self._runner.claim()
        try:
            self._run_claimed(stop_at, idle_timeout_s)
        finally:
            # released on EVERY exit (including a raise before or
            # inside the pull loop, or from pipe.close) — a leaked
            # claim would turn every later run() into a false race
            self._runner.release()
        err = self._pipe.error
        if err is not None and not isinstance(err,
                                              BlockVerificationError):
            raise err

    def _renew_pipe(self) -> None:
        """Swap the closed engine for a fresh one over the ledger's
        height; the closed one's timings accumulate."""
        self._secs_base[0] += self._pipe.stage_secs
        self._secs_base[1] += self._pipe.await_secs
        self._secs_base[2] += self._pipe.commit_secs
        self._pipe = self._make_pipe()

    def _tip_hash(self) -> Optional[bytes]:
        # the store's own record: a ledger bootstrapped from a
        # snapshot holds no block below its height to hash
        ledger = self._channel.ledger
        if ledger.height == 0:
            return None
        return ledger.blockstore.last_block_hash

    def _note_rejected(self, number: int) -> None:
        self.rejected.append(number)
        del self.rejected[:-1000]          # bounded memory

    def _take_deferred_rejection(self) -> bool:
        """Drain the failed engine; True where what failed it was a
        block's signature verdict (recorded in `rejected`), which
        leaves the ledger at the rejected block's number: everything
        before it committed, nothing at or after it did."""
        # the join also orders us after the commit thread's report to
        # a failover source (`_make_pipe`'s `fail`)
        self._pipe.close()
        err = self._pipe.error
        if not isinstance(err, BlockVerificationError):
            return False
        self._note_rejected(err.number)
        _deferred_rejections().add(1)
        return True

    def _run_claimed(self, stop_at: Optional[int],
                     idle_timeout_s: float) -> None:
        if self._pipe.closed:
            # reusable client (the pre-engine contract): each run()
            # gets fresh workers; prior runs' timings accumulate
            self._renew_pipe()
            self._stop.clear()
        start = self._channel.ledger.height
        prev_hash = self._tip_hash()
        report = self._report
        dropped: Optional[BaseException] = None
        try:
            source_iter = iter(self._source.blocks(
                start, stop=stop_at, stop_event=self._stop,
                timeout_s=idle_timeout_s))
            while True:
                # "recv" attributes stage 1: the pull wait + the MCS
                # host check, per block (the part of the wall the
                # commit pipeline can never hide)
                with tracing.span("recv") as recv_span:
                    try:
                        block = next(source_iter)
                    except StopIteration:
                        break              # clean end / idle timeout
                    except Exception as e:
                        # dropped stream, single-endpoint mode:
                        # surface a TYPED error with the resume point,
                        # not a bare transport exception (a failover
                        # source handles this internally and never
                        # raises here).  Raised AFTER the finally
                        # drains the pipe, so the carried height
                        # includes every in-flight commit — it IS the
                        # next run()'s re-seek point.
                        dropped = e
                        break
                    if self._stop.is_set():
                        break
                    number = block.header.number
                    recv_span.set(block=number)
                    if self._pipe.error is not None:
                        # a verdict landed on a block already in the
                        # pipe.  A failover source has been told (it
                        # rewinds to the rejected block at this
                        # yield, on another orderer): start over from
                        # the ledger's tip.  Anything else ends the
                        # run (recorded or re-raised below).
                        if report is None or \
                                not self._take_deferred_rejection():
                            break
                        self._renew_pipe()
                        prev_hash = self._tip_hash()
                        if number != self._channel.ledger.height:
                            # pulled past the rejected block, from the
                            # orderer that served it: discarded
                            continue
                    try:
                        # the host half of the MCS gate (hashes, the
                        # signature metadata); the signatures' policy
                        # verdict rides the block's own verify batch
                        with tracing.span("mcs_verify", block=number):
                            block_sigs = self._channel.mcs.check_block(
                                block, expected_prev_hash=prev_hash)
                    except BlockVerificationError:
                        # tampered/malformed block: drop it, never
                        # commit.  With a failover source, ask it to
                        # re-fetch this block from a DIFFERENT orderer
                        # and keep pulling (reference:
                        # blocksprovider.go:227 — disconnect and retry
                        # another orderer); a single-endpoint source
                        # fails closed by stopping.
                        self._note_rejected(number)
                        if report is not None:
                            report(number)
                            continue
                        break
                prev_hash = protoutil.block_header_hash(block.header)
                try:
                    self._pipe.submit(block, block_sigs)
                except Exception:
                    if self._pipe.error is None:
                        raise              # not a pipeline failure
                    if report is None:
                        break              # taken up after close below
                    # the next turn's check takes it up: the source
                    # rewinds only when asked for its next block
        finally:
            # unbounded join (the pre-engine contract): run() never
            # returns with commits silently in flight, however long
            # the tail block's cold XLA compile takes
            self._pipe.close()
        # a signature verdict that landed on the tail: the block is
        # rejected as a structural failure is (recorded, run() returns);
        # any other pipeline error is run()'s to re-raise
        if self._pipe.error is not None:
            self._take_deferred_rejection()
        if dropped is not None:
            height = self._channel.ledger.height
            if isinstance(dropped, DeliverDisconnected):
                if dropped.height is None:
                    dropped.height = height
                raise dropped
            raise DeliverDisconnected(
                f"deliver stream dropped at height {height}: "
                f"{dropped!r}", height=height) from dropped

    def stop(self) -> None:
        self._stop.set()

    def wait_for_height(self, height: int, timeout_s: float = 30.0) -> bool:
        """Block until `height` blocks are committed.  Re-reads the
        pipe each slice: a reused client swaps in a fresh engine per
        run(), and a waiter must follow it rather than watch a closed
        pipe whose height never advances."""
        import time
        deadline = time.monotonic() + timeout_s
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                return False
            try:
                if self._pipe.wait_height(height, min(left, 1.0)):
                    return True
            except Exception:
                return False
