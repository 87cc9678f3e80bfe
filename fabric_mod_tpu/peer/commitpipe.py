"""Pipelined block-commit engine: overlap stage(N+1) with finish+commit(N).

(reference: the serial StoreBlock composition of
gossip/state/state.go:817 — validate -> MVCC -> commit, one block at a
time — restructured the way FastFabric (Gorenflo et al., 2019) and
StreamChain (Istvan et al., 2018) pipeline Fabric's commit path.)

The validator already split the block hot path into `stage` (host
unpack + policy compilation + device batch DISPATCH, no await) and
`finish` (await verdicts + sequential flag resolution) —
peer/txvalidator.py.  This module runs that seam as a bounded
pipeline over an in-order block stream:

  caller       submit(block)   -> bounded in-queue (backpressure)
  stage loop   stage(N+1): host unpack + device dispatch, CONCURRENT
               with ...
  commit loop  finish(N): await verdicts; FIRST the block signature's
               (below); resolve flags; then
               kvledger.commit_block(N): MVCC + block store + state

`submit(block, block_sigs)` hands the stage loop the block signatures'
SignedData too (peer/mcs.check_block; the deliver client does): their
items ride the block's own verify batch, and the commit loop's first
act after the verdict await is their BlockValidation verdict, before a
flag is written, a config applied or a byte committed.  Unsatisfied,
`finish` raises BlockVerificationError and the sticky error below
drains every later staged and queued block uncommitted.  A block
submitted without them is one its caller verified beforehand.

`depth` bounds how many blocks may be staged-but-uncommitted at once;
depth=1 is bit-identical to the synchronous Committer (stage(N+1)
cannot start until commit(N) finished).  Whenever a staged block sets
`StagedBlock.needs_barrier` (config txs, VALIDATION_PARAMETER writes,
lifecycle-namespace writes — state that pass-1 staging READS), the
stage loop drains to a strict barrier: the next block stages only
after the barrier block's commit lands, so staged reads never race
committed state.  Everything else about verdict order is already
safe ahead-of-commit: duplicate-txid and key-level override
resolution run in `finish`, strictly in block order.

Every stage is instrumented (MetricsProvider -> opsserver /metrics):
  fabric_commitpipe_stage_seconds    host unpack + dispatch per block
  fabric_commitpipe_await_seconds    device-verdict wait per block
  fabric_commitpipe_commit_seconds   MVCC + ledger commit per block
                                     (the ledger's own histograms
                                     split mvcc/store/state within)
  fabric_commitpipe_occupancy        staged-but-uncommitted blocks
  fabric_commitpipe_barriers_total   barrier drains taken
  fabric_commitpipe_blocks_total     blocks committed via a pipeline
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from typing import Callable, List, Optional

from fabric_mod_tpu import faults
from fabric_mod_tpu.concurrency import (GuardedQueue, OwnedState,
                                        RegisteredLock,
                                        RegisteredThread, assert_joined)
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.observability.metrics import (MetricOpts,
                                                  default_provider)
from fabric_mod_tpu.observability.opsserver import default_health
from fabric_mod_tpu.observability.logging import get_logger

log = get_logger("peer.commitpipe")

_STAGE_OPTS = MetricOpts(
    "fabric", "commitpipe", "stage_seconds",
    help="Host unpack + policy compilation + device dispatch time per "
         "block (the pipeline's front stage).")
_AWAIT_OPTS = MetricOpts(
    "fabric", "commitpipe", "await_seconds",
    help="Device-verdict wait per block (overlapped with the next "
         "block's staging when depth > 1).")
_COMMIT_OPTS = MetricOpts(
    "fabric", "commitpipe", "commit_seconds",
    help="Flag resolution + MVCC + ledger commit time per block.")
_OCCUPANCY_OPTS = MetricOpts(
    "fabric", "commitpipe", "occupancy",
    help="Blocks staged but not yet committed (pipeline fill; bounded "
         "by the configured depth).  Labeled per consumer: multiple "
         "live engines (a deliver client's pipe + a shard router's) "
         "must not overwrite each other's fill level.",
    label_names=("consumer",))
_BARRIER_OPTS = MetricOpts(
    "fabric", "commitpipe", "barriers_total",
    help="Barrier drains: blocks whose config/VALIDATION_PARAMETER/"
         "lifecycle writes forced the next stage to wait for commit.")
_BLOCKS_OPTS = MetricOpts(
    "fabric", "commitpipe", "blocks_total",
    help="Blocks committed through a pipelined committer.")


@functools.lru_cache(maxsize=None)
def _metrics():
    prov = default_provider()
    return (prov.histogram(_STAGE_OPTS),
            prov.histogram(_AWAIT_OPTS),
            prov.histogram(_COMMIT_OPTS),
            prov.gauge(_OCCUPANCY_OPTS),
            prov.counter(_BARRIER_OPTS),
            prov.counter(_BLOCKS_OPTS))


# per-instance health-registry key suffix (consumer labels repeat)
_pipe_seq = itertools.count()


# The depth every engine is built at.  A third staged block moved no
# cell on the chip (PERF.md section 6, PR 31: depth 3 beside depth 2).
DEPTH = 2


class ValidatorCommitTarget:
    """The minimal channel-shaped commit target: one TxValidator bound
    to one ledger.  PipelinedCommitter only needs `stage_block`,
    `commit_staged` and `.ledger` — peer.Channel provides them in
    production; this adapter serves the bench and tests where no
    channel config machinery exists."""

    def __init__(self, validator, ledger):
        self.validator = validator
        self.ledger = ledger

    def stage_block(self, block):
        return self.validator.stage(block)

    def commit_staged(self, staged) -> List[int]:
        flags = staged.validator.finish(staged)
        return self.ledger.commit_block(
            staged.block, flags,
            rwsets=getattr(staged, "rwsets", None))


class PipelinedCommitter:
    """Bounded commit pipeline over an in-order block stream.

    `submit(block)` enqueues for staging and returns (backpressure via
    the bounded in-queue); blocks commit strictly in submission order
    on the commit loop.  `store_block` is the synchronous facade (used
    by the drop-in Committer seam): submit + wait for that block's
    commit, returning its final flags.  Threads start lazily on first
    submit and are daemons; `close()` drains and joins them.
    """

    def __init__(self, channel, depth: int = DEPTH,
                 in_queue: int = 8,
                 on_commit: Optional[Callable] = None,
                 on_error: Optional[Callable] = None,
                 consumer: str = "adhoc"):
        """`channel`: stage_block/commit_staged/.ledger (peer.Channel
        or ValidatorCommitTarget).  `depth`: max staged-but-uncommitted
        blocks (floor 1).  `on_commit(block, flags)` fires after each
        commit, `on_error(exc)` once on the first failure.  `consumer`
        labels the occupancy gauge (keep the set small: "deliver",
        "shard<slice>", "adhoc")."""
        self._channel = channel
        self.depth = max(1, depth)
        # in-queue: many producers (submit callers + close sentinel),
        # one consumer (the stage loop); staged queue: strict SPSC
        # stage -> commit.  Ownership is machine-checked under
        # FMT_RACECHECK.
        self._in_q: "GuardedQueue" = GuardedQueue(
            max(1, in_queue), name=f"commitpipe-in[{consumer}]")
        self._staged_q: "GuardedQueue" = GuardedQueue(
            name=f"commitpipe-staged[{consumer}]", single_producer=True)
        self._on_commit = on_commit
        self._on_error = on_error
        # one condition variable guards all pipeline state: inflight
        # count (the depth bound), committed height (barrier + flush
        # waits), the sticky first error.  Registry-fed lock: the cv
        # nests inside the submit lock and around the ledger's ranked
        # OrderedLock — inversions are cycles the registry reports.
        self._cv = threading.Condition(
            RegisteredLock(f"commitpipe-cv[{consumer}]"))
        self._inflight = 0
        self._height = channel.ledger.height
        self._barrier_height: Optional[int] = None
        self._last_submitted: Optional[int] = None
        self._err: Optional[Exception] = None
        self._closed = False
        self._started = False
        self._start_lock = RegisteredLock(
            f"commitpipe-start[{consumer}]")
        # serializes producers through the in-queue put: without it,
        # two overlapping store_block callers could update
        # _last_submitted in order yet enqueue out of order
        self._submit_lock = RegisteredLock(
            f"commitpipe-submit[{consumer}]")
        self._threads: List[threading.Thread] = []
        # cumulative per-stage wall seconds (the e2e bench reads these
        # off the deliver client to show the verify/commit overlap).
        # Single-writer contract made machine-checked: the stage loop
        # owns stage timing, the commit loop owns await/commit timing;
        # reads (bench, deliver client) stay open.
        self._stage_state = OwnedState(
            f"commitpipe-stage[{consumer}]", secs=0.0)
        self._commit_state = OwnedState(
            f"commitpipe-commit[{consumer}]", await_secs=0.0,
            commit_secs=0.0)
        (self._m_stage, self._m_await, self._m_commit,
         occupancy, self._m_barriers, self._m_blocks) = _metrics()
        self._m_occupancy = occupancy.with_labels(consumer)
        self._consumer = consumer
        # real health: a poisoned (sticky-error, not yet discarded)
        # pipeline flips /healthz — the registry existed since the ops
        # server landed, this is the first commit-path registrant.
        # Keyed per INSTANCE (consumer labels repeat: every channel's
        # deliver client is consumer="deliver" — a shared key would let
        # the newest registration mask another channel's poisoned pipe);
        # close() unregisters, so the registry tracks live pipes only.
        self._health_key = f"commitpipe[{consumer}#{next(_pipe_seq)}]"
        default_health().register(self._health_key, self._health_check)

    def _health_check(self) -> None:
        if self._err is not None and not self._closed:
            raise RuntimeError(
                f"commit pipeline [{self._consumer}] poisoned: "
                f"{self._err!r}")

    # -- timing surface (kept: bench/deliver-client read these) -----------
    @property
    def stage_secs(self) -> float:
        return self._stage_state.secs

    @property
    def await_secs(self) -> float:
        return self._commit_state.await_secs

    @property
    def commit_secs(self) -> float:
        return self._commit_state.commit_secs

    # -- lifecycle -------------------------------------------------------
    def _ensure_started(self) -> None:
        with self._start_lock:
            if self._started:
                return
            self._started = True
            for name, fn in (("commitpipe-stage", self._stage_loop),
                             ("commitpipe-commit", self._commit_loop)):
                t = RegisteredThread(target=fn, name=name,
                                     structure="PipelinedCommitter")
                t.start()
                self._threads.append(t)

    @property
    def error(self) -> Optional[Exception]:
        return self._err

    def _fail(self, e: Exception) -> None:
        with self._cv:
            if self._err is None:
                self._err = e
            self._cv.notify_all()
        if self._on_error is not None:
            try:
                self._on_error(e)
            except Exception as cb_err:
                log.debug("on_error callback raised: %r", cb_err)

    # -- producer side ---------------------------------------------------
    def submit(self, block, block_sigs=None) -> None:
        """Enqueue one block for pipelined commit; with `block_sigs`
        (the block signatures' SignedData) its BlockValidation verdict
        rides the block's batch and gates the commit (module
        docstring).  Blocks only on the bounded in-queue (or a
        pending error).  Blocks MUST arrive in
        block-number order; a misordered submit (stale redelivery, or
        a racing producer's block arriving early) is rejected HERE
        with the ledger's own error type, to the offending caller
        only — never admitted to poison the shared pipeline with a
        commit-time out-of-order failure that would hit an unrelated
        later caller (the sync path's per-caller arbitration)."""
        with self._submit_lock:
            with self._cv:
                if self._err is not None:
                    raise self._err
                if self._closed:
                    # checked BEFORE starting workers: a closed
                    # never-started pipe must not spawn threads that
                    # nothing will ever send the shutdown sentinel to
                    raise RuntimeError("commit pipeline is closed")
                num = block.header.number
                # ledger-aware base: the chain may have advanced past
                # this pipe's construction snapshot (e.g. a deliver
                # client built early, gossip commits landing before
                # run()) — such in-order streams are not misordered
                base = max(self._height, self._channel.ledger.height)
                expected = (base if self._last_submitted is None
                            else max(base, self._last_submitted + 1))
                if num != expected:
                    from fabric_mod_tpu.ledger.kvledger import (
                        LedgerError)
                    raise LedgerError(
                        f"submit out of order: block {num}, pipeline "
                        f"expects {expected}")
                self._last_submitted = num
            self._ensure_started()
            # the deliver thread held back by a full pipeline
            with tracing.span("submit_wait", block=num):
                self._in_q.put((block, block_sigs))

    def store_block(self, block) -> List[int]:
        """Synchronous facade: submit + wait for THIS block's commit;
        returns its final flags.  Pipelining still happens across
        concurrent/overlapping callers."""
        from fabric_mod_tpu.protos import protoutil
        num = block.header.number
        self.submit(block)
        self.wait_height(num + 1)
        return list(protoutil.block_txflags(block))

    def wait_height(self, height: int,
                    timeout_s: Optional[float] = None) -> bool:
        """Block until `height` blocks are committed (or the pipeline
        failed, re-raising its error)."""
        deadline = None if timeout_s is None else \
            time.monotonic() + timeout_s
        with self._cv:
            while self._height < height and self._err is None:
                left = None if deadline is None else \
                    deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._cv.wait(timeout=left if left is not None else 0.5)
            if self._height >= height:
                # truthfully report a reached height even if a LATER
                # block's failure set the sticky error meanwhile — the
                # waiter's own block is durably committed
                return True
            raise self._err

    def flush(self, timeout_s: Optional[float] = None) -> bool:
        """Wait until every submitted block is committed."""
        with self._cv:
            last = self._last_submitted
        if last is None:
            if self._err is not None:
                raise self._err
            return True
        return self.wait_height(last + 1, timeout_s)

    def close(self, timeout_s: Optional[float] = None) -> None:
        """Drain submitted work and join the workers.  The default
        (None) joins until drained — close() must not return with
        commits silently in flight (a cold XLA compile can hold the
        tail block for minutes); pass a bound only where abandoning
        the workers is acceptable (e.g. discarding a pipe that
        already failed).  A pending pipeline error stays readable on
        `.error` (callers that need to re-raise do so — the deliver
        client does)."""
        # taking the submit lock excludes a producer mid-submit, so
        # "started" is stable when read and the sentinel can't race a
        # block into a closed pipe
        with self._submit_lock:
            with self._cv:
                if self._closed:
                    return
                self._closed = True
            started = self._started
        # a closed (drained or discarded) engine leaves the health
        # registry: its sticky error was surfaced to its callers, and
        # keeping the entry would pin the whole channel/ledger graph
        # in the process-global registry forever
        default_health().unregister(self._health_key)
        if not started:
            return
        self._in_q.put(None)
        # leak-checked join: with FMT_RACECHECK armed, workers that
        # outlive the drain raise instead of parking as daemons.  The
        # commit loop may legally call close() via on_error/on_commit
        # callbacks — assert_joined skips the current thread.
        assert_joined(self._threads, owner="PipelinedCommitter",
                      timeout=timeout_s)

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def consumer(self) -> str:
        """The occupancy-gauge label this engine reports under.  The
        shard router labels per slice ("shard0", "shard1", ...), so
        /metrics shows each slice's pipeline fill separately — the
        placement-balance view next to the router's channels gauge."""
        return self._consumer

    def _barrier_holds(self) -> bool:
        """A staged block that needs a barrier is not committed yet
        (under `self._cv`)."""
        return (self._barrier_height is not None
                and self._height < self._barrier_height)

    # -- stage loop: host unpack + device dispatch -----------------------
    def _stage_loop(self) -> None:
        try:
            while True:
                with tracing.span("stage_wait_block"):     # starved
                    item = self._in_q.get()
                if item is None:
                    return
                block, block_sigs = item
                with tracing.span("stage_wait_slot",       # blocked
                                  block=block.header.number), self._cv:
                    # depth bound + barrier drain share the wait: stage
                    # only when a slot is free AND no barrier block is
                    # still committing; the barrier's part of the wait
                    # is a span of its own
                    while self._err is None and (
                            self._inflight >= self.depth
                            or self._barrier_holds()):
                        if self._barrier_holds():
                            with tracing.span(
                                    "stage_barrier_wait",
                                    block=block.header.number,
                                    barrier_block=self._barrier_height - 1):
                                while self._err is None and \
                                        self._barrier_holds():
                                    self._cv.wait(timeout=0.5)
                        else:
                            self._cv.wait(timeout=0.5)
                    if self._err is not None:
                        continue           # drain mode (below)
                    self._inflight += 1
                    self._m_occupancy.set(self._inflight)
                t0 = time.perf_counter()
                # chaos seam: an engine crash while staging (the
                # sticky-error drain below is the recovery contract
                # under test — a poisoned pipe must fail its callers
                # and be rebuildable from the committed height)
                faults.point("commitpipe.stage")
                # one flight-recorder timeline per block: the stage
                # side's sub-spans (unpack, device_dispatch) land
                # here; StagedBlock carries it across the handoff and
                # the commit loop resumes it (None when FMT_TRACE is
                # unset — zero objects, zero writes)
                tl = tracing.start_timeline(self._consumer,
                                            block.header.number)
                with tracing.timeline_scope(tl):
                    # a target that never folds (ValidatorCommitTarget)
                    # keeps its one-argument stage_block
                    staged = (self._channel.stage_block(block)
                              if block_sigs is None else
                              self._channel.stage_block(block,
                                                        block_sigs))
                if tl is not None:
                    staged.trace_timeline = tl
                dt = time.perf_counter() - t0
                self._stage_state.secs += dt
                self._m_stage.observe(dt)
                if staged.needs_barrier:
                    with self._cv:
                        self._barrier_height = block.header.number + 1
                    self._m_barriers.add(1)
                self._staged_q.put(staged)
        except Exception as e:
            self._fail(e)
            # keep draining so a bounded-queue producer never deadlocks
            while self._in_q.get() is not None:
                pass
        finally:
            self._staged_q.put(None)

    # -- commit loop: await verdicts, resolve, MVCC + commit -------------
    def _commit_loop(self) -> None:
        while True:
            with tracing.span("commit_wait_staged"):       # starved
                staged = self._staged_q.get()
            if staged is None:
                return
            tl = getattr(staged, "trace_timeline", None)
            try:
                # chaos seam: a crash between verdict await and ledger
                # write — the worst spot: the block is staged, its
                # device batch resolved, and NOTHING may have reached
                # the ledger (crash-resume must re-commit it exactly
                # once from the durable height)
                faults.point("commitpipe.commit")
                with tracing.timeline_scope(tl):
                    t0 = time.perf_counter()
                    staged.resolve_mask()  # the device-verdict wait
                    dt = time.perf_counter() - t0
                    self._commit_state.await_secs += dt
                    self._m_await.observe(dt)
                    t0 = time.perf_counter()
                    flags = self._channel.commit_staged(staged)
                    dt = time.perf_counter() - t0
                    self._commit_state.commit_secs += dt
                    self._m_commit.observe(dt)
            except Exception as e:
                self._fail(e)
                while self._staged_q.get() is not None:
                    pass
                return
            finally:
                tracing.finish_timeline(tl)
            with self._cv:
                self._inflight -= 1
                self._m_occupancy.set(self._inflight)
                self._height = staged.block.header.number + 1
                self._cv.notify_all()
            self._m_blocks.add(1)
            if self._on_commit is not None:
                try:
                    self._on_commit(staged.block, flags)
                except Exception as e:     # fan-out is advisory
                    log.debug("on_commit fan-out for block %d "
                              "raised: %r",
                              staged.block.header.number, e)
