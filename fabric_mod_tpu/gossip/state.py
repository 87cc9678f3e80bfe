"""Gossip state transfer: in-order payload buffer feeding the commit
pipeline, with anti-entropy catch-up.

(reference: gossip/state/state.go — the payloads buffer + the
deliverPayloads loop at :583 popping blocks in sequence and
committing at :817; anti-entropy requests for missing ranges at
:583-838.)

The background drain loop is EVENT-DRIVEN: `add_block` signals the
buffer's condition variable whenever the next in-order block becomes
poppable, so commit latency is wakeup latency, not a poll interval
(the old loop slept 50 ms between drains — an idle-latency floor per
block and idle CPU burn).  The anti-entropy tick keeps its own
interval, as in the reference's separate goroutine.

On a channel bound to a shard router, drained blocks feed the
router's PipelinedCommitter (peer/commitpipe.py) instead of the
synchronous store_block — stage(N+1) overlaps finish+commit(N).
"""
from __future__ import annotations

import heapq
import threading
import time
from typing import Callable, Dict, List, Optional

from fabric_mod_tpu.concurrency import (RegisteredLock,
                                        RegisteredThread, assert_joined)
from fabric_mod_tpu.observability import tracing
from fabric_mod_tpu.protos import messages as m


class PayloadsBuffer:
    """Min-heap of blocks keyed by number; pop only when the next
    expected sequence is present (reference: the payloads buffer)."""

    def __init__(self, next_seq: int):
        self._heap: List = []
        self._have: set = set()
        self.next_seq = next_seq
        self._known_to = next_seq          # 1 past the highest num seen
        # registry-fed: the buffer lock nests inside the provider's
        # drain lock and around the commit pipe's locks — any future
        # inversion across those is a detected cycle, not a deadlock
        self._lock = RegisteredLock("gossip-payloads")
        self.ready = threading.Condition(self._lock)

    def push(self, block: m.Block) -> bool:
        num = block.header.number
        with self._lock:
            if num >= self._known_to:
                self._known_to = num + 1
            if num < self.next_seq or num in self._have:
                return False               # stale/duplicate
            heapq.heappush(self._heap, (num, block.encode()))
            self._have.add(num)
            if num == self.next_seq:
                self.ready.notify_all()
            return True

    def pop_in_order(self) -> Optional[m.Block]:
        with self._lock:
            if self._heap and self._heap[0][0] == self.next_seq:
                num, raw = heapq.heappop(self._heap)
                self._have.discard(num)
                self.next_seq += 1
                return m.Block.decode(raw)
            return None

    def wait_ready(self, timeout_s: Optional[float]) -> bool:
        """Block until the next in-order block is poppable (True) or
        the timeout lapses (False).  `wake()` also returns the waiter
        (spurious wakeups are fine — the drain loop re-checks)."""
        with self._lock:
            if self._heap and self._heap[0][0] == self.next_seq:
                return True
            return self.ready.wait(timeout=timeout_s)

    def wake(self) -> None:
        """Wake any wait_ready waiter (shutdown, external prod)."""
        with self._lock:
            self.ready.notify_all()

    def resync(self, next_seq: int) -> None:
        """Rewind the expected sequence (lowering only): a popped
        block that never actually committed (its committer failed) is
        gone from the heap, so without the rewind every redelivery
        would be rejected as stale and the gap would be invisible to
        anti-entropy — the channel would stall permanently.  Buffered
        future blocks stay valid."""
        with self._lock:
            if next_seq < self.next_seq:
                self.next_seq = next_seq

    def missing_range(self) -> Optional[range]:
        """The gap blocking progress, if any (for anti-entropy).  An
        empty heap still reports a gap when a block we KNOW exists
        (it was pushed — e.g. popped into a committer that failed,
        then resync()'d) is missing: without the `_known_to` bound
        that block would be invisible here and, if gossip never
        redelivers it, the channel would stall at the rewound
        height."""
        with self._lock:
            head = self._heap[0][0] if self._heap else self._known_to
            if head <= self.next_seq:
                return None
            return range(self.next_seq, head)


class GossipStateProvider:
    """Binds the buffer to a committer; the deliver loop commits
    blocks strictly in order (reference: state.go:583)."""

    def __init__(self, channel, request_missing: Optional[Callable] = None,
                 on_tick: Optional[Callable] = None):
        """`on_tick` runs on the anti-entropy cadence alongside the
        gap check (the node wires its pull engine here): a block lost
        at the chain TAIL leaves the payload buffer gapless — only a
        periodic hello/digest pull can discover it, so without this
        hook a dropped final push stalls an idle peer forever (found
        by the soak harness's background-drop chaos plan)."""
        self._channel = channel
        self.buffer = PayloadsBuffer(channel.ledger.height)
        self._request_missing = request_missing
        self._on_tick = on_tick
        self._tick_seq = -1                # buffer progress marker
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # serializes pop->commit sequences: two concurrent drain()
        # callers interleaving pops would submit blocks out of order
        self._drain_lock = RegisteredLock("gossip-state-drain")
        self._active_pipe = None           # the pipe drain last fed

    def add_block(self, block: m.Block) -> bool:
        """Verified block in (MCS check happens in the gossip node
        before this, reference: mcs.go VerifyBlock upstream).  Pushing
        the next in-order block wakes the background drain loop."""
        return self.buffer.push(block)

    def _commit_pipeline(self):
        """The channel's PipelinedCommitter, when it has one (a
        router-bound peer.Channel; bare committer stubs in tests have
        no getter)."""
        getter = getattr(self._channel, "commit_pipeline", None)
        return getter() if getter is not None else None

    def _refresh_pipe(self):
        """Fetch the channel pipe; on a NEW pipe (first use, or the
        channel rebuilt a failed one) rewind the buffer to the
        committed height — blocks handed to a previous pipe but never
        committed are not coming back, and without the rewind both
        gossip redelivery and anti-entropy would treat the lost range
        as already handled.  Caller holds _drain_lock."""
        pipe = self._commit_pipeline()
        if pipe is not self._active_pipe:
            self.buffer.resync(self._channel.ledger.height)
            self._active_pipe = pipe
        return pipe

    def drain(self, max_blocks: int = 1000) -> int:
        """Commit everything poppable now; returns count.  Through a
        commit pipeline the blocks are SUBMITTED in order and
        commit asynchronously — `flush()` (or `stop()`) waits them
        out."""
        n = 0
        with self._drain_lock:
            pipe = self._refresh_pipe()
            # the drain is the gossip->commit seam: its span parents
            # the engine-side block timelines submitted under it, so a
            # gossip-fed commit traces back to the drain that fed it
            with tracing.span("gossip.drain") as drain_span:
                while n < max_blocks:
                    block = self.buffer.pop_in_order()
                    if block is None:
                        break
                    try:
                        if pipe is not None:
                            pipe.submit(block)
                        else:
                            self._channel.store_block(block)
                    except Exception:
                        # the popped block never committed: rewind so
                        # it stays requestable instead of stalling the
                        # channel on a permanent invisible gap
                        self.buffer.resync(self._channel.ledger.height)
                        raise
                    n += 1
                drain_span.set(blocks=n)
        return n

    def flush(self, timeout_s: Optional[float] = None) -> bool:
        """Wait until every drained block is actually committed (a
        no-op on the synchronous path)."""
        pipe = self._commit_pipeline()
        if pipe is None:
            return True
        return pipe.flush(timeout_s)

    def request_gap(self) -> Optional[range]:
        """Immediately request the gap blocking progress, if any.
        The relay's repair prod: a child that just SAW a frame beyond
        its next needed block knows the gap exists NOW — waiting out
        the anti-entropy cadence would add a full interval to every
        relay drop's repair latency.  The periodic tick below remains
        the backstop for gaps nobody observed."""
        gap = self.buffer.missing_range()
        if gap is not None and self._request_missing is not None:
            self._request_missing(gap)
        return gap

    def anti_entropy_tick(self) -> Optional[range]:
        """If a gap blocks progress, ask for it
        (reference: the anti-entropy goroutine).  Also detects an
        ASYNC pipeline failure on a quiescent channel: without this
        check the rebuild+resync would wait for the next drain —
        which only fires on a new block — leaving a lost tail
        invisible to the gap request below forever."""
        with self._drain_lock:
            self._refresh_pipe()
        gap = self.buffer.missing_range()
        if gap is not None and self._request_missing is not None:
            self._request_missing(gap)
        # the pull hook fires only on a QUIESCENT channel (no buffer
        # progress since the previous tick): while blocks are flowing
        # the push path is clearly alive and a pull is pure overhead;
        # when nothing moved, either we are fully caught up or the
        # tail was lost — exactly the two cases only a pull can tell
        # apart
        seq = self.buffer.next_seq
        if self._on_tick is not None and seq == self._tick_seq:
            self._on_tick()
        self._tick_seq = seq
        return gap

    # -- background mode --------------------------------------------------
    def start(self, interval_s: float = 0.5) -> None:
        """Idempotent: a second start() (e.g. two services composed
        over one node) does not spawn a second drain loop.
        `interval_s` is the ANTI-ENTROPY cadence only — commits are
        event-driven off `add_block`."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()

        def loop():
            from fabric_mod_tpu.observability import get_logger
            log = get_logger("gossip.state")
            next_tick = time.monotonic() + interval_s
            while not self._stop.is_set():
                timeout = max(0.0, next_tick - time.monotonic())
                got = self.buffer.wait_ready(timeout)
                if self._stop.is_set():
                    return
                if got:
                    try:
                        self.drain()
                    except Exception as e:
                        # the loop must survive a failed commit: drain
                        # already resynced the buffer, and this same
                        # thread runs the anti-entropy that re-requests
                        # the gap — dying here would stall the channel
                        log.warning("background drain failed: %s "
                                    "(resynced; redelivery/anti-"
                                    "entropy will retry)", e)
                if time.monotonic() >= next_tick:
                    try:
                        self.anti_entropy_tick()
                    except Exception as e:
                        # same survival contract as drain: the tick
                        # runs a user callback and a pipe health
                        # check — neither may kill the loop
                        log.warning("anti-entropy tick failed: %s", e)
                    next_tick = time.monotonic() + interval_s
        self._thread = RegisteredThread(target=loop,
                                        name="gossip-state-drain",
                                        structure="GossipStateProvider")
        self._thread.start()

    def stop(self) -> None:
        """Best-effort teardown: drain + wait out pending commits,
        logging (never raising) on failure — any commit error was
        already surfaced to the drain caller that hit it, and the
        resync in drain() keeps uncommitted blocks requestable."""
        self._stop.set()
        self.buffer.wake()
        if self._thread is not None:
            assert_joined((self._thread,),
                          owner="GossipStateProvider", timeout=5)
        from fabric_mod_tpu.observability import get_logger
        try:
            self.drain()
            # generous: the tail blocks may still be compiling/
            # committing (a cold XLA verify compile runs minutes)
            if not self.flush(timeout_s=600.0):
                get_logger("gossip.state").warning(
                    "stop(): commit pipeline did not drain within "
                    "600s — tail blocks remain uncommitted "
                    "(redeliverable)")
        except Exception as e:
            get_logger("gossip.state").warning(
                "stop(): final drain failed: %s — uncommitted blocks "
                "remain requestable after resync", e)
