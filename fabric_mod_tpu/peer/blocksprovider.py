"""Deliver failover: rotate across orderer endpoints with backoff.

(reference: internal/pkg/peer/blocksprovider/blocksprovider.go
`DeliverBlocks` — the retry loop with exponential backoff at :141 —
plus internal/pkg/peer/orderers/connection.go's endpoint source.)

`FailoverDeliverSource` has the same ``blocks()`` generator contract as
the in-process DeliverService and the single-endpoint
GrpcDeliverSource, so DeliverClient stays transport-agnostic.  What it
adds:

* a LIST of orderer endpoints, tried round-robin; a stream that ends
  (disconnect, terminal status) moves to the next endpoint and re-seeks
  from the next block the caller still needs — the caller sees one
  uninterrupted, gap-free block sequence;
* exponential backoff between full rotations (every endpoint failed),
  so a fully-down ordering service costs sleep, not spin;
* `report_bad_block(n)`: the caller's verify stage (MCS) flags a block
  that failed verification; the source re-fetches from `n` on a
  DIFFERENT orderer instead of the caller halting commit forever — the
  reference's "disconnect and try another orderer" stance
  (blocksprovider.go:227 VerifyBlock error path).  The report may come
  some blocks late and from another thread (the deliver client's
  signature verdict lands with the block's own verify batch): the
  source rewinds at its next yield, or within a poll of a quiet
  stream, and whatever it yielded past `n` is the caller's to discard.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Iterator, List, Optional, Sequence

from fabric_mod_tpu import faults
from fabric_mod_tpu.comm.grpc_comm import GRPCClient
from fabric_mod_tpu.observability import get_logger
from fabric_mod_tpu.orderer.server import SERVICE, make_seek_envelope
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.utils.retry import Retrier
from fabric_mod_tpu.concurrency.threads import RegisteredThread
from fabric_mod_tpu.concurrency.locks import RegisteredLock

log = get_logger("peer.blocksprovider")


class Endpoint:
    """One orderer address + its TLS material (lazy-dialed)."""

    def __init__(self, address: str,
                 server_root_pem: Optional[bytes] = None,
                 client_cert_pem: Optional[bytes] = None,
                 client_key_pem: Optional[bytes] = None,
                 override_authority: Optional[str] = None):
        self.address = address
        self._tls = (server_root_pem, client_cert_pem, client_key_pem,
                     override_authority)
        self._client: Optional[GRPCClient] = None

    def client(self) -> GRPCClient:
        if self._client is None:
            root, cert, key, auth = self._tls
            self._client = GRPCClient(self.address, server_root_pem=root,
                                      client_cert_pem=cert,
                                      client_key_pem=key,
                                      override_authority=auth)
        return self._client

    def reset(self) -> None:
        """Drop the cached channel (a dead connection must not be
        reused after its orderer restarts)."""
        if self._client is not None:
            self._client.close()
            self._client = None


class FailoverDeliverSource:
    """Multi-orderer deliver stream with rotation + backoff."""

    def __init__(self, endpoints: Sequence[Endpoint], channel_id: str,
                 base_backoff_s: float = 0.1, max_backoff_s: float = 10.0,
                 retrier: Optional[Retrier] = None):
        """`retrier` owns the between-full-rotations backoff schedule
        (jittered exponential, utils/retry.py); pass a seeded one for
        a deterministic schedule — default derives from
        base_backoff_s/max_backoff_s."""
        if not endpoints:
            raise ValueError("at least one orderer endpoint required")
        self._endpoints: List[Endpoint] = list(endpoints)
        self._channel_id = channel_id
        self._retrier = retrier if retrier is not None else Retrier(
            base_s=base_backoff_s, max_s=max_backoff_s,
            name="deliver.failover")
        self._idx = 0                      # current endpoint
        self._resume: Optional[int] = None  # set by report_bad_block
        self._lock = RegisteredLock("peer.blocksprovider._lock")
        self.rotations = 0                 # observability

    def report_bad_block(self, number: int) -> None:
        """The caller's verify stage rejected block `number`: re-fetch
        it from a different orderer (fail-closed per orderer, not
        forever)."""
        with self._lock:
            # the lowest pending wins: a later block of the same
            # stream is re-fetched with it
            if self._resume is None or number < self._resume:
                self._resume = number
        log.warning("block %d failed verification; rotating orderer",
                    number)

    def _rewind_pending(self) -> bool:
        with self._lock:
            return self._resume is not None

    def _rotate(self) -> None:
        with self._lock:
            self._endpoints[self._idx].reset()
            self._idx = (self._idx + 1) % len(self._endpoints)
            self.rotations += 1

    def current_address(self) -> str:
        with self._lock:
            return self._endpoints[self._idx].address

    def blocks(self, start: int = 0, stop: Optional[int] = None,
               stop_event: Optional[threading.Event] = None,
               timeout_s: float = 30.0) -> Iterator[m.Block]:
        """Yield blocks [start, stop] in order, failing over as needed.

        Ends only when `stop` is reached or `stop_event` fires (an
        endless peer stream passes stop=None and stops via the event).
        `timeout_s` bounds ONE quiet stream — a source that hangs
        without closing is treated as failed and rotated away from.
        """
        import grpc

        next_needed = start
        consecutive_failures = 0
        with self._lock:
            self._resume = None            # an earlier pull's report
        while not (stop_event is not None and stop_event.is_set()):
            if stop is not None and next_needed > stop:
                return
            ep = self._endpoints[self._idx]
            stream_start = next_needed
            made_progress = False
            try:
                seek = make_seek_envelope(self._channel_id, next_needed,
                                          stop)
                stream = ep.client().stream_stream(
                    SERVICE, "Deliver", iter([seek.encode()]),
                    timeout=None)
                try:
                    watchdog = _StreamWatchdog(stream, timeout_s,
                                               stop_event,
                                               self._rewind_pending)
                    for raw in watchdog.iterate():
                        # chaos seam: a mid-stream death of THIS
                        # endpoint (the except below rotates away)
                        faults.point("deliver.failover.stream")
                        resp = m.DeliverResponse.decode(raw)
                        if resp.block is None:
                            break          # terminal status
                        blk = resp.block
                        if blk.header.number != next_needed:
                            # gap or replay: this orderer is not
                            # serving what we asked — rotate
                            log.warning(
                                "orderer %s sent block %d, wanted %d",
                                ep.address, blk.header.number,
                                next_needed)
                            break
                        yield blk
                        if self._rewind_pending():
                            break          # rewind + rotate below
                        next_needed = blk.header.number + 1
                        made_progress = True
                        if stop_event is not None and stop_event.is_set():
                            return
                        if stop is not None and next_needed > stop:
                            return
                finally:
                    watchdog.abandon()
                    stream.cancel()
            except grpc.RpcError as e:
                # repr, not e.code(): an RpcError without a bound
                # code() would make the log call itself raise inside
                # the except block and kill the deliver thread this
                # handler exists to protect
                log.info("deliver stream to %s failed: %r",
                         ep.address, e)
            except Exception as e:
                # anything else a bad orderer can induce (garbage
                # frames failing DeliverResponse.decode, ...) must
                # rotate, not kill the peer's deliver thread
                log.warning("deliver stream to %s raised: %r",
                            ep.address, e)
            with self._lock:
                resume, self._resume = self._resume, None
            if resume is not None:
                # the caller's verify stage rejected block `resume`,
                # at once or (a deferred verdict) some yields later.
                # A stream counts as PROGRESS only if it delivered a
                # block below the one rejected, one the caller kept —
                # otherwise N orderers all serving an unverifiable
                # block would rotate in a hot loop with the backoff
                # never engaging
                next_needed = min(next_needed, resume)
                made_progress = stream_start < resume
            self._rotate()
            if made_progress:
                consecutive_failures = 0
            else:
                consecutive_failures += 1
                if consecutive_failures >= len(self._endpoints):
                    # full rotation without progress: back off on the
                    # shared jittered-exponential schedule (the
                    # Retrier clamps the exponent, so a multi-hour
                    # outage cannot overflow the float and kill the
                    # deliver thread)
                    delay = self._retrier.delay_for(
                        consecutive_failures - len(self._endpoints))
                    if stop_event is not None:
                        if stop_event.wait(delay):
                            return
                    else:
                        time.sleep(delay)  # fmtlint: allow[clocks] -- stop_event-less caller: wall-clock backoff; the schedule itself is the injectable Retrier


class _StreamWatchdog:
    """Bounds the gap between stream messages: a stream that stalls
    longer than `timeout_s` without closing is abandoned (cancel) so
    the caller can rotate — gRPC's own keepalive only detects dead
    TCP, not a live-but-silent orderer."""

    _DONE = object()
    _POLL_S = 0.5                         # stop_event responsiveness

    def __init__(self, stream, timeout_s: float,
                 stop_event: Optional[threading.Event],
                 rewind_pending: Callable[[], bool]):
        self._stream = stream
        self._timeout = timeout_s
        self._stop_event = stop_event
        # a quiet stream is left when its consumer asked for a rewind
        self._rewind_pending = rewind_pending
        self._abandoned = threading.Event()

    def abandon(self) -> None:
        """Unblock the pump thread (it must never stay parked in
        q.put after the consumer walks away — that would leak one
        thread per rotation)."""
        self._abandoned.set()

    def iterate(self):
        import queue as _queue
        q: "_queue.Queue" = _queue.Queue(8)

        def pump():
            try:
                for item in self._stream:
                    while not self._abandoned.is_set():
                        try:
                            q.put(item, timeout=0.5)
                            break
                        except _queue.Full:
                            continue
                    if self._abandoned.is_set():
                        return
            except Exception as e:
                log.debug("watchdog pump exiting: %r", e)
            while not self._abandoned.is_set():
                try:
                    q.put(self._DONE, timeout=0.5)
                    return
                except _queue.Full:
                    continue

        t = RegisteredThread(target=pump, name="deliver-pump",
                             structure="peer.blocksprovider")
        t.start()
        try:
            waited = 0.0
            while True:
                # short polls so a stop_event (peer shutdown) is seen
                # within _POLL_S even under a very long idle timeout
                try:
                    item = q.get(timeout=min(self._POLL_S,
                                             self._timeout))
                except _queue.Empty:
                    if (self._stop_event is not None
                            and self._stop_event.is_set()):
                        self._stream.cancel()
                        return
                    if self._rewind_pending():
                        self._stream.cancel()
                        return
                    waited += self._POLL_S
                    if waited >= self._timeout:
                        self._stream.cancel()  # silent stream: abandon
                        return
                    continue
                if item is self._DONE:
                    return
                waited = 0.0
                yield item
                if (self._stop_event is not None
                        and self._stop_event.is_set()):
                    self._stream.cancel()
                    return
        finally:
            self.abandon()
