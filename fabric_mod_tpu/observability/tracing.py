"""In-process distributed tracing + flight recorder: attribute every
commit-path millisecond.

(reference model: Dapper (Sigelman et al., 2010) — trace_id/span_id/
parent links with explicit context propagation across async seams —
applied the way FastFabric (Gorenflo et al., 2019) profiled Fabric's
commit path before optimizing it.  The reference repo ships the
metrics half of this layer (core/operations + common/diag,
reproduced in observability/metrics.py + opsserver.py); this module
is the missing tracing half.)

Four instruments, one arming gate (``FMT_TRACE``, the FMT_RACECHECK
/ FMT_FAULTS cost model — unset, every seam is one module-flag read
and NO span objects are allocated):

* **Spans** — ``with tracing.span("unpack", block=7):`` creates a
  Span (trace_id/span_id/parent) timed on the injectable clock,
  pushed on a thread-local stack so nested spans parent naturally.
  Explicit carriers cross threads (``current_ctx()`` → pass the
  TraceContext, ``span(name, parent=ctx)``) and processes
  (``inject()``/``extract()`` — a gRPC-metadata traceparent pair, the
  broadcast client/server carrier).  Finished spans land in a bounded
  ring served at ``/trace`` and feed per-name cumulative totals (the
  bench's stage-attribution source) plus the
  ``fabric_trace_substage_seconds`` histogram.  Beside its wall
  duration a span keeps its **self time** (the duration less that of
  the spans nested in it on the same thread, so a sum over names
  counts no second twice) and its **thread CPU time**
  (``time.thread_time()`` at both ends: wall less CPU is the time the
  thread was off the CPU — blocked, or waiting for the interpreter
  lock).  The spans in ``spannames.WAIT_SPANS`` are waits, not work.
  A span's ``ts`` is ``time.time()``: whoever holds a profiler trace
  of the same stretch puts the spans beside the device's programs by
  the session's own start on that clock (the benchmark does).

* **Block timelines** — the commit path opens one
  ``start_timeline(consumer, block_num)`` per block; every span that
  finishes while that timeline is installed (``timeline_scope``)
  becomes one of its sub-stage entries (recv, unpack, der_marshal,
  device_dispatch, verdict_await, policy_finish, mvcc, ledger_write,
  fingerprint).  The timeline object itself is the cross-thread
  carrier: the commitpipe stage loop starts it, StagedBlock carries
  it, the commit loop resumes it — one per-block record of where the
  milliseconds went, in a bounded **flight recorder** ring served at
  ``/flight``.

* **The collector's pauses** — while armed, and only then, one
  ``gc.callbacks`` entry times every collection of every generation
  as a span ``gc_pause`` (attributes ``generation``, ``collected``,
  ``uncollectable``) on the thread that ran it, nested under the span
  open there: the pause leaves that span's self time, and joins the
  block timeline installed on that thread.  A collection can start
  inside any allocation, the recorder's own critical section
  included, so the callback takes no lock: it reads the clocks,
  appends one tuple to the recorder's inbox, and ``add_span``,
  ``totals()`` and ``recent_spans()`` drain the inbox into the ring,
  the totals and the histogram.  Its cost is two callbacks a
  collection, armed only; unarmed, ``gc.callbacks`` holds nothing of
  this module's.

* **Auto-dumps** — SoakError, a circuit-breaker open, and fault-seam
  fires snapshot the recorder (rate-limited) so a failure report
  carries the timeline of what the system was DOING, not just which
  invariant broke.

Plus the device lens: ``export_chrome_trace()`` writes the span ring
as Chrome trace-event JSON (Perfetto-loadable; device dispatches as
async slices) and ``install_compile_counter()`` counts XLA
compiles/retraces into ``fabric_tpu_compiles_total``.  The module
opens no profiler session of its own: whoever wants the device's
side (the benchmark does) opens one around the work.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import gc
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from fabric_mod_tpu.observability.metrics import (MetricOpts,
                                                  default_provider)
from fabric_mod_tpu.utils import knobs
from fabric_mod_tpu.concurrency.locks import RegisteredLock

# -- the arming gate (mirrors concurrency.core / faults.core) ---------------

_enabled = knobs.get_bool("FMT_TRACE")


def armed() -> bool:
    return _enabled


def enable(on: bool) -> None:
    global _enabled
    _enabled = bool(on)
    _hook_collector(_enabled)


@contextlib.contextmanager
def active(on: bool = True):
    """Scoped arming — tests and the bench's traced arms."""
    prev = _enabled
    enable(on)
    try:
        yield
    finally:
        enable(prev)


# -- clock (injectable: tests drive a ManualClock through spans) ------------

_clock = time.time


def set_clock(fn) -> None:
    """``fn() -> float`` seconds; pass ``time.time`` to restore."""
    global _clock
    _clock = fn


# -- ring bounds ------------------------------------------------------------

def _ring(env: str) -> int:
    return max(8, knobs.get_int(env))


SPAN_RING = _ring("FMT_TRACE_SPANS")
FLIGHT_RING = _ring("FMT_TRACE_RING")

_SUBSTAGE_OPTS = MetricOpts(
    "fabric", "trace", "substage_seconds",
    help="Per-span wall seconds by sub-stage name (the commit "
         "timeline's recv/unpack/der_marshal/device_dispatch/"
         "verdict_await/policy_*/mvcc/ledger_write/fingerprint "
         "split, FMT_TRACE armed only).",
    label_names=("stage",))
_COMPILES_OPTS = MetricOpts(
    "fabric", "tpu", "compiles_total",
    help="XLA compiles/retraces observed via jax.monitoring (0 until "
         "install_compile_counter() ran; a climbing value mid-steady-"
         "state means shapes are churning and dispatches re-trace).")


@functools.lru_cache(maxsize=None)
def _substage_hist():
    return default_provider().histogram(
        _SUBSTAGE_OPTS, buckets=(0.0005, 0.002, 0.01, 0.05, 0.25,
                                 1.0, 5.0, 30.0))


@functools.lru_cache(maxsize=None)
def _compiles_counter():
    return default_provider().counter(_COMPILES_OPTS)


# -- context ---------------------------------------------------------------

class TraceContext(collections.namedtuple("TraceContext",
                                          ("trace_id", "span_id"))):
    """The minimal propagated identity: what a child span needs to
    link itself under a parent across any seam."""
    __slots__ = ()


_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def new_trace_id() -> str:
    return os.urandom(8).hex()


def current_ctx() -> Optional[TraceContext]:
    """This thread's innermost live span as a carrier, or None."""
    st = getattr(_tls, "stack", None)
    if not st:
        return None
    sp = st[-1]
    return TraceContext(sp.trace_id, sp.span_id)


# gRPC metadata carrier (lowercase key per gRPC metadata rules)
TRACE_METADATA_KEY = "fmt-trace-context"


def inject(ctx: Optional[TraceContext] = None
           ) -> Optional[List[Tuple[str, str]]]:
    """Serialize a context as gRPC metadata; None when unarmed or no
    context is live (callers pass the result straight through —
    ``metadata=None`` is gRPC's no-metadata)."""
    if not _enabled:
        return None
    if ctx is None:
        ctx = current_ctx()
    if ctx is None:
        return None
    return [(TRACE_METADATA_KEY, f"{ctx.trace_id}-{ctx.span_id}")]


def extract(metadata) -> Optional[TraceContext]:
    """Parse the carrier out of gRPC invocation metadata (any iterable
    of (key, value)); malformed/absent → None, never a raise — a bad
    header must not fail the RPC it rode in on."""
    if not metadata:
        return None
    try:
        for key, value in metadata:
            if key == TRACE_METADATA_KEY:
                tid, _, sid = str(value).partition("-")
                if tid and sid:
                    return TraceContext(tid, sid)
    except Exception:
        return None
    return None


# -- spans -------------------------------------------------------------------

class Span:
    """One timed operation.  Context manager; on exit it pops the TLS
    stack, lands in the recorder ring + totals, and — when a block
    timeline is installed on this thread — becomes one of that
    timeline's sub-stage entries."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "ts",
                 "dur", "self_dur", "cpu", "attrs", "thread",
                 "_child", "_cpu0")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], attrs: Dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs
        self.thread = threading.current_thread().name
        self.ts = 0.0
        self.dur = 0.0
        self.self_dur = 0.0     # dur less the nested spans' dur
        self.cpu = 0.0          # this thread's CPU seconds inside
        self._child = 0.0
        self._cpu0 = 0.0

    @property
    def ctx(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id)

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        # timed before it is pushed: a collection that finds it on the
        # stack finds its `ts` set (`_on_gc`)
        self._cpu0 = time.thread_time()
        self.ts = _clock()
        _stack().append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur = max(0.0, _clock() - self.ts)
        self.cpu = max(0.0, time.thread_time() - self._cpu0)
        self.self_dur = max(0.0, self.dur - self._child)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        st = getattr(_tls, "stack", None)
        if st and st[-1] is self:
            st.pop()
            if st:
                st[-1]._child += self.dur
        tl = getattr(_tls, "timeline", None)
        if tl is not None:
            tl.add(self.name, self.ts, self.dur)
        _recorder.add_span(self)
        return False

    def to_dict(self) -> Dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "ts": self.ts, "dur": round(self.dur, 6),
                "self": round(self.self_dur, 6),
                "cpu": round(self.cpu, 6),
                "thread": self.thread, "attrs": self.attrs}


class _NoopSpan:
    """The unarmed singleton: every method a no-op, every entry
    returns itself.  ``span()`` returns THIS object (never a fresh
    allocation) when FMT_TRACE is unset — the zero-allocation
    contract the differential test pins."""

    __slots__ = ()
    ctx = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NOOP = _NoopSpan()


def span(name: str, parent=None, **attrs):
    """Open a span.  `parent` may be a TraceContext, a Span, or None
    (None: the thread's current span, else a fresh trace).  Unarmed:
    returns the no-op singleton — no allocation, no clock read."""
    if not _enabled:
        return _NOOP
    if parent is None:
        parent = current_ctx()
    if parent is None:
        trace_id, parent_id = new_trace_id(), None
    else:
        trace_id, parent_id = parent.trace_id, parent.span_id
    return Span(name, trace_id, os.urandom(4).hex(), parent_id, attrs)


# -- block timelines (the flight recorder's unit) ---------------------------

class BlockTimeline:
    """One block's commit-path timeline: every sub-stage span that ran
    while this timeline was installed.  Created by the commit engine
    on the stage side, carried by StagedBlock across the stage→commit
    handoff, finished after the ledger write — the cross-thread trace
    of exactly one block."""

    __slots__ = ("consumer", "block_num", "trace_id", "ts", "dur",
                 "subs", "_done")

    def __init__(self, consumer: str, block_num: int, trace_id: str):
        self.consumer = consumer
        self.block_num = block_num
        self.trace_id = trace_id
        self.ts = _clock()
        self.dur = 0.0
        self.subs: List[Tuple[str, float, float]] = []
        self._done = False

    def add(self, name: str, ts: float, dur: float) -> None:
        self.subs.append((name, ts, dur))

    def to_dict(self) -> Dict:
        return {"consumer": self.consumer, "block": self.block_num,
                "trace_id": self.trace_id, "ts": self.ts,
                "dur": round(self.dur, 6),
                "subs": [{"name": n, "ts": t, "dur": round(d, 6)}
                         for n, t, d in self.subs]}


def start_timeline(consumer: str, block_num: int,
                   parent: Optional[TraceContext] = None
                   ) -> Optional[BlockTimeline]:
    if not _enabled:
        return None
    return BlockTimeline(
        consumer, block_num,
        parent.trace_id if parent is not None else new_trace_id())


@contextlib.contextmanager
def timeline_scope(tl: Optional[BlockTimeline]):
    """Install `tl` as this thread's active timeline (None: no-op).
    Spans finishing inside the scope become its sub-stage entries."""
    if tl is None:
        yield None
        return
    prev = getattr(_tls, "timeline", None)
    _tls.timeline = tl
    try:
        yield tl
    finally:
        _tls.timeline = prev


def finish_timeline(tl: Optional[BlockTimeline]) -> None:
    """Close the timeline and push it into the flight-recorder ring
    (idempotent — engine error paths may finish defensively)."""
    if tl is None or tl._done:
        return
    tl._done = True
    tl.dur = max(0.0, _clock() - tl.ts)
    _recorder.add_timeline(tl)


# -- the recorder ------------------------------------------------------------

class Recorder:
    """Bounded rings of recent spans / block timelines / events, the
    cumulative per-name totals (bench stage attribution), and the
    auto-dump snapshots.  One process-wide instance; every access is
    lock-serialized and cheap (deque appends)."""

    _DUMP_MIN_INTERVAL_S = 5.0

    def __init__(self):
        self._lock = RegisteredLock("observability.tracing._lock")
        self._spans: collections.deque = collections.deque(
            maxlen=SPAN_RING)
        self._timelines: collections.deque = collections.deque(
            maxlen=FLIGHT_RING)
        self._events: collections.deque = collections.deque(maxlen=256)
        self._dumps: collections.deque = collections.deque(maxlen=8)
        # name -> [secs, n, self secs, cpu secs]
        self._totals: Dict[str, List[float]] = {}
        self._last_dump = 0.0
        # the collector's pauses not yet drained, appended by `_on_gc`
        # without the lock and taken out only under it; bounded for a
        # process that is armed and records nothing else (a collection
        # at every allocation, tests/test_tracing.py, left ~1,500)
        self._pauses: collections.deque = collections.deque(
            maxlen=max(SPAN_RING, 1 << 16))

    def add_span(self, sp: Span) -> None:
        with self._lock:
            pauses = self._drain_locked()
            self._put_locked(sp)
        _observe_pauses(pauses)
        _substage_hist().with_labels(sp.name).observe(sp.dur)

    def _put_locked(self, sp: Span) -> None:
        self._spans.append(sp.to_dict())
        tot = self._totals.get(sp.name)
        if tot is None:
            tot = self._totals[sp.name] = [0.0, 0, 0.0, 0.0]
        tot[0] += sp.dur
        tot[1] += 1
        tot[2] += sp.self_dur
        tot[3] += sp.cpu

    def _drain_locked(self) -> List[float]:
        """The pauses `_on_gc` left, into the ring and the totals as
        spans; their durations, for the histogram outside the lock."""
        durs = []
        while self._pauses:
            (ts, dur, cpu, thread, parent, generation, collected,
             uncollectable) = self._pauses.popleft()
            sp = Span(GC_PAUSE,
                      parent.trace_id if parent else new_trace_id(),
                      os.urandom(4).hex(),
                      parent.span_id if parent else None,
                      {"generation": generation, "collected": collected,
                       "uncollectable": uncollectable})
            sp.thread, sp.ts, sp.dur, sp.self_dur, sp.cpu = (
                thread, ts, dur, dur, cpu)
            self._put_locked(sp)
            durs.append(dur)
        return durs

    def add_timeline(self, tl: BlockTimeline) -> None:
        with self._lock:
            self._timelines.append(tl.to_dict())

    def note_event(self, kind: str, detail: str) -> None:
        with self._lock:
            self._events.append(
                {"ts": _clock(), "kind": kind, "detail": detail})

    # -- read surface ------------------------------------------------------
    def recent_spans(self, trace_id: Optional[str] = None,
                     limit: int = 512) -> List[Dict]:
        with self._lock:
            pauses = self._drain_locked()
            out = list(self._spans)
        _observe_pauses(pauses)
        if trace_id is not None:
            out = [s for s in out if s["trace_id"] == trace_id]
        return out[-limit:]

    def timelines(self, limit: int = FLIGHT_RING) -> List[Dict]:
        with self._lock:
            return list(self._timelines)[-limit:]

    def events(self, limit: int = 256) -> List[Dict]:
        with self._lock:
            return list(self._events)[-limit:]

    def totals(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            pauses = self._drain_locked()
            out = {name: {"secs": round(t[0], 6), "count": int(t[1]),
                          "self_secs": round(t[2], 6),
                          "cpu_secs": round(t[3], 6)}
                   for name, t in self._totals.items()}
        _observe_pauses(pauses)
        return out

    def dumps(self) -> List[Dict]:
        with self._lock:
            return list(self._dumps)

    def span_count(self) -> int:
        with self._lock:
            return len(self._spans)

    def timeline_count(self) -> int:
        with self._lock:
            return len(self._timelines)

    def reset(self) -> None:
        """Clear everything (bench attribution windows, tests)."""
        with self._lock:
            self._spans.clear()
            self._timelines.clear()
            self._events.clear()
            self._dumps.clear()
            self._totals.clear()
            self._pauses.clear()
            self._last_dump = 0.0

    # -- auto-dump ---------------------------------------------------------
    def auto_dump(self, reason: str) -> Optional[Dict]:
        """Snapshot the recorder on a failure signal (SoakError,
        breaker open, fault fire).  Rate-limited: a fault storm must
        not turn the recorder into its own hot path.  The "dump"
        event is appended only when a snapshot was actually taken —
        the tape must not claim dumps the limiter suppressed (nor let
        phantom entries evict the fault/shed breadcrumbs)."""
        now = time.monotonic()
        with self._lock:
            if now - self._last_dump < self._DUMP_MIN_INTERVAL_S \
                    and self._dumps:
                return None
            self._last_dump = now
            snap = {"reason": reason, "ts": _clock(),
                    "timelines": list(self._timelines)[-32:],
                    "events": list(self._events)[-64:]}
            self._dumps.append(snap)
            self._events.append(
                {"ts": _clock(), "kind": "dump", "detail": reason})
        return snap


_recorder = Recorder()


def recorder() -> Recorder:
    return _recorder


# -- the collector's pauses --------------------------------------------------

GC_PAUSE = "gc_pause"
# the open collection's `_clock()` (None: none open) and thread CPU time;
# the interpreter runs one collection at a time
_gc_began: List[Optional[float]] = [None, 0.0]
_hook_lock = RegisteredLock("observability.tracing._hook_lock")


def _on_gc(phase: str, info: Dict) -> None:
    """The `gc.callbacks` entry, installed while armed only.  A
    collection starts inside whatever allocation crossed the threshold,
    on any thread, with whatever locks that thread holds — the
    recorder's and a histogram's included — so this takes no lock and
    never blocks: it reads the clocks, charges the pause to the span
    open on this thread, and appends one tuple for `Recorder` to
    drain."""
    if phase == "start":
        _gc_began[1] = time.thread_time()
        _gc_began[0] = _clock()
        return
    t0 = _gc_began[0]
    if t0 is None:              # hooked while a collection ran
        return
    _gc_began[0] = None
    t1 = _clock()
    cpu = time.thread_time() - _gc_began[1]
    st = getattr(_tls, "stack", None)
    parent = st[-1] if st else None
    if parent is not None:
        # only the part inside the parent: a closing span has its `dur`
        end = parent.ts + parent.dur if parent.dur else t1
        inside = min(t1, end) - max(t0, parent.ts)
        if inside > 0:
            parent._child += inside
    tl = getattr(_tls, "timeline", None)
    if tl is not None:
        tl.add(GC_PAUSE, t0, t1 - t0)
    ident = threading.get_ident()
    # not `current_thread()`: for a thread `threading` never saw it
    # registers one under `threading`'s own lock
    th = threading._active.get(ident)
    _recorder._pauses.append((
        t0, t1 - t0, cpu, th.name if th is not None else f"Dummy-{ident}",
        parent, info["generation"], info["collected"],
        info["uncollectable"]))


def _hook_collector(on: bool) -> None:
    with _hook_lock:
        hooked = _on_gc in gc.callbacks
        if on != hooked:
            _gc_began[0] = None
            if on:
                gc.callbacks.append(_on_gc)
            else:
                gc.callbacks.remove(_on_gc)


def _observe_pauses(durs: List[float]) -> None:
    if durs:
        hist = _substage_hist().with_labels(GC_PAUSE)
        for dur in durs:
            hist.observe(dur)


_hook_collector(_enabled)


def note_event(kind: str, detail: str) -> None:
    """Record a one-line event into the flight recorder (armed only —
    unarmed this is one flag read)."""
    if _enabled:
        _recorder.note_event(kind, detail)


def auto_dump(reason: str) -> None:
    if _enabled:
        _recorder.auto_dump(reason)


def flight_text(limit: int = 8) -> str:
    """Compact flight-recorder tail for attaching to error text
    (SoakError's replay block): the last `limit` block timelines, one
    line each, plus recent events."""
    lines = [f"flight recorder (last {limit} block timelines):"]
    for tl in _recorder.timelines()[-limit:]:
        subs = " ".join(f"{s['name']}={s['dur'] * 1000:.1f}ms"
                        for s in tl["subs"])
        lines.append(
            f"  [{tl['consumer']}] block {tl['block']} "
            f"trace {tl['trace_id']} dur {tl['dur'] * 1000:.1f}ms: "
            f"{subs or '(no sub-spans)'}")
    ev = _recorder.events()[-limit:]
    if ev:
        lines.append("recent events: " + "; ".join(
            f"{e['kind']}:{e['detail']}" for e in ev))
    return "\n".join(lines)


def flight_dump() -> Dict:
    """The /flight payload: ring + events + auto-dumps + totals."""
    return {"armed": _enabled,
            "timelines": _recorder.timelines(),
            "events": _recorder.events(),
            "dumps": _recorder.dumps(),
            "totals": _recorder.totals()}


def substage_totals() -> Dict[str, Dict[str, float]]:
    return _recorder.totals()


# -- Chrome trace-event export (Perfetto-loadable) --------------------------

def export_chrome_trace(path: str) -> int:
    """Write the span ring as Chrome trace-event JSON: one complete
    ("X") event per span (ts/dur in µs), device dispatches ALSO as
    async ("b"/"e") slices so the device lane reads as its own track
    in Perfetto.  Returns the number of events written."""
    pid = os.getpid()
    events: List[Dict] = [{
        "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
        "args": {"name": "fabric_mod_tpu"}}]
    tids: Dict[str, int] = {}
    for sp in _recorder.recent_spans(limit=SPAN_RING):
        tid = tids.setdefault(sp["thread"], len(tids) + 1)
        events.append({
            "ph": "X", "pid": pid, "tid": tid, "name": sp["name"],
            "cat": "span", "ts": round(sp["ts"] * 1e6, 1),
            "dur": round(sp["dur"] * 1e6, 1),
            "args": {"trace_id": sp["trace_id"],
                     "span_id": sp["span_id"],
                     "parent_id": sp["parent_id"], **sp["attrs"]}})
        if sp["name"] == "device_dispatch":
            ts = round(sp["ts"] * 1e6, 1)
            common = {"pid": pid, "tid": tid, "cat": "device",
                      "name": "device_batch", "id": sp["span_id"]}
            events.append({"ph": "b", "ts": ts, **common})
            events.append({
                "ph": "e", "ts": round((sp["ts"] + sp["dur"]) * 1e6, 1),
                **common})
    for name, tid in tids.items():
        events.append({"ph": "M", "pid": pid, "tid": tid,
                       "name": "thread_name", "args": {"name": name}})
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms",
                   "otherData": {"xla_compiles": _compile_count,
                                 "substage_totals": substage_totals()}},
                  f)
    return len(events)


# -- device lens: the compile counter ---------------------------------------

_compile_lock = RegisteredLock("observability.tracing._compile_lock")
_compile_installed = False
_compile_count = 0


def install_compile_counter() -> bool:
    """Count XLA compiles/retraces into fabric_tpu_compiles_total via
    jax.monitoring event listeners.  Best-effort and idempotent: the
    listener API varies across jax versions, so failure to install
    just leaves the counter at 0 (never an import error on the
    commit path)."""
    global _compile_installed
    with _compile_lock:
        if _compile_installed:
            return True

        def _on_event(event: str, *a, **kw) -> None:
            global _compile_count
            if "compile" in event or "trace" in event:
                # concurrent dispatch threads compile concurrently:
                # the read-modify-write needs the lock or retraces
                # undercount — the exact shape-churn signal this
                # counter exists to surface
                with _compile_lock:
                    _compile_count += 1
                _compiles_counter().add(1)

        try:
            import jax
            jax.monitoring.register_event_listener(_on_event)
            _compile_installed = True
        except Exception:
            return False
    return True


def compile_count() -> int:
    return _compile_count
