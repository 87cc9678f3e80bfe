"""One timeline: the program's spans beside the device's programs, and
a name for every second in which the chip did nothing.

Two steps, as in `reduce.py`, so that the arithmetic can be checked on
made-up events:

* `read_session(path)` reads, from a profiler trace, the executions
  of compiled programs on the fullest chip (line `XLA Modules`; from
  line `XLA Ops` only the rest of a program that was running when the
  tracer started) and the session's start and stop on the wall clock
  (plane `Task Environment`, stats `profile_start_time`/
  `profile_stop_time`, ns since the epoch; the trace's own times
  count from that start);
* everything else is arithmetic on plain tuples.

The program's spans come from its recorder's ring, on `time.time()`:
the same wall clock, so `ts * 1e9 - profile_start_time` puts a span on
the trace's clock.  The device's clock is not the host's:
`device_shift_ns` moves the device's events so that no program starts
before the `device_enqueue` span that sent it began.

The session is longer than the window `cellrun.py` held it open for:
it starts some 50 ms before `start_trace` returns and ends when the
tracers have stopped, 0.3 s and more after the device's tracer
recorded its last event.  `window_stretch` takes a stretch of the
window's length out of the session: the one that ends with the last
recorded program.  It may begin earlier than `cellrun.py`'s own
window did (by no more than `start_trace` took), and the device's
tracer starts later than the session: where the trace begins in the
middle of a program the stretch begins no earlier than that program's
first recorded operation; where it does not, a program that ran and
ended between the session's start and the tracer's would be missed.

What the host did while the chip did nothing is said in two ways.
`idle_under_ns`: the idle time during which a span of some name was
at work on any thread (its *self* interval: its own time, less the
spans nested in it on its thread).  Threads overlap, so these do not
add up to the idle time.  And a name for each idle interval, by the
rule below: one winner per interval, which changes hands between runs
where two spans cover it about equally.

The naming rule (PERF.md section 3).  Idle is every part of the
window in which no program ran, the time before the first and after
the last included.  An idle interval is named after the span with the
largest overlap, among the *self* intervals (a span's own time, less
the spans nested in it on its thread) of spans that are not in
`wait_spans`, on any thread.  Where no such span covers at least half
of the interval: `host_waiting` if the wait spans do, else
`host_unattributed`.

What a device call costs, and how much work it did, is read from whole
calls only (`whole_calls`): a program that ran from start to end
inside the window, with the items of the dispatch that sent it.  A
program cut at either end of the window is busy time and no call.
"""
import dataclasses
import glob
import math
import os
import re
import sys
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmarks.reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                               union_seconds)

HOST_WAITING = "host_waiting"
HOST_UNATTRIBUTED = "host_unattributed"
SESSION_PLANE = "Task Environment"
ENQUEUE = "device_enqueue"        # the span that sends one program
MARSHAL = "der_marshal"           # the span that packs that program's items

Interval = Tuple[float, float]                  # (start_ns, end_ns)
# (thread, name, start_ns, end_ns) on the trace's clock
HostSpan = Tuple[str, str, float, float]
Program = Tuple[str, float, float]              # (event name, start, end)


# -- reading -----------------------------------------------------------------

def find_session_xplane(since_s: float) -> Optional[str]:
    """The trace `cellrun.profile_window` wrote in this run: it keeps
    its profile under a `bench-*` directory of the temporary
    directory until the result is assembled.  `since_s` is a
    `time.time()` of this run from before its profiler window: a trace
    written earlier is what a run that died left behind.  More than
    one written since is another process's beside this one's: that is
    refused, not guessed at."""
    found = [p for p in glob.glob(os.path.join(
        tempfile.gettempdir(), "bench-*", "profile", "plugins", "profile",
        "*", "*.xplane.pb")) if os.path.getmtime(p) >= since_s]
    if len(found) > 1:
        print(f"timeline: {len(found)} traces under "
              f"{tempfile.gettempdir()}/bench-* were written during this "
              f"run; which is this run's cannot be told: {sorted(found)}",
              file=sys.stderr)
    return found[0] if len(found) == 1 else None


def read_session(path: str, extent: Optional[list] = None
                 ) -> Tuple[List[Program], Optional[Interval],
                            Optional[Interval]]:
    """(program executions on the fullest chip, each named by its
    event: `<jit name>(<fingerprint>)`; the session's start and stop in
    wall-clock ns; the rest of a program that was running when the
    device's tracer started).  Such a program has no event on the
    programs' line, only its remaining operations on line `XLA Ops`:
    the stretch from the first to the last operation that ended before
    the first recorded program began.  `extent`, if given, receives the
    (first start, last end) of everything recorded on that chip's two
    lines: where the device's tracer began and stopped recording."""
    from jax.profiler import ProfileData
    by_plane: Dict[str, List[Program]] = {}
    ops_lines = {}
    session = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == SESSION_PLANE:
            session = {k: v for k, v in plane.stats}
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                by_plane[plane.name] = [
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns))
                    for ev in line.events]
            elif line.name == OPS_LINE:
                ops_lines[plane.name] = line
    fullest = max(by_plane, default=None, key=lambda name: union_seconds(
        [p[1:] for p in by_plane[name]]))
    programs = sorted(by_plane.get(fullest, []), key=lambda p: p[1])
    in_flight = None
    ops = ops_lines[fullest].events if fullest in ops_lines else []
    if programs and ops:
        first = programs[0][1]
        lo = hi = None
        for ev in ops:
            end = ev.start_ns + ev.duration_ns
            if end <= first:
                lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                hi = end if hi is None else max(hi, end)
        if lo is not None:
            in_flight = (float(lo), float(hi))
    if extent is not None and programs:
        extent.append((
            min([programs[0][1]] + [float(ev.start_ns) for ev in ops]),
            max([p[2] for p in programs]
                + [float(ev.start_ns + ev.duration_ns) for ev in ops])))
    try:
        wall = (float(session["profile_start_time"]),
                float(session["profile_stop_time"]))
    except KeyError:
        wall = None
    return programs, wall, in_flight


def spans_on_trace_clock(ring: Iterable[dict], start_wall_ns: float
                         ) -> List[HostSpan]:
    """The recorder's spans (`Span.to_dict()`: `ts` and `dur` in
    seconds of `time.time()`) on the trace's clock."""
    return [(sp["thread"], sp["name"], sp["ts"] * 1e9 - start_wall_ns,
             (sp["ts"] + sp["dur"]) * 1e9 - start_wall_ns) for sp in ring]


def cut(spans: Iterable[HostSpan], stretch: Interval) -> List[HostSpan]:
    lo, hi = stretch
    return [(t, n, max(a, lo), min(b, hi)) for t, n, a, b in spans
            if b > lo and a < hi]


# -- arithmetic --------------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def subtract(interval: Interval, holes: Iterable[Interval]
             ) -> List[Interval]:
    """What is left of `interval` outside the merged `holes`."""
    a, b = interval
    out = []
    for ha, hb in holes:
        if hb <= a or ha >= b:
            continue
        if ha > a:
            out.append((a, ha))
        a = max(a, hb)
    if a < b:
        out.append((a, b))
    return out


def overlap_ns(interval: Interval, merged: Iterable[Interval]) -> float:
    a, b = interval
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged)


def self_intervals(spans: Iterable[HostSpan]) -> Dict[str, List[Interval]]:
    """name -> merged intervals in which a span of that name was the
    innermost open span of its thread."""
    by_thread: Dict[str, List[HostSpan]] = {}
    for sp in spans:
        by_thread.setdefault(sp[0], []).append(sp)
    out: Dict[str, List[Interval]] = {}
    for own in by_thread.values():
        # an enclosing span starts no later and ends no earlier
        own.sort(key=lambda s: (s[2], -s[3]))
        children: List[List[Interval]] = [[] for _ in own]
        open_: List[int] = []
        for i, (_t, _n, a, b) in enumerate(own):
            while open_ and own[open_[-1]][3] <= a:
                open_.pop()
            if open_:
                children[open_[-1]].append((a, b))
            open_.append(i)
        for (_t, name, a, b), kids in zip(own, children):
            out.setdefault(name, []).extend(subtract((a, b), merge(kids)))
    return {name: merge(iv) for name, iv in out.items()}


def window_stretch(busy: Iterable[Interval], window_ns: float,
                   session_ns: float, not_before: float = 0.0) -> Interval:
    """A stretch of the session as long as the window `cellrun.py`
    held the profiler open for.  The device's tracer records no
    program that ends after that window does, so the stretch ends
    where the last recorded one does, and starts `window_ns` earlier:
    but not before the session, nor before `not_before`, the first
    operation of a program that was running when the device's tracer
    started (what the chip did before that, nobody recorded; the
    window opened after it).  Where
    the device idles at the window's end the stretch begins earlier
    than the window did, by no more than `start_trace` took to return
    (58 and 45 ms in two sessions with a host tracer to show it:
    PERF.md section 6)."""
    last_end = max((b for _a, b in busy), default=0.0)
    start = max(0.0, not_before, last_end - window_ns)
    return (start, min(start + window_ns, session_ns))


def idle_intervals(busy: Iterable[Interval], stretch: Interval
                   ) -> List[Interval]:
    """The stretch less the busy intervals: the time before the first
    and after the last of them included."""
    return subtract(stretch, merge(busy))


def working_overlaps(interval: Interval,
                     self_iv: Dict[str, List[Interval]],
                     wait_spans: Sequence[str]) -> List[Tuple[str, float]]:
    """[(name, ns of the interval inside that span's self intervals)]
    of the spans that are work, largest first."""
    found = [(name, overlap_ns(interval, iv))
             for name, iv in self_iv.items() if name not in wait_spans]
    return sorted((f for f in found if f[1] > 0), key=lambda f: -f[1])


def name_idle(interval: Interval, self_iv: Dict[str, List[Interval]],
              wait_spans: Sequence[str]) -> str:
    half = (interval[1] - interval[0]) / 2.0
    working = working_overlaps(interval, self_iv, wait_spans)
    if working and working[0][1] >= half:
        return working[0][0]
    waiting = merge(iv for name in wait_spans
                    for iv in self_iv.get(name, []))
    if waiting and overlap_ns(interval, waiting) >= half:
        return HOST_WAITING
    return HOST_UNATTRIBUTED


def idle_under_ns(idle: Iterable[Interval],
                  self_iv: Dict[str, List[Interval]],
                  names: Sequence[str]) -> float:
    """ns of the idle intervals during which a span of one of `names`
    was at work on some thread (counted once where two were)."""
    at_work = merge(iv for name in names for iv in self_iv.get(name, []))
    return sum(overlap_ns(iv, at_work) for iv in idle)


def pairing_offset(programs: Sequence[Interval],
                   enqueues: Sequence[Interval],
                   slack_ns: float = 5e6) -> Optional[int]:
    """The device runs programs in the order they were sent, so the
    k-th program of the trace (by start) belongs to the (i + k)-th
    enqueue (by start) for one i: the largest for which no program
    starts more than `slack_ns` before its enqueue began.  None where
    there is no such i."""
    programs, enqueues = sorted(programs), sorted(enqueues)
    for first in range(len(enqueues) - len(programs), -1, -1):
        if all(e[0] <= p[0] + slack_ns
               for p, e in zip(programs, enqueues[first:])):
            return first
    return None


def pair_enqueues(programs: Sequence[Interval],
                  enqueues: Sequence[Interval],
                  slack_ns: float = 5e6) -> List[Tuple[Interval, Interval]]:
    """[(program, the enqueue that sent it)], by `pairing_offset`.
    Empty where there is no such pairing."""
    first = pairing_offset(programs, enqueues, slack_ns)
    if first is None:
        return []
    return list(zip(sorted(programs), sorted(enqueues)[first:]))


def device_shift_ns(pairs: Sequence[Tuple[Interval, Interval]]) -> float:
    """What to add to the device's times so that no program starts
    before the enqueue that sent it began: 0 unless the trace shows
    such a start.  (Not before the enqueue *returned*: under the
    interpreter lock the span's thread gets to close it milliseconds
    after the runtime launched the program.)  `pair_enqueues` takes
    the latest enqueues that can have sent the programs, so the lags
    it shows are the least they can be, and with them the shift; where
    the device's queue held a program more, the true lags are longer.
    What is left of the two clocks' difference is no more than the
    smallest lag of a program behind its enqueue's beginning, if the
    device's clock is the late one; if it is early, nothing here
    bounds it."""
    return max([0.0] + [e[0] - p[0] for p, e in pairs])


# -- whole calls -------------------------------------------------------------

@dataclasses.dataclass
class Session:
    """What `cellrun.profile_window` recorded: the trace's programs
    and the end of the last thing the device's tracer recorded on that
    chip (`read_session`'s extent), the session's start on the wall
    clock in ns, and the window it held the profiler open for on the
    same clock (`time.time()` once `start_trace` had returned, and
    before `stop_trace` was called)."""
    programs: List[Program]
    start_wall_ns: float
    window_wall: Tuple[float, float]
    recorded_end: Optional[float] = None


# a program event that ends this close to the last thing the device's
# tracer recorded touches the end of the recording
TOUCH_NS = 1e4
# the recording was stopped with a program running only where it ended
# this close to the window's close or later: on the chip a cut tail ends
# 0.05-1.0 ms before the host's close by the trace's clock; a recording
# that ends earlier ends where the chip went idle, after a whole program
CUT_NS = 5e6


# (program event name, device seconds, real items its dispatch marshalled
# or None where no `der_marshal` span is found for it)
Call = Tuple[str, float, Optional[int]]


def whole_calls(session: Session, ring: Sequence[dict],
                like: Sequence[str]) -> List[Call]:
    """The calls of the programs whose event name matches one of the
    regular expressions `like` that ran whole inside the window.

    The device's tracer starts some 50 ms before `start_trace` returns
    and stops with the window.  So a program event that began before
    the window opened, by the host's clock, may have been running when
    the tracer started, and one that touches the end of what the
    tracer recorded (`Session.recorded_end`, within `TOUCH_NS`) may
    have been cut when it stopped, where the recording went on to
    within `CUT_NS` of the window's close: neither is a call, nor is
    one that ended after the window closed.  (On the chip a program
    launched at the window's last commit event leaves a tail of
    0.1-3.6 ms that ends inside the window by the host's clock, at the
    recording's end; where the chip went idle after the last program
    and stayed so until the close, that program is whole.  The first
    program of a stretch is often the first thing recorded, and is
    whole where it began after the window opened.)
    The rest of a program that `read_session` returns as in flight has
    no event and is no call either.  Each program is given to the
    `device_enqueue` span that sent it by `pairing_offset`, its times
    are shifted by `device_shift_ns`, and its items are those of the
    `der_marshal` span that the same thread began last, under the same
    parent, before that enqueue began: the real signatures of that one
    call.  Empty where the programs cannot be paired with enqueues."""
    sent = sorted((p for p in session.programs
                   if any(re.search(pat, p[0]) for pat in like)),
                  key=lambda p: p[1:])
    enqueues = sorted((sp for sp in ring if sp["name"] == ENQUEUE),
                      key=lambda sp: (sp["ts"], sp["dur"]))
    on_trace = [(sp["ts"] * 1e9 - session.start_wall_ns,
                 (sp["ts"] + sp["dur"]) * 1e9 - session.start_wall_ns)
                for sp in enqueues]
    first = pairing_offset([p[1:] for p in sent], on_trace)
    if first is None:
        return []
    shift = device_shift_ns(list(zip([p[1:] for p in sent],
                                     on_trace[first:])))
    marshals: Dict[tuple, List[dict]] = {}
    for sp in ring:
        if sp["name"] == MARSHAL:
            marshals.setdefault((sp["thread"], sp.get("parent_id")),
                                []).append(sp)
    lo, hi = (w * 1e9 - session.start_wall_ns for w in session.window_wall)
    last_seen = math.inf if session.recorded_end is None \
        else session.recorded_end
    stopped_running = last_seen + shift > hi - CUT_NS
    calls: List[Call] = []
    for (name, start, end), enq in zip(sent, enqueues[first:]):
        if start + shift < lo or end + shift > hi \
                or (stopped_running and last_seen - end < TOUCH_NS):
            continue
        marshal = max((m for m in marshals.get(
            (enq["thread"], enq.get("parent_id")), [])
            if m["ts"] <= enq["ts"]), key=lambda m: m["ts"], default=None)
        calls.append((name, (end - start) / 1e9,
                      None if marshal is None
                      else marshal["attrs"].get("items")))
    return calls
