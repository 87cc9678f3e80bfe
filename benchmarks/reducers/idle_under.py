"""Of a stretch of the profiler's session as long as the window
`cellrun.py` held it open for, the share in which the chip ran no
program while a span of one of the named spans was at work on some
thread of the host (`timeline.py`: the span's self intervals), in
percent of the stretch.  The time before the first and after the last
program counts as idle.  The pipeline's threads overlap, so the
metrics of this reducer do not add up to the idle share.

The stretch's idle intervals go to standard error once per run,
longest first, each with the name `timeline.name_idle` gives it, the
spans most at work in it and the time the wait spans cover; the ten
longest, with their names, are the result line's `breakdown.idle_gaps`
(`idle_gaps`).

The session's programs are read from the trace `cellrun.py` wrote in
this run, the spans from the program's own recorder; the device's
times are shifted by `timeline.device_shift_ns` (the `device_enqueue`
spans against the programs that match `programs`).  None where there
is no trace, where the program records no `device_enqueue` span (a
program from before the waits were spans) or none of the named spans.

spec: {"spans": [names of the spans at work], "wait_spans": [names of
       the spans that are waits, not work: `spannames.WAIT_SPANS`],
       "programs": [regular expressions on the jit name of the
       programs a `device_enqueue` span sends]}
"""
import dataclasses
import functools
import re
import sys
from typing import Dict, List, Optional, Set

from benchmarks import timeline


@dataclasses.dataclass
class View:
    stretch: timeline.Interval
    idle: List[timeline.Interval]
    self_iv: Dict[str, List[timeline.Interval]]
    recorded: Set[str]                 # span names of the whole run
    gaps: List[list]                   # [[name, seconds]], longest first


def reduce(spec, window):
    if window.trace is None:
        return None
    view = view_of_run(window.trace.window_s, tuple(spec["programs"]),
                       tuple(spec["wait_spans"]))
    if view is None or not view.recorded & set(spec["spans"]):
        return None
    under = timeline.idle_under_ns(view.idle, view.self_iv, spec["spans"])
    return 100.0 * under / (view.stretch[1] - view.stretch[0])


def idle_gaps(spec, window, n: int = 10) -> List[list]:
    """[[name, seconds]] of the stretch's `n` longest idle intervals,
    each named by `timeline.name_idle`; empty where the run gives no
    view (no trace, no program in it, no `device_enqueue` span)."""
    if window.trace is None:
        return []
    view = view_of_run(window.trace.window_s, tuple(spec["programs"]),
                       tuple(spec["wait_spans"]))
    return [] if view is None else view.gaps[:n]


@functools.lru_cache(maxsize=1)
def view_of_run(window_s: float, programs_like: tuple, wait_spans: tuple
                ) -> Optional[View]:
    """Read once per run: the metrics of this reducer share it."""
    from fabric_mod_tpu.observability import tracing
    ring = tracing.recorder().recent_spans(limit=1 << 30)
    if not any(sp["name"] == timeline.ENQUEUE for sp in ring):
        return None
    path = timeline.find_session_xplane(min(sp["ts"] for sp in ring))
    if path is None:
        return None
    programs, wall, in_flight = timeline.read_session(path)
    if not programs or wall is None:
        return None
    spans = timeline.spans_on_trace_clock(ring, wall[0])
    sent = [p[1:] for p in programs
            if any(re.search(pat, p[0]) for pat in programs_like)]
    pairs = timeline.pair_enqueues(
        sent, [s[2:] for s in spans if s[1] == timeline.ENQUEUE])
    shift = timeline.device_shift_ns(pairs)
    lags = sorted(p[0] + shift - e[0] for p, e in pairs)
    busy = [(a + shift, b + shift) for a, b in
            [p[1:] for p in programs] + ([in_flight] if in_flight else [])]
    session_ns = wall[1] - wall[0]
    stretch = timeline.window_stretch(
        busy, window_s * 1e9, session_ns,
        not_before=in_flight[0] + shift if in_flight else 0.0)
    self_iv = timeline.self_intervals(timeline.cut(spans, stretch))
    idle = timeline.idle_intervals(busy, stretch)
    longest = sorted(idle, key=lambda iv: iv[0] - iv[1])
    names = [timeline.name_idle(iv, self_iv, wait_spans)
             for iv in longest[:10]]
    told = []
    for iv, name in zip(longest[:8], names):
        told.append({
            "name": name,
            "s": round((iv[1] - iv[0]) / 1e9, 6),
            "start_s": round(iv[0] / 1e9, 6),
            "at_work_s": [[n, round(ns / 1e9, 6)] for n, ns in
                          timeline.working_overlaps(
                              iv, self_iv, wait_spans)[:4]],
            "waiting_s": round(timeline.idle_under_ns(
                [iv], self_iv, wait_spans) / 1e9, 6)})
    print(f"idle_under: stretch {stretch[0] / 1e9:.6f}s to "
          f"{stretch[1] / 1e9:.6f}s of a session of "
          f"{session_ns / 1e9:.6f}s, {len(programs)} programs"
          f"{' and the rest of one that ran when the tracer started' if in_flight else ''}, "
          f"{len(pairs)} paired with their enqueue and starting "
          f"at least {[round(x / 1e3) for x in lags[:8]]} us after it "
          f"began, "
          f"device times shifted by {shift / 1e3:.1f} us, idle "
          f"{sum(b - a for a, b in idle) / 1e9:.6f}s in {len(idle)} "
          f"intervals; the longest: {told}", file=sys.stderr)
    return View(stretch, idle, self_iv, {sp["name"] for sp in ring},
                [[name, (iv[1] - iv[0]) / 1e9]
                 for iv, name in zip(longest, names)])
