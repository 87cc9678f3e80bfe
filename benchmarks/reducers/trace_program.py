"""Device milliseconds per call of the compiled program that took most
device time in the traced window's whole calls (`timeline.whole_calls`:
a program cut at either end of the window is no call), among those
whose jit name matches.  None where the window holds no whole call.
The whole calls go to standard error: program, ms, items.

spec: {"programs": [regular expressions on the jit name]}
"""
import sys

from benchmarks import timeline


def reduce(spec, window):
    if window.session is None:
        return None
    session = window.session
    calls = timeline.whole_calls(session, window.ring, spec["programs"])
    opened = session.window_wall[0] * 1e9 - session.start_wall_ns
    print(f"trace_program: {len(calls)} whole call(s) of "
          f"{len(session.programs)} program event(s) in the trace: "
          f"{[(n, round(1e3 * s, 4), i) for n, s, i in calls]}; ms from "
          f"the window's opening: closed "
          f"{1e3 * (session.window_wall[1] - session.window_wall[0]):.4f}, "
          f"recorded to {round((session.recorded_end - opened) / 1e6, 4) if session.recorded_end else None}, "
          f"programs {[(round((a - opened) / 1e6, 4), round((b - opened) / 1e6, 4)) for _n, a, b in session.programs]}",
          file=sys.stderr)
    by_program = {}
    for name, secs, _items in calls:
        by_program.setdefault(name, []).append(secs)
    if not by_program:
        return None
    secs = max(by_program.values(), key=sum)
    return 1e3 * sum(secs) / len(secs)
