"""From a profiler trace (`.xplane.pb`) to device numbers.

Two steps, so that the arithmetic can be checked without a trace:

* `read_device_events(path)` reads the planes of the devices with
  `jax.profiler.ProfileData` and returns plain tuples;
* `summarize_events(...)` is pure arithmetic on those tuples: the
  union of the intervals in which an operation ran (busy) and the time
  by operation name.  What one program's call took is read from whole
  calls only (`timeline.whole_calls`).

Layout of a TPU trace as `jax.profiler` writes it (looked at by hand,
PERF.md section 6): one plane per chip named `/device:TPU:<n>`; its
line `XLA Modules` holds one event per execution of a compiled
program, named `<jit name>(<fingerprint>)`; its line `XLA Ops` holds
one event per operation inside them.  Other lines (`Steps`,
`XLA TraceMe`, ...) repeat or group these and are not counted.
"""
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# (plane, line, name, start_ns, duration_ns)
Event = Tuple[str, str, str, float, float]


def find_xplane(profile_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def read_device_events(path: str, layout: Optional[list] = None,
                       planes: Optional[list] = None) -> List[Event]:
    """`layout`, if given, receives one line of text per plane and
    line of the trace (name, events, most frequent names): what to
    read when the trace has to be looked at by hand.  `planes`, if
    given, receives the name of every device plane, also of one on
    which nothing ran."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if on_device and planes is not None:
            planes.append(plane.name)
        for line in plane.lines:
            keep = on_device and line.name in (OPS_LINE, MODULES_LINE)
            names: Dict[str, int] = {}
            n = 0
            for ev in line.events:
                n += 1
                if layout is not None and on_device:
                    names[ev.name] = names.get(ev.name, 0) + 1
                if keep:
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
            if layout is not None and line.name == "XLA TraceMe" and any(
                    "Dropped" in k for k in names):
                layout.append(f"trace plane {plane.name!r}: the profiler "
                              f"DROPPED trace buffers; busy time is "
                              f"undercounted")
            if layout is not None and n:
                top = sorted(names.items(), key=lambda kv: -kv[1])[:4]
                layout.append(f"trace plane {plane.name!r} line "
                              f"{line.name!r}: {n} events {top}")
    return out


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of (start_ns, end_ns) intervals, seconds."""
    total = 0.0
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e9


def op_of(op_event_name: str) -> str:
    """An op event is named by its whole HLO line; `%fusion.2 = ...`
    -> `fusion.2`."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over the chips used
    n_events: int
    n_planes: int
    op_secs: Dict[str, float]           # operation name -> seconds

    def top_ops(self, n: int) -> List[list]:
        ranked = sorted(self.op_secs.items(), key=lambda kv: -kv[1])
        return [[name, secs] for name, secs in ranked[:n]]


def summarize_events(events: List[Event], window_s: float) -> TraceSummary:
    by_plane: Dict[str, List[Tuple[float, float]]] = {}
    op_secs: Dict[str, float] = {}
    for plane, line, name, start, dur in events:
        if line == OPS_LINE:
            by_plane.setdefault(plane, []).append((start, start + dur))
            op = op_of(name)
            op_secs[op] = op_secs.get(op, 0.0) + dur / 1e9
    if not by_plane:
        # a trace with programs but no per-op line: the programs'
        # own intervals are the busy time
        for plane, line, name, start, dur in events:
            if line == MODULES_LINE:
                by_plane.setdefault(plane, []).append((start, start + dur))
    busy = [union_seconds(iv) for iv in by_plane.values()]
    return TraceSummary(
        window_s=window_s,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        n_events=len(events), n_planes=len(by_plane),
        op_secs=op_secs)


def summarize(path: str, window_s: float,
              layout: Optional[list] = None) -> Optional[TraceSummary]:
    """None where the trace has no device plane: nothing of the chip
    was recorded.  A device plane on which no operation ran in the
    window is a measurement: busy 0 of `window_s`."""
    planes: List[str] = []
    events = read_device_events(path, layout, planes)
    if not planes:
        return None
    return summarize_events(events, window_s)
