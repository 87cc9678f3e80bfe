"""The one timeline on events whose answer is known by hand: the idle
intervals and their names, self time, the device's shift, and the
reducers of the metrics that read them.  Beside `test_reduce.py`, and
run the same way:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/ -q -p no:cacheprovider
"""
import dataclasses
import json
import os
import re
import time

import pytest

from benchmarks import manifest, timeline, work
from benchmarks.manifest import HERE
from benchmarks.reducers import idle_under, roofline, trace_program
from fabric_mod_tpu.observability import spannames, tracing

MS = 1e6    # nanoseconds
WAITS = sorted(spannames.WAIT_SPANS)

NEW_METRICS = [
    "mcs_verify_ms_per_block", "submit_wait_ms_per_block",
    "stage_starved_ms_per_block", "stage_blocked_ms_per_block",
    "commit_starved_ms_per_block", "device_enqueue_ms_per_block",
    "idle_under_unpack_pct", "idle_under_enqueue_pct"]


def sp(thread, name, start_ms, end_ms):
    return (thread, name, start_ms * MS, end_ms * MS)


@dataclasses.dataclass
class FakeWindow:
    span_counts: dict
    trace: object = None


@pytest.fixture(autouse=True)
def clean_recorder():
    tracing.enable(False)
    tracing.recorder().reset()
    idle_under.view_of_run.cache_clear()
    yield
    tracing.enable(False)
    tracing.set_clock(time.time)
    tracing.recorder().reset()


def test_idle_before_the_first_and_after_the_last_program_is_counted():
    busy = [(100 * MS, 110 * MS), (105 * MS, 130 * MS), (400 * MS, 410 * MS)]
    idle = timeline.idle_intervals(busy, (0.0, 1000 * MS))
    assert idle == [(0.0, 100 * MS), (130 * MS, 400 * MS),
                    (410 * MS, 1000 * MS)]
    assert sum(b - a for a, b in idle) == pytest.approx(960 * MS)
    # with no span at all, nobody owns any of it
    assert {timeline.name_idle(iv, {}, WAITS) for iv in idle} == {
        timeline.HOST_UNATTRIBUTED}


def test_a_working_span_wins_over_a_longer_wait_span():
    spans = [sp("deliver", "submit_wait", 0, 100),       # waits all through
             sp("stage", "unpack", 20, 80),              # works 60 of 100
             sp("commit", "policy_finish", 0, 30)]
    self_iv = timeline.self_intervals(spans)
    assert timeline.name_idle((0.0, 100 * MS), self_iv, WAITS) == "unpack"
    assert timeline.working_overlaps((0.0, 100 * MS), self_iv, WAITS) == [
        ("unpack", 60 * MS), ("policy_finish", 30 * MS)]


def test_idle_under_a_span_counts_overlapping_threads_once():
    spans = [sp("stage", "unpack", 20, 80),
             sp("commit", "policy_finish", 0, 30),
             sp("commit", "policy_gather", 60, 120),
             sp("other", "policy_finish", 10, 40)]
    self_iv = timeline.self_intervals(spans)
    idle = [(0.0, 50 * MS), (70 * MS, 100 * MS)]
    assert timeline.idle_under_ns(idle, self_iv, ["unpack"]) == \
        pytest.approx(40 * MS)
    # 0-40 by two threads at once, then 70-100: 70, not 90
    assert timeline.idle_under_ns(
        idle, self_iv, ["policy_finish", "policy_gather"]) == \
        pytest.approx(70 * MS)
    assert timeline.idle_under_ns(idle, self_iv, ["mvcc"]) == 0.0


@pytest.mark.parametrize("spans,name", [
    # work covers 40 of 100, waits cover 70: waiting
    ([sp("stage", "unpack", 0, 40), sp("commit", "verdict_await", 30, 100)],
     timeline.HOST_WAITING),
    # work covers 40, waits 45: nobody owns half of it
    ([sp("stage", "unpack", 0, 40), sp("commit", "verdict_await", 55, 100)],
     timeline.HOST_UNATTRIBUTED),
    # two threads in the same span count its time once: 30, not 60
    ([sp("a", "mvcc", 0, 30), sp("b", "mvcc", 0, 30)],
     timeline.HOST_UNATTRIBUTED),
    ([], timeline.HOST_UNATTRIBUTED),
])
def test_half_covered_intervals_are_waiting_or_unattributed(spans, name):
    assert timeline.name_idle((0.0, 100 * MS),
                              timeline.self_intervals(spans), WAITS) == name


def test_window_lies_where_the_last_program_ends():
    busy = [(300 * MS, 320 * MS), (700 * MS, 730 * MS)]
    # a window of 500 ms in a session of 1,200: it ends with the last
    # program, or starts with the session where that would be earlier
    assert timeline.window_stretch(busy, 500 * MS, 1200 * MS) == \
        (230 * MS, 730 * MS)
    assert timeline.window_stretch(busy, 900 * MS, 1200 * MS) == \
        (0.0, 900 * MS)
    assert timeline.window_stretch([], 900 * MS, 800 * MS) == \
        (0.0, 800 * MS)
    # the trace begins in the middle of a program, 250 ms in: what the
    # chip did before that, nobody recorded
    assert timeline.window_stretch([(250 * MS, 260 * MS)] + busy, 500 * MS,
                                   1200 * MS, not_before=250 * MS) == \
        (250 * MS, 750 * MS)


def test_a_program_running_when_the_tracer_started_is_read_from_its_ops(
        monkeypatch):
    import types

    import jax.profiler

    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)

    def plane(name, lines, stats=()):
        return types.SimpleNamespace(
            name=name, stats=list(stats),
            lines=[types.SimpleNamespace(name=n, events=e)
                   for n, e in lines.items()])
    planes = [
        plane("Task Environment", {}, [("profile_start_time", 7e18),
                                       ("profile_stop_time", 7e18 + 9e8)]),
        plane("/device:TPU:0", {
            "XLA Modules": [ev("jit_verify(1)", 300.0, 100.0)],
            # the tail of a program from before the tracer started,
            # then the recorded program's own operations
            "XLA Ops": [ev("%a = x", 40.0, 10.0), ev("%b = x", 55.0, 200.0),
                        ev("%c = x", 300.0, 60.0), ev("%d = x", 365.0, 35.0)],
        })]
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: types.SimpleNamespace(planes=planes)))
    extent = []
    programs, wall, in_flight = timeline.read_session("made-up", extent)
    assert programs == [("jit_verify(1)", 300.0, 400.0)]
    assert wall == (7e18, 7e18 + 9e8)
    assert in_flight == (40.0, 255.0)
    # what the device's tracer recorded: from the rest's first operation
    # to the program's end
    assert extent == [(40.0, 400.0)]
    planes[1].lines[1].events[:2] = []
    assert timeline.read_session("made-up")[2] is None


def test_self_intervals_leave_out_the_nested_spans_of_the_same_thread():
    spans = [sp("stage", "device_dispatch", 0, 100),
             sp("stage", "der_marshal", 10, 40),
             sp("stage", "device_enqueue", 40, 90),
             sp("commit", "mvcc", 20, 30)]        # another thread: no child
    got = timeline.self_intervals(spans)
    assert got["device_dispatch"] == [(0.0, 10 * MS), (90 * MS, 100 * MS)]
    assert got["der_marshal"] == [(10 * MS, 40 * MS)]
    assert got["mvcc"] == [(20 * MS, 30 * MS)]
    # an idle interval inside the marshal is the marshal's, though the
    # dispatch span is open all through
    assert timeline.name_idle((12 * MS, 38 * MS), got, WAITS) == "der_marshal"


def test_device_shift_pairs_programs_with_enqueues_in_order():
    # the device's clock runs early: the first program seems to start
    # half a millisecond before its enqueue began; the second queued
    # behind the first
    enqueues = [(0 * MS, 1 * MS), (50 * MS, 51 * MS), (52 * MS, 53 * MS),
                (200 * MS, 201 * MS)]
    programs = [(49.5 * MS, 79.5 * MS), (79.5 * MS, 109.5 * MS)]
    pairs = timeline.pair_enqueues(programs, enqueues)
    assert pairs == [(programs[0], enqueues[1]), (programs[1], enqueues[2])]
    assert timeline.device_shift_ns(pairs) == pytest.approx(0.5 * MS)
    # the program in flight when the session opened pairs with the
    # enqueue from before it
    pairs = timeline.pair_enqueues([(3 * MS, 20 * MS)] + programs,
                                   enqueues[:3])
    assert pairs[0] == ((3 * MS, 20 * MS), enqueues[0])
    assert timeline.device_shift_ns(pairs) == pytest.approx(0.5 * MS)
    # a program that starts while its enqueue is still open (the
    # thread waits for the interpreter lock to close the span): no shift
    assert timeline.device_shift_ns(
        [((30 * MS, 170 * MS), (2 * MS, 42 * MS))]) == 0.0
    assert timeline.pair_enqueues(programs, enqueues[:1]) == []
    assert timeline.device_shift_ns([]) == 0.0


# -- whole calls --------------------------------------------------------------

START_NS = 1.7e18                   # the session's start on the wall clock
TABLES = "jit__verify_core_tables_impl(4711)"
LADDER = "jit__verify_core_impl(815)"


def wall(ms):
    """The `time.time()` of `ms` milliseconds into the session."""
    return (START_NS + ms * MS) / 1e9


def dispatch(ring, items, at_ms, parent, thread="stage"):
    """One device call as the provider records it: the marshal of its
    items, then the enqueue, which begins 1 ms before the program does
    on the device."""
    ring.append({"name": "der_marshal", "thread": thread,
                 "parent_id": parent, "ts": wall(at_ms - 3), "dur": 0.0015,
                 "attrs": {"items": items, "bucket": 2048}})
    ring.append({"name": "device_enqueue", "thread": thread,
                 "parent_id": parent, "ts": wall(at_ms - 1), "dur": 0.0005,
                 "attrs": {"bucket": 2048}})


@dataclasses.dataclass
class CallWindow:
    session: object
    ring: list
    device_kind: str = "TPU v5 lite"


def window_of(programs, ring, opened=100, closed=500, recorded_end=None):
    """The profiler held open from `opened` to `closed` ms into the
    session, the device's tracer recording until `recorded_end` (ms)."""
    return CallWindow(timeline.Session(
        programs, START_NS, (wall(opened), wall(closed)),
        recorded_end and recorded_end * MS), ring)


SPEC = {"programs": ["verify"]}


@pytest.mark.parametrize("opened,closed,recorded_end", [
    # as on the chip: the last program is cut where the recording
    # ends, which on the host's clock lies just inside the window
    (100, 484.5, 483.5),
    # where the recording's end is not known, the window alone
    (100, 483, None),
], ids=["as_on_the_chip", "window_alone"])
def test_whole_calls_leave_out_the_programs_cut_at_either_end(
        opened, closed, recorded_end):
    """Four calls of the table program a block period apart: one began
    before the window opened (it may have been running when the tracer
    started), two ran whole, one was cut when the tracer stopped.  Time
    and work are those of the two whole calls."""
    ring, programs = [], []
    for k, (start, end, items) in enumerate([
            (90, 139, 1498), (200, 249, 1200), (330, 379, 1300),
            (480, 483.5, 1100)]):
        dispatch(ring, items, start, parent=f"block{k}")
        programs.append((TABLES, start * MS, end * MS))
    programs.append(("jit_other(5)", 10 * MS, 11 * MS))   # no enqueue
    window = window_of(programs, ring, opened, closed, recorded_end)
    calls = timeline.whole_calls(window.session, ring, SPEC["programs"])
    assert [(n, i) for n, _s, i in calls] == [(TABLES, 1200), (TABLES, 1300)]
    assert [s for _n, s, _i in calls] == [pytest.approx(0.049)] * 2
    assert trace_program.reduce(SPEC, window) == pytest.approx(49.0)
    least = work.least_seconds(1200 + 1300, "TPU v5 lite")["seconds"]
    assert roofline.reduce(SPEC, window) == pytest.approx(
        100.0 * least / 0.098)
    assert 0.012 < roofline.reduce(SPEC, window) < 0.035


def test_the_first_thing_recorded_is_whole_where_it_began_in_the_window():
    """`testnet10.backlog` on the chip: the chip idles when the tracer
    starts, one whole 5.8 ms program, then the tail of the next one,
    cut where the recording ends."""
    ring = []
    dispatch(ring, 31, 120, parent="block40")
    dispatch(ring, 31, 131, parent="block41")
    programs = [(TABLES, 120 * MS, 125.8 * MS), (TABLES, 131 * MS, 131.8 * MS)]
    window = window_of(programs, ring, opened=100, closed=132,
                       recorded_end=131.8)
    assert [(i, round(s, 6)) for _n, s, i in timeline.whole_calls(
        window.session, ring, SPEC["programs"])] == [(31, 0.0058)]
    assert trace_program.reduce(SPEC, window) == pytest.approx(5.8)


@pytest.mark.parametrize("programs_ms,closed,recorded_end,whole", [
    # `thakkar4.backlog-nof` on the chip: the chip idled for the last
    # 77 ms of the window, after three whole programs
    ([(103.09, 152.13), (245.43, 294.47), (383.68, 432.72)], 509.88,
     432.72, 3),
    # `smallbank.backlog-zipf`: a tail from before the window, then two
    # whole programs and 125 ms of idle
    ([(98.41, 98.65), (233.91, 282.94), (467.82, 516.86)], 641.98, 516.86,
     2),
    # the same last program with the recording ending 2 ms before the
    # close: it may have been stopped while the program ran
    ([(233.91, 282.94), (467.82, 516.86)], 518.86, 516.86, 1),
], ids=["idle_77ms", "idle_125ms", "stopped_at_close"])
def test_the_last_thing_recorded_is_whole_where_the_chip_idled_to_the_close(
        programs_ms, closed, recorded_end, whole):
    ring, programs = [], []
    for k, (start, end) in enumerate(programs_ms):
        dispatch(ring, 1498, start, parent=f"block{k}")
        programs.append((TABLES, start * MS, end * MS))
    window = window_of(programs, ring, opened=100, closed=closed,
                       recorded_end=recorded_end)
    calls = timeline.whole_calls(window.session, ring, SPEC["programs"])
    assert len(calls) == whole
    assert [s for _n, s, _i in calls] == [pytest.approx(0.04904, abs=1e-4)] * whole


def test_each_call_takes_the_items_of_its_own_marshal():
    """A block split between the table program and the ladder: two
    marshals and two enqueues under one parent, and another block's
    call on another thread between them."""
    ring = []
    dispatch(ring, 1000, 200, parent="block7")
    dispatch(ring, 7, 230, parent="block8", thread="commit")
    dispatch(ring, 498, 260, parent="block7")
    programs = [(TABLES, 200 * MS, 249 * MS), (LADDER, 249 * MS, 255 * MS),
                (LADDER, 260 * MS, 402 * MS)]
    calls = timeline.whole_calls(window_of(programs, ring).session, ring,
                                 SPEC["programs"])
    assert [(n, i) for n, _s, i in calls] == [
        (TABLES, 1000), (LADDER, 7), (LADDER, 498)]
    # the program that took most device time is the ladder's
    assert trace_program.reduce(SPEC, window_of(programs, ring)) == \
        pytest.approx(74.0)


def test_a_tail_alone_gives_no_reading_where_it_gave_170_percent():
    """5 us of a program cut when the window closed: read as a call,
    1,498 signatures' least time over it is ~177%."""
    ring = []
    dispatch(ring, 1498, 499.993, parent="block9")
    programs = [(TABLES, 499.993 * MS, 499.998 * MS)]
    least = work.least_seconds(1498, "TPU v5 lite")["seconds"]
    assert 100.0 * least / 5e-6 > 170.0
    # it ends where the recording does, inside the window by the host's
    # clock; or after the window closed, where no extent is known
    for window in (window_of(programs, ring, recorded_end=499.998),
                   window_of(programs, ring, closed=499.996)):
        assert trace_program.reduce(SPEC, window) is None
        assert roofline.reduce(SPEC, window) is None
    # nor is anything read where the programs cannot be paired with
    # their enqueues, or where no session was recorded
    assert timeline.whole_calls(window.session, [], SPEC["programs"]) == []
    assert roofline.reduce(SPEC, CallWindow(None, ring)) is None


def test_the_reducer_finds_nothing_rather_than_zero(monkeypatch):
    spec = {"spans": ["unpack"], "wait_spans": WAITS,
            "programs": ["verify"]}
    # no trace
    assert idle_under.reduce(spec, FakeWindow({})) is None
    # a program from before this PR: no `device_enqueue` span
    ring = [{"name": "unpack", "thread": "stage", "ts": 5.0, "dur": 1.0}]
    monkeypatch.setattr(tracing.recorder(), "recent_spans",
                        lambda limit=0: ring)
    traced = FakeWindow({}, trace=FakeTrace(0.1))
    assert idle_under.reduce(spec, traced) is None
    # the trace cannot be told: none, or more than one, since the run began
    ring.append({"name": "device_enqueue", "thread": "stage", "ts": 5.5,
                 "dur": 0.1})
    idle_under.view_of_run.cache_clear()
    monkeypatch.setattr(timeline, "find_session_xplane", lambda since: None)
    assert idle_under.reduce(spec, traced) is None


def test_only_this_runs_trace_is_found(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(timeline.tempfile, "gettempdir",
                        lambda: str(tmp_path))

    def leave(run, mtime):
        d = tmp_path / f"bench-{run}" / "profile" / "plugins" / "profile" \
            / "2026_01_01"
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (mtime, mtime))
        return str(f)
    assert timeline.find_session_xplane(0.0) is None
    left_behind = leave("dead", 1000.0)
    assert timeline.find_session_xplane(0.0) == left_behind
    assert timeline.find_session_xplane(1500.0) is None
    own = leave("own", 2000.0)
    assert timeline.find_session_xplane(1500.0) == own
    leave("other", 2100.0)
    assert timeline.find_session_xplane(1500.0) is None
    assert "cannot be told" in capsys.readouterr().err


@dataclasses.dataclass
class FakeTrace:
    window_s: float


def test_recorded_trace_and_a_made_up_ring_give_named_idle(monkeypatch,
                                                           capsys):
    """`testdata/probe.xplane.pb`: five executions of one program,
    ~11 ms apart from 43.5 ms on, in a session of 345 ms."""
    path = os.path.join(HERE, "testdata", "probe.xplane.pb")
    with open(os.path.join(HERE, "testdata", "probe.json")) as f:
        facts = json.load(f)
    programs, wall, in_flight = timeline.read_session(path)
    assert in_flight is None            # the probe's chip was at rest
    assert len(programs) == facts["calls"]
    assert {p[0].split("(")[0] for p in programs} == {"jit_bench_probe"}
    length_s = (wall[1] - wall[0]) / 1e9
    assert facts["window_s"] < length_s < 1.0

    def ring_span(name, thread, start_ns, end_ns):
        return {"name": name, "thread": thread,
                "ts": (wall[0] + start_ns) / 1e9,
                "dur": (end_ns - start_ns) / 1e9}
    ring = [ring_span("unpack", "stage", 0, wall[1] - wall[0])]
    for _name, a, _b in programs:
        # each enqueue begins 0.1 ms after its program starts on the
        # device's clock: the shift has to come out as 0.1 ms
        ring.append(ring_span("device_enqueue", "stage",
                              a + 0.1 * MS, a + 0.6 * MS))
    monkeypatch.setattr(timeline, "find_session_xplane",
                        lambda since: path)
    monkeypatch.setattr(tracing.recorder(), "recent_spans",
                        lambda limit=0: ring)
    spec = {"spans": ["unpack"], "wait_spans": WAITS,
            "programs": ["bench_probe"]}
    window = FakeWindow({}, trace=FakeTrace(facts["window_s"]))
    # the stretch ends with the last program; what the programs leave
    # of it is unpack's, but for the enqueues' half milliseconds
    end = max(b for _n, _a, b in programs)
    start = end - facts["window_s"] * 1e9
    busy_ns = sum(b - max(a, start) for _n, a, b in programs if b > start)
    idle_pct = 100.0 * (1.0 - busy_ns / (end - start))
    assert 50.0 < idle_pct < 100.0
    # the programs take microseconds: four of the five enqueues' half
    # milliseconds lie in the idle time after them (the last one ends
    # after the stretch does), and the rest is unpack's alone
    under_enqueue = idle_under.reduce(
        dict(spec, spans=["device_enqueue"]), window)
    assert under_enqueue == pytest.approx(
        100.0 * 4 * 0.0005 / facts["window_s"], abs=0.1)
    assert idle_under.reduce(spec, window) == pytest.approx(
        idle_pct - under_enqueue, abs=0.01)
    # a span the run recorded nowhere: nothing; one that it did, but
    # not where the chip idled: zero
    assert idle_under.reduce(dict(spec, spans=["mvcc"]), window) is None
    said = capsys.readouterr().err
    assert said.count("idle_under:") == 1        # read once per run
    shift_us = float(re.search(r"shifted by (\S+) us", said).group(1))
    # (a `time.time()` of today resolves a quarter of a microsecond)
    assert shift_us == pytest.approx(100.0, abs=1.0)
    assert "'name': 'unpack'" in said and "'at_work_s': [['unpack', 0.0" \
        in said
    # the breakdown's idle gaps: the same intervals, named, longest first
    gaps = idle_under.idle_gaps(spec, window)
    assert 0 < len(gaps) <= 10 and gaps[0][0] == "unpack"
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert "idle_under:" not in capsys.readouterr().err  # not read again
    # a program that records no enqueue span: nothing, not zero
    idle_under.view_of_run.cache_clear()
    monkeypatch.setattr(tracing.recorder(), "recent_spans",
                        lambda limit=0: ring[:1])
    assert idle_under.reduce(spec, window) is None


def test_manifest_has_the_new_metrics_with_their_files():
    bench = manifest.benchmark_json()
    by_name = {p["name"]: p for p in bench["per_layer"]}
    for name in NEW_METRICS:
        entry = by_name[name]
        assert entry["moves"] == "committed_tx_s"
        assert "workloads" not in entry          # both cells read it
        spec, fn = manifest.reducer_for(name)
        assert callable(fn)
        for span in spec.get("spans", []) + spec.get("wait_spans", []):
            assert spannames.is_declared(span), span
        if spec["reducer"] == "idle_under":
            assert sorted(spec["wait_spans"]) == WAITS


def test_traced_run_reports_the_new_span_metrics():
    from benchmarks.test_correct import drive
    result = drive(91, traced=True)
    got = set(result["metrics"])
    # the stand-in verifier marshals and enqueues nothing, and the
    # CPU has no device plane
    assert set(NEW_METRICS) - got == {
        "device_enqueue_ms_per_block", "idle_under_unpack_pct",
        "idle_under_enqueue_pct"}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["mcs_verify_ms_per_block"] <= m["recv_ms_per_block"]
