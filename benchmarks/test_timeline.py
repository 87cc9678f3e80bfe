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

from benchmarks import manifest, timeline
from benchmarks.manifest import HERE
from benchmarks.reducers import idle_under
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
    # `reduce.gaps` saw only the 270 ms in the middle; with no span
    # at all, nobody owns any of it
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
    programs, wall, in_flight = timeline.read_session("made-up")
    assert programs == [("jit_verify", 300.0, 400.0)]
    assert wall == (7e18, 7e18 + 9e8)
    assert in_flight == (40.0, 255.0)
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
    assert {p[0] for p in programs} == {"jit_bench_probe"}
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
