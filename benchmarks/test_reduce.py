"""The trace reduction on events whose answer is known by hand and on
a small recorded trace, the yardstick's constants, and the manifest
against the files it names.  Runs on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/ -q -p no:cacheprovider
"""
import json
import os
import re

import pytest

from benchmarks import cellrun, manifest, reduce, timeline, work
from benchmarks.manifest import HERE, CHECKOUT

MS = 1e6    # nanoseconds


def ev(line, name, start_ms, dur_ms, plane="/device:TPU:0"):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def test_busy_is_the_union_of_op_intervals():
    events = [
        ev(reduce.OPS_LINE, "fusion.1", 0, 10),
        ev(reduce.OPS_LINE, "fusion.2", 5, 10),      # overlaps: union 15
        ev(reduce.OPS_LINE, "fusion.1", 40, 10),     # gap of 25 before
        ev(reduce.OPS_LINE, "copy", 90, 5),          # gap of 40 before
        ev(reduce.MODULES_LINE, "jit_verify_core(7)", 0, 15),
        ev(reduce.MODULES_LINE, "jit_verify_core(7)", 40, 10),
        ev(reduce.MODULES_LINE, "jit_verify_core(9)", 90, 5),
        ev(reduce.MODULES_LINE, "jit_other(1)", 200, 1),
    ]
    s = reduce.summarize_events(events, window_s=0.1)
    assert s.busy_s == pytest.approx(0.030)
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.70)
    assert s.top_ops(1) == [["fusion.1", pytest.approx(0.020)]]
    # a trace with programs and no per-op line: the programs are busy
    modules = [e for e in events if e[1] == reduce.MODULES_LINE]
    assert reduce.summarize_events(modules, 0.1).busy_s == \
        pytest.approx(0.031)


def test_busy_is_averaged_over_the_chips_used():
    events = [ev(reduce.OPS_LINE, "a", 0, 10, plane="/device:TPU:0"),
              ev(reduce.OPS_LINE, "a", 0, 30, plane="/device:TPU:1")]
    assert reduce.summarize_events(events, 1.0).busy_s == \
        pytest.approx(0.020)


def test_no_device_events_reduce_to_nothing(tmp_path):
    assert reduce.summarize_events([], 1.0).busy_s == 0.0
    with pytest.raises(FileNotFoundError):
        reduce.find_xplane(str(tmp_path))


def made_up_trace(monkeypatch, device_lines):
    """`jax.profiler.ProfileData.from_file` of any path gives a session
    plane and, unless `device_lines` is None, a TPU plane with these
    lines ({name: [(event name, start_ns, duration_ns)]})."""
    import types

    import jax.profiler

    def plane(name, lines, stats=()):
        return types.SimpleNamespace(
            name=name, stats=list(stats),
            lines=[types.SimpleNamespace(name=n, events=[
                types.SimpleNamespace(name=e, start_ns=a, duration_ns=d)
                for e, a, d in evs]) for n, evs in lines.items()])
    planes = [plane("Task Environment", {}, [
        ("profile_start_time", 7e18), ("profile_stop_time", 7e18 + 9e8)])]
    if device_lines is not None:
        planes.append(plane("/device:TPU:0", device_lines))
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: types.SimpleNamespace(planes=planes)))


def made_up_stamps(blocks=6):
    """A window of `blocks` commit events after the last warm-up block
    (block 2), closed at the last of them."""
    import types
    stamps = cellrun.Stamps(2, 60.0, lambda: ({}, 0, 0.0))
    for n in range(1, 3 + blocks):
        stamps(types.SimpleNamespace(
            header=types.SimpleNamespace(number=n),
            data=types.SimpleNamespace(data=[b""] * 8)))
    stamps.close_dry()
    return stamps


def assembled(summary, session):
    from benchmarks.reducers import idle_under
    from benchmarks.test_correct import OFF_CHIP, the_cell
    from fabric_mod_tpu.observability import tracing
    tracing.recorder().reset()          # no other test's spans
    idle_under.view_of_run.cache_clear()
    return cellrun.assemble(the_cell(), made_up_stamps(), True, summary,
                            session, [], dict(OFF_CHIP), {}, 0.0,
                            lambda msg: None)


def test_a_stretch_with_no_program_is_idle_and_correct(monkeypatch):
    """A device plane on which nothing ran in the window: busy 0 of the
    window, the run correct, and no metric of the device's programs."""
    made_up_trace(monkeypatch, {"XLA Modules": [], "XLA Ops": []})
    summary = reduce.summarize("made-up", window_s=0.412)
    programs, wall, _ = timeline.read_session("made-up")
    assert summary is not None and programs == []
    result = assembled(summary, timeline.Session(
        programs, wall[0], (7e9 + 0.1, 7e9 + 0.512)))
    assert result["correct"], result["compared"]
    assert result["compared"]["trace_missing"]["value"] == 0
    assert result["device"]["busy_s"] == 0.0
    assert result["device"]["window_s"] == 0.412
    assert not {"verify_kernel_ms_per_call", "verify_roofline_pct"} \
        & set(result["metrics"])


def test_a_session_with_no_trace_file_or_no_device_is_missing(
        monkeypatch, tmp_path):
    import jax.profiler
    made_up_trace(monkeypatch, None)            # no device plane
    assert reduce.summarize("made-up", window_s=0.4) is None
    # a session that wrote nothing: no trace file at all
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    from benchmarks.test_correct import the_cell
    said = []
    assert cellrun.profile_window(made_up_stamps(), the_cell(), 0.1,
                                  str(tmp_path), said.append) == (None, None)
    assert any("no .xplane.pb" in line for line in said)
    result = assembled(None, None)
    assert not result["correct"]
    assert result["compared"]["trace_missing"]["value"] == 1
    assert "busy_s" not in result["device"]


def test_recorded_trace_reads_as_recorded():
    """`testdata/probe.xplane.pb` was recorded on one TPU v5 lite chip
    (`benchmarks/testdata/record_probe.py`): CALLS executions of one
    jitted program named `bench_probe`, inside a window of known
    length, written beside it in `probe.json`."""
    path = os.path.join(HERE, "testdata", "probe.xplane.pb")
    with open(os.path.join(HERE, "testdata", "probe.json")) as f:
        facts = json.load(f)
    layout = []
    s = reduce.summarize(path, window_s=facts["window_s"], layout=layout)
    assert s is not None and s.n_planes == 1
    extent = []
    programs, _wall, _ = timeline.read_session(path, extent)
    assert [p[0].startswith("jit_bench_probe(") for p in programs] == \
        [True] * facts["calls"]
    program_s = sum(b - a for _n, a, b in programs) / 1e9
    # the program's intervals and the union of its operations agree,
    # and the recording spans all of them
    assert program_s == pytest.approx(s.busy_s, rel=0.01)
    assert extent[0][0] <= programs[0][1] and extent[0][1] >= programs[-1][2]
    assert 0 < s.busy_s < facts["window_s"]
    assert any("XLA Modules" in line for line in layout)


def test_yardstick_constants_follow_from_their_derivation():
    assert work.MODMULS_PER_VERIFY == 383 + 2 + 256 * 8 + 192 * 11 + 2
    assert work.OPS_PER_VERIFY == work.MODMULS_PER_VERIFY * 256
    least = work.least_seconds(1497, "TPU v5 lite")
    assert least["binds"] == "operations"
    assert least["seconds"] == pytest.approx(
        1497 * work.OPS_PER_VERIFY / 197e12)
    with pytest.raises(KeyError):
        work.least_seconds(1, "TPU v9 imaginary")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_names_files_that_exist_and_agree():
    bench = manifest.benchmark_json()
    assert bench["paths"] == ["benchmarks"]
    e2e = {e["name"] for e in bench["end_to_end"]}
    assert "setup_s" in e2e
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cell = manifest.Cell(w["name"], bench)       # raises on a mismatch
        used.add(w["config"])
        assert cell.file["why"] and len(w["why"]) <= 200
        cfg = configs[w["config"]]
        assert os.path.exists(os.path.join(CHECKOUT, cfg["file"]))
        assert sorted(cfg["reduced"]) == sorted(cell.config["reduced"])
        changed = [k for k, v in cell.config["source_settings"].items()
                   if cell.config["settings"][k] != v]
        assert set(changed) <= set(cfg["reduced"])
        assert hasattr(cell.generator(), "provision")
        assert {e["name"] for e in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert used == set(configs)
    layers = set()
    for p in bench["per_layer"]:
        assert NAME.match(p["name"]) and p["moves"] in e2e
        spec, fn = manifest.reducer_for(p["name"])
        assert callable(fn)
        layers.add(p["layer"])
    with open(os.path.join(CHECKOUT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
