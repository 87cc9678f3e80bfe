"""The trace reduction on events whose answer is known by hand and on
a small recorded trace, the yardstick's constants, and the manifest
against the files it names.  Runs on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/ -q -p no:cacheprovider
"""
import json
import os
import re

import pytest

from benchmarks import manifest, reduce, work
from benchmarks.manifest import HERE, CHECKOUT

MS = 1e6    # nanoseconds


def ev(line, name, start_ms, dur_ms, plane="/device:TPU:0"):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def test_busy_is_the_union_of_op_intervals_and_gaps_are_what_is_left():
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
    assert s.gap_secs[:2] == [pytest.approx(0.040), pytest.approx(0.025)]
    assert s.top_ops(1) == [["fusion.1", pytest.approx(0.020)]]
    found = s.modules_matching(["verify"])
    assert found == {"jit_verify_core(7)": [pytest.approx(0.025), 2],
                     "jit_verify_core(9)": [pytest.approx(0.005), 1]}


def test_busy_is_averaged_over_the_chips_used():
    events = [ev(reduce.OPS_LINE, "a", 0, 10, plane="/device:TPU:0"),
              ev(reduce.OPS_LINE, "a", 0, 30, plane="/device:TPU:1")]
    assert reduce.summarize_events(events, 1.0).busy_s == \
        pytest.approx(0.020)


def test_no_device_events_reduce_to_nothing(tmp_path):
    assert reduce.summarize_events([], 1.0).busy_s == 0.0
    with pytest.raises(FileNotFoundError):
        reduce.find_xplane(str(tmp_path))


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
    found = s.modules_matching(["bench_probe"])
    assert sum(v[1] for v in found.values()) == facts["calls"]
    program_s = sum(v[0] for v in found.values())
    # the program's intervals and the union of its operations agree
    assert program_s == pytest.approx(s.busy_s, rel=0.01)
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
