"""bench.py's entry: one process, no children, no way to the CPU but
`--cpu`, and every JSON line labelled with the device that measured.

(The suite runs with the platform forced to the CPU, which is exactly
the "no chip" machine the device guard exists for.)
"""
import json
import sys

import pytest

import bench


def _main(monkeypatch, *argv) -> int:
    monkeypatch.setattr(sys, "argv", ["bench.py", *argv])
    return bench.main()


@pytest.mark.parametrize("argv", [
    ("--metric", "verify"),
    ("--metric", "commitpipe"),                  # device arm by default
    ("--metric", "broadcaststorm", "--storm-verifier", "device"),
], ids=lambda a: " ".join(a))
def test_device_metric_refuses_to_run_off_the_chip(monkeypatch, capsys,
                                                   argv):
    assert _main(monkeypatch, "--batch", "8", "--reps", "1", *argv) != 0
    out, err = capsys.readouterr()
    assert out == ""                 # no number under a device name
    assert "refusing" in err and "--cpu" in err


def test_host_only_metric_runs_and_names_its_device(monkeypatch, capsys):
    assert _main(monkeypatch, "--cpu", "--metric", "marshal",
                 "--batch", "64", "--reps", "1") == 0
    (line,) = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(line)
    assert rec["metric"].startswith("marshal_items_per_sec")
    assert (rec["platform"], rec["device_kind"]) == ("cpu", "cpu")
    assert rec["n_devices"] >= 1


def test_bench_starts_no_children():
    assert not hasattr(bench, "subprocess")
    for gone in ("supervise", "_spawn_worker", "_preflight_probe",
                 "_run_bounded"):
        assert not hasattr(bench, gone), gone
