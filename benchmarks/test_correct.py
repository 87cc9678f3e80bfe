"""`correct` has to come out false when the timed path is broken.

These tests skip the harness's look for a chip and drive the rest of a
run (`cellrun.run_cell`) at a size a test can hold: a test's cells of
8-tx blocks (`testdata/`), the software verifier standing in for the
device one, with the fault planted underneath.  A sound run comes out
correct; each fault the cell can have comes out not correct, by the
number named.  (One chip, so no exchange between chips to leave out.)

Two rehearsal deployments, and every fault on both: three orgs under
2-of-3 with two endorsements per transaction, as the benchmark's two
configurations run; and five orgs under 3-of-5 with three
endorsements, whose network (`"network": {"orgs": [...]}`), rule
(`"reference"`) and endorsement count (`endorsements_per_tx`) reach
the run from its files alone.

The precision control cannot be shown on the CPU, where
`Precision.HIGH` and `HIGHEST` are the same arithmetic: it is run on
the chip by `seeds.py --control` (readings in PERF.md);
`test_control_on_the_chip` repeats it where a TPU is attached, and
`test_control_stand_in` plants on the CPU what the control did on the
chip.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/ -q -p no:cacheprovider
"""
import ast
import copy
import glob
import os
import sys
import time
import types

import numpy as np
import pytest

from benchmarks.manifest import Cell, HERE, benchmark_json

SECONDS = 1.5
OFF_CHIP = {"platform": "cpu", "kind": "cpu", "count": 1}
THREE_ORGS = ("rehearsal8.backlog", "rehearsal-8", "backlog")
FIVE_ORGS = ("rehearsal5org.backlog3of5", "rehearsal-5org", "backlog3of5")


@pytest.fixture(params=[THREE_ORGS, FIVE_ORGS], ids=["3org", "5org"])
def cell(request) -> Cell:
    return the_cell(request.param)


def the_cell(deployment=THREE_ORGS) -> Cell:
    name, config, traffic = deployment
    bench = benchmark_json()
    bench["workloads"] = [{
        "name": name, "config": config, "traffic": traffic, "chips": 1,
        "why": "a test's cell"}]
    return Cell(name, bench, root=os.path.join(HERE, "testdata"))


class Standin:
    """The software verifier behind the device verifier's seam, with a
    hook on the verdicts of each batch."""

    def __init__(self, alter=None):
        from fabric_mod_tpu.bccsp.sw import SwCSP
        from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
        self._inner = FakeBatchVerifier(SwCSP())
        self._alter = alter or (lambda items, mask: mask)

    def verify_many(self, items):
        mask = np.asarray(self._inner.verify_many(items), bool)
        return self._alter(items, mask.copy())

    def verify_many_async(self, items):
        out = self.verify_many(items)
        return lambda: out

    def verify_many_fused_async(self, items):
        return self.verify_many_async(items)


def drive(seed, alter=None, wrap_channel=None, traced=False, cell=None):
    from benchmarks.cellrun import run_cell
    return run_cell(
        cell or the_cell(), seed, SECONDS, traced, OFF_CHIP,
        lambda msg: None, time.perf_counter(),
        make_verifier=lambda: Standin(alter), wrap_channel=wrap_channel,
        # set-up's own check of the warm-up verdicts would stop a broken
        # verifier before the window; the fault is for `correct` to find
        strict_warm=alter is None)


def over_limit(result):
    return {k for k, v in result["compared"].items()
            if v["value"] > v["limit"]}


def test_sound_run_is_correct(cell):
    result = drive(2 ** 31 + 12345, cell=cell)
    assert result["correct"], over_limit(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"committed_tx_s", "setup_s"}
    assert list(result)[-1] == "compared"


def test_traced_run_reports_per_layer_metrics_and_no_trace_off_chip():
    result = drive(77, traced=True)
    # spans are read; the CPU has no device plane, so the trace's
    # metrics are left out and the run says the trace is missing
    assert {"unpack_ms_per_tx", "recv_ms_per_block",
            "commit_ms_per_block"} <= set(result["metrics"])
    assert "verify_roofline_pct" not in result["metrics"]
    assert over_limit(result) == {"trace_missing"}


def half_left_out(items, mask):
    """Half of the batch is never verified: taken as valid."""
    mask[len(mask) // 2:] = True
    return mask


def answer_altered(items, mask):
    """One verdict of each block-sized batch altered where produced."""
    if len(mask) > 1:
        mask[0] = not mask[0]
    return mask


@pytest.mark.parametrize("alter,number", [
    (half_left_out, "flag_diff"),
    (answer_altered, "flag_diff"),
])
def test_broken_verdicts_are_not_correct(alter, number, cell):
    result = drive(5, alter=alter, cell=cell)
    assert not result["correct"]
    assert number in over_limit(result)


def test_step_that_leaves_the_ledger_unchanged_is_not_correct(cell):
    def commit_nothing(channel):
        def commit_staged(staged):
            return list(staged.validator.finish(staged))
        channel.commit_staged = commit_staged
    result = drive(6, wrap_channel=commit_nothing, cell=cell)
    assert not result["correct"]
    assert "blocks_unread" in over_limit(result)


def all_lanes_false(items, mask):
    mask[:] = False
    return mask


def test_control_stand_in(cell):
    """What the precision control did on the chip (PERF.md): every
    lane came back False, the orderer's block signature with them, so
    the peer rejected the first block and no window opened."""
    result = drive(7, alter=all_lanes_false, cell=cell)
    assert not result["correct"]
    assert over_limit(result) & {"window_not_closed", "rejected_blocks",
                                 "flag_diff"}


def test_the_rule_is_the_configurations_own():
    """The five-org chain judged by three orgs' rule: a transaction
    whose second of three endorsements is corrupted has two counting
    orgs, VALID by 2-of-3 and a policy failure by 3-of-5."""
    cell = the_cell(FIVE_ORGS)
    cell.config = copy.deepcopy(cell.config)
    cell.config["settings"]["orgs"] = 3
    result = drive(2 ** 31 + 12345, cell=cell)
    assert not result["correct"]
    assert "flag_diff" in over_limit(result)


def misjudging(cell: Cell, misjudge):
    """The cell, with `misjudge(tx, code, writes) -> writes` between
    its rule and the comparison."""
    sound = cell.rule().Rule

    class Rule(sound):
        def judge(self, tx, block, index):
            code, writes = super().judge(tx, block, index)
            return code, misjudge(tx, code, writes)
    cell.rule = lambda: types.SimpleNamespace(Rule=Rule)
    return cell


@pytest.mark.parametrize("misjudge", [
    # a write the peer did not make
    lambda tx, code, writes: {**writes, (tx.ns, "never-" + tx.key): b"x"},
    # a key of the peer's that the rule did not expect
    lambda tx, code, writes: {},
], ids=["write_not_made", "key_not_expected"])
def test_state_that_differs_from_the_rules_is_not_correct(misjudge):
    result = drive(8, cell=misjudging(the_cell(FIVE_ORGS), misjudge))
    assert over_limit(result) == {"state_diff"}


@pytest.mark.parametrize("deployment", [
    FIVE_ORGS, ("rehearsalnof4.backlog-nof", "rehearsal-nof4", "backlog-nof")],
    ids=["5org", "nof4"])
def test_network_key_the_program_does_not_take_stops_the_run(deployment):
    """A key in the configuration's `network` that the program's
    `e2e.Network` does not take: stopped before anything is set up,
    key and configuration named."""
    from benchmarks.cellrun import RunFailure, run_cell
    cell = the_cell(deployment)
    cell.config = copy.deepcopy(cell.config)
    cell.config["network"]["no_such_setting"] = 1
    set_up = []
    with pytest.raises(RunFailure) as failure:
        run_cell(cell, 9, SECONDS, False, OFF_CHIP, lambda msg: None,
                 time.perf_counter(),
                 make_verifier=lambda: set_up.append("the verifier"))
    assert "'no_such_setting'" in str(failure.value)
    assert f"'{deployment[1]}'" in str(failure.value)
    assert not set_up


@pytest.mark.parametrize("items,programs", [
    (30, [64]), (1497, [2048]), (2100, [2048, 64]),
    (6000, [2048, 2048, 2048])])
def test_buckets_a_block_reaches_are_reckoned_as_the_program_does(
        items, programs):
    from benchmarks.cellrun import buckets_reached
    from fabric_mod_tpu.bccsp.tpu import BUCKETS
    assert buckets_reached(items, BUCKETS) == programs


def test_a_rule_imports_nothing_of_the_program():
    allowed = set(sys.stdlib_module_names) | {"cryptography"}
    for path in glob.glob(os.path.join(HERE, "references", "*.py")):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                # a relative import would be the benchmark's: refused too
                names = [node.module if node.level == 0 else "."]
            else:
                continue
            for name in names:
                assert name.split(".")[0] in allowed, (path, name)


def test_control_on_the_chip():
    import jax
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the precision control differs from the program "
                    "only on a TPU")
    import json
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "seeds.py"), "--workload",
         benchmark_json()["workloads"][0]["name"], "--seeds", "1,2,3",
         "--seconds", "6", "--control"],
        stdout=subprocess.PIPE, text=True).stdout
    last = json.loads(out.strip().splitlines()[-1])
    assert last["correct"] == 0 and last["not_correct"] == 3
