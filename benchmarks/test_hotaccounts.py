"""The deployment `fabricpp-hot-1024`: its files are found by name and
agree with each other, the mix draws what the configuration states,
and a rehearsal of the cell (200 accounts of which 20 are hot, blocks
of 8, the software verifier standing in: `testdata/`) is `correct`
under its rule and comes out not `correct` under each fault the new
mechanism can have: a ledger that checks no read, a chaincode that
writes another value, a fact whose recorded version is not the one
that was read.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_hotaccounts.py -q -p no:cacheprovider
"""
import ast
import os
import random
import types

import pytest

from benchmarks.manifest import Cell, HERE, benchmark_json, reducer_for
from benchmarks.test_correct import drive, over_limit, the_cell
from benchmarks.test_smallbank import with_provision_seen

CELL = "fabricpp1024.backlog-hot"
CONFIG = "fabricpp-hot-1024"
REHEARSAL = ("rehearsalhot.backlog-hot", "rehearsal-hotaccounts",
             "backlog-hot")
NEW_METRIC = "idle_under_chunk_pct"


def test_manifest_finds_the_cell_and_what_it_names():
    bench = benchmark_json()
    cell = Cell(CELL, bench)
    assert cell.chips == 1
    assert cell.entry["why"] == cell.file["why"]
    assert len(cell.entry["why"]) <= 200
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200
    for word in ("1810.13177", "custom workload", "BS=1024", "10,000",
                 "RW=8", "HR=40%", "HW=10%", "HSS=1%"):
        assert word in entry["source"], word
    assert entry["reduced"] == cell.config["reduced"] \
        == sorted(cell.config["reduced_why"]) == ["batch_timeout"]
    assert cell.generator().__name__.endswith("traffic.hotaccounts")
    assert cell.rule().__name__.endswith("references.hot_accounts_mvcc")
    assert cell.file["warm_buckets"] == [2048]
    assert (cell.params["warm_blocks"], cell.params["provision_tx_s"]) \
        == (2, 1000)
    # the new metric is the cell's alone, and every accepted metric
    # that lists no cells is reported here too
    (new,) = [p for p in bench["per_layer"] if p["name"] == NEW_METRIC]
    assert new["workloads"] == [CELL] and new["layer"] == "verify provider"
    assert new["moves"] == "committed_tx_s"
    assert {p["name"] for p in cell.per_layer} == {
        p["name"] for p in bench["per_layer"] if "workloads" not in p} \
        | {NEW_METRIC}
    for other in ("default500.backlog", "smallbank.backlog-zipf"):
        assert NEW_METRIC not in {
            p["name"] for p in Cell(other, bench).per_layer}


def test_the_deployment_keeps_its_sources_shapes():
    cell = Cell(CELL)
    config, params = cell.config, cell.params
    source, settings = config["source_settings"], config["settings"]
    assert source == {"max_message_count": 1024, "accounts": 10000, "rw": 8,
                      "hot_read": 0.4, "hot_write": 0.1, "hot_set": 0.01,
                      "channels": 1}
    # no shape of the source is changed
    for key, value in source.items():
        assert settings[key] == value, key
    for key in ("accounts", "rw", "hot_read", "hot_write", "hot_set"):
        assert params[key] == settings[key], key
    assert settings["absolute_max_bytes"] == settings["preferred_max_bytes"] \
        == 10 * 1024 * 1024
    assert config["network"] == {
        "preferred_max_bytes": settings["preferred_max_bytes"]}
    assert config["guarantees"] == Cell("smallbank.backlog-zipf").config[
        "guarantees"]
    for key in ("rw", "sets", "value_written", "initial_balance",
                "byte_limits", "orgs", "stale_blocks", "load", "recalled"):
        assert key in config["assumed"], key
    assert "written_from" in config
    assert (params["stale_blocks"], params["endorsements_per_tx"]) == (0, 2)
    assert params["single_endorsed_per"] == 150 \
        == params["corrupt_signature_per"]
    from fabric_mod_tpu.peer.chaincode import HotAccountsContract
    assert HotAccountsContract.RW == settings["rw"]
    assert params["chaincode"] == "accounts"


def test_a_block_of_the_cell_is_two_calls_of_the_bucket_it_warms():
    from benchmarks.cellrun import buckets_reached
    from fabric_mod_tpu.bccsp.tpu import BUCKETS
    # 1,024 creators and two endorsements each, less the single-endorsed
    # (about 7 a block; none, or three times as many)
    for items in (3072, 3065, 3050):
        assert buckets_reached(items, BUCKETS) == [2048, 2048]


def test_the_draw_is_the_seeds_and_as_hot_as_the_mix_says():
    """The same seed draws the same operations; over 10,000 draws every
    set has 8 distinct accounts, 40 reads in 100 and 10 writes in 100
    fall among the 100 hot accounts, and the seed decides which those
    are."""
    from benchmarks.traffic import hotaccounts
    params = Cell(CELL).params
    seed = 2 ** 31 + 5
    ops = hotaccounts.draw_operations(random.Random(seed), params, 10000)
    assert ops == hotaccounts.draw_operations(
        random.Random(seed), params, 10000)
    assert ops[:50] != hotaccounts.draw_operations(
        random.Random(6), params, 50)
    hot, cold = hotaccounts.hot_and_cold(random.Random(seed), params)
    assert (len(hot), len(cold)) == (100, 9900)
    assert set(hot) != set(
        hotaccounts.hot_and_cold(random.Random(6), params)[0])
    assert sorted(hot + cold) == list(range(10000))
    hot = set(hot)
    reads = writes = 0
    for op, args in ops:
        assert op == "move" and len(args) == 17
        assert len(set(args[:8])) == 8 == len(set(args[8:16]))
        assert 1 <= args[16] <= params["amount_max"]
        reads += sum(a in hot for a in args[:8])
        writes += sum(a in hot for a in args[8:16])
    assert 0.385 < reads / 80000 < 0.405        # 0.4 less the redraws
    assert 0.094 < writes / 80000 < 0.106
    with pytest.raises(hotaccounts.TrafficError):
        hotaccounts.draw_operations(random.Random(1),
                                    dict(params, hot_set=0.0005), 10)


def test_rule_takes_its_imports_from_the_standard_library():
    """Nothing of the program and nothing of the benchmark: this rule
    imports nothing at all (the signature test is handed in)."""
    path = os.path.join(HERE, "references", "hot_accounts_mvcc.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    assert not [n for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))]


def test_rule_stops_where_the_configuration_and_the_mix_disagree():
    rule = Cell(CELL).rule()
    with pytest.raises(rule.RuleError):
        rule.Rule({"orgs": 3, "accounts": 10000, "rw": 8},
                  {"accounts": 1000, "rw": 8}, None)
    with pytest.raises(rule.RuleError):
        rule.Rule({"orgs": 3, "accounts": 10000, "rw": 8},
                  {"accounts": 10000, "rw": 4}, None)


def test_new_metric_reads_nothing_where_there_is_no_trace():
    """Off the chip, and on a program from before the span: None, and
    the result line leaves the metric out."""
    from benchmarks.cellrun import Window
    spec, reduce_fn = reducer_for(NEW_METRIC)
    assert spec["reducer"] == "idle_under"
    assert spec["spans"][0] == "dispatch_chunk"
    assert spec["wait_spans"] == reducer_for(
        "idle_under_enqueue_pct")[0]["wait_spans"]
    window = Window(seconds=1.0, blocks=2, txs=16, span_secs={},
                    span_counts={}, dispatches=[])
    assert reduce_fn(spec, window) is None


# --- the rehearsal -----------------------------------------------------------

def test_rehearsal_is_correct_and_most_of_it_conflicts():
    said = []
    result = drive(2 ** 31 + 4321, cell=with_provision_seen(
        the_cell(REHEARSAL), said.append))
    assert result["correct"], over_limit(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"committed_tx_s", "setup_s"}
    backlog = said[0]
    assert backlog.warm_blocks == backlog.load_blocks + 2 == 5
    assert backlog.expected_codes[11] > 0 and backlog.expected_codes[10] > 0


def test_rehearsal_with_no_read_check_is_not_correct():
    """A ledger that takes every read for fresh: every transaction the
    policy passed is VALID and writes."""
    def skip_the_read_check(channel):
        from fabric_mod_tpu.ledger import mvcc
        ledger = channel.ledger
        commit = ledger.commit_block

        def commit_block(block, incoming_flags=None, rwsets=None):
            kept = mvcc.validate_kv_read
            mvcc.validate_kv_read = lambda db, batch, ns, read: True
            try:
                return commit(block, incoming_flags, rwsets=rwsets)
            finally:
                mvcc.validate_kv_read = kept
        ledger.commit_block = commit_block
    result = drive(41, wrap_channel=skip_the_read_check,
                   cell=the_cell(REHEARSAL))
    assert not result["correct"]
    assert {"flag_diff", "state_diff"} <= over_limit(result)


def test_rehearsal_with_places_counted_from_0_is_not_correct(monkeypatch):
    """The chaincode counts the writes' places from 0: every flag is as
    the rule's, the balances are not."""
    from fabric_mod_tpu.peer.chaincode import HotAccountsContract
    honest = HotAccountsContract._op_move
    monkeypatch.setattr(
        HotAccountsContract, "_op_move",
        lambda self, stub, *nums: honest(self, stub, *nums[:-1],
                                         nums[-1] - 1))
    result = drive(43, cell=the_cell(REHEARSAL))
    assert not result["correct"]
    assert over_limit(result) == {"state_diff"}


def altered_rule(cell, fault: str):
    """`cell` with one fault planted in its RULE: `stale_read` lets the
    first stale read it meets pass (one wrong version check),
    `wrong_write` recomputes the eighth of a transaction's writes one
    unit too high (one wrong recomputed write of the eight).  The
    chip's control (PERF.md) plants the same."""
    module = cell.rule()

    class Planted(module.Rule):
        passed_one = False

        def judge(self, tx, block, index):
            if fault == "stale_read" and not self.passed_one and any(
                    self.held[key][1] != version for key, version in tx.reads):
                self.passed_one = True
                tx = type(tx)(**{**vars(tx), "reads": [
                    (key, self.held[key][1]) for key, _ in tx.reads]})
            return super().judge(tx, block, index)

        def writes_of(self, op, args):
            writes = super().writes_of(op, args)
            if fault == "wrong_write" and op == "move":
                writes[list(writes)[-1]] += 1
            return writes

    cell.rule = lambda: types.SimpleNamespace(Rule=Planted)
    return cell


@pytest.mark.parametrize("fault,number", [
    ("stale_read", "flag_diff"), ("wrong_write", "state_diff")])
def test_rehearsal_under_a_rule_with_a_planted_fault_is_not_correct(
        fault, number):
    """The control: a rule given one wrong version check, or one wrong
    recomputed write, no longer agrees with a sound peer."""
    result = drive(47, cell=altered_rule(the_cell(REHEARSAL), fault))
    assert not result["correct"]
    assert number in over_limit(result)


def test_rehearsal_with_an_altered_version_in_a_fact_is_not_correct():
    """One recorded version moved by one: the rule calls that read
    stale, the peer (whose envelope has the version that was read)
    does not."""
    cell = the_cell(REHEARSAL)

    def alter_one_version(backlog):
        # the last transaction the rule calls VALID
        from benchmarks.reference import Signatures
        rule = cell.rule().Rule(cell.config["settings"], cell.params,
                                Signatures().counts)
        codes = [rule.judge(t, 1 + i // backlog.block_txs,
                            i % backlog.block_txs)[0]
                 for i, t in enumerate(backlog.txs)]
        tx = [t for t, code in zip(backlog.txs, codes)
              if code == 0 and t.reads][-1]
        key, (block, index) = tx.reads[0]
        tx.reads[0] = (key, (block, index + 1))
    with_provision_seen(cell, alter_one_version)
    result = drive(53, cell=cell)
    assert not result["correct"]
    assert "flag_diff" in over_limit(result)
