"""The deployment `smallbank-zipf`: its files are found by name and
agree with each other, and a rehearsal of the cell (200 accounts,
blocks of 8, the software verifier standing in: `testdata/`) is
`correct` under its rule and comes out not `correct` under each fault
the new mechanism can have: a ledger that checks no read, a chaincode
that computes another balance, a fact whose recorded version is not
the one that was read.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_smallbank.py -q -p no:cacheprovider
"""
import ast
import os
import types

import pytest

from benchmarks.manifest import Cell, HERE, benchmark_json, reducer_for
from benchmarks.test_correct import drive, over_limit, the_cell

CELL = "smallbank.backlog-zipf"
REHEARSAL = ("rehearsalbank.backlog-zipf", "rehearsal-smallbank",
             "backlog-zipf")
NEW_METRICS = ("rwset_extract_ms_per_tx", "mvcc_validate_ms_per_tx")


def test_manifest_finds_the_cell_and_what_it_names():
    bench = benchmark_json()
    cell = Cell(CELL, bench)
    assert cell.chips == 1
    assert cell.entry["why"] == cell.file["why"]
    assert len(cell.entry["why"]) <= 200
    (entry,) = [c for c in bench["configs"] if c["name"] == "smallbank-zipf"]
    assert entry["file"] == "benchmarks/configs/smallbank-zipf.json"
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200
    for word in ("Smallbank", "Blockbench", "smallbank.go", "1810.13177",
                 "100,000", "Pw 0.95", "s 1.0", "configtx.yaml"):
        assert word in entry["source"], word
    assert entry["reduced"] == cell.config["reduced"] \
        == sorted(cell.config["reduced_why"])
    assert cell.generator().__name__.endswith("traffic.smallbank")
    assert cell.rule().__name__.endswith("references.smallbank_mvcc")
    assert cell.file["warm_buckets"] == [2048]
    assert cell.params["warm_blocks"] == 2
    assert cell.params["provision_tx_s"] in (1000, 750, 500)
    # the two new metrics are the cell's alone, and every accepted
    # metric (none lists its cells) is reported here too
    new = [p for p in bench["per_layer"] if p["name"] in NEW_METRICS]
    assert len(new) == 2
    for p in new:
        assert p["workloads"] == [CELL] and p["layer"] == "commit"
        assert p["moves"] == "committed_tx_s"
    assert {p["name"] for p in cell.per_layer} >= {
        p["name"] for p in bench["per_layer"] if "workloads" not in p} \
        | set(NEW_METRICS)
    other = Cell("default500.backlog", bench)
    assert not set(NEW_METRICS) & {p["name"] for p in other.per_layer}


def test_the_deployment_keeps_its_sources_shapes():
    """The channel is `fabric-default-500`'s, setting for setting; the
    workload's numbers are the source's and the mix draws what the
    configuration states."""
    config = Cell(CELL).config
    default = Cell("default500.backlog").config
    assert "network" not in config and "network" not in default
    source, settings = config["source_settings"], config["settings"]
    for key, value in default["settings"].items():
        assert settings[key] == value, key
    assert set(settings) - set(default["settings"]) == {
        "chaincode", "accounts", "zipf_s", "p_write"}
    assert {k for k, v in source.items() if settings[k] != v} \
        == {"batch_timeout"} <= set(config["reduced"])
    assert settings["accounts"] == 100000
    assert config["guarantees"][:4] == default["guarantees"]
    assert "MVCC_READ_CONFLICT" in config["guarantees"][4]
    for key in ("balances_and_amounts", "mix", "keys", "stale_blocks",
                "orgs"):
        assert key in config["assumed"]
    params = Cell(CELL).params
    for key in ("accounts", "zipf_s", "p_write"):
        assert params[key] == settings[key], key
    assert params["mix"] == {
        "transact_savings": 0.19, "deposit_checking": 0.19,
        "send_payment": 0.19, "write_check": 0.19, "amalgamate": 0.19,
        "balance": 0.05}
    assert (params["initial_balance"], params["amount_max"]) == (10 ** 6, 100)
    assert (params["stale_blocks"], params["endorsements_per_tx"]) == (0, 2)
    assert params["single_endorsed_per"] == 150 \
        == params["corrupt_signature_per"]


def test_the_block_of_the_cell_reaches_the_bucket_it_warms():
    from benchmarks.cellrun import buckets_reached
    from fabric_mod_tpu.bccsp.tpu import BUCKETS
    # 500 creators and two endorsements each, less the single-endorsed
    for items in (1500, 1490):
        assert buckets_reached(items, BUCKETS) == [2048]


def test_the_draw_is_the_seeds_and_as_skewed_as_the_mix_says():
    """The same seed draws the same operations; over 100,000 accounts
    at s = 1.0 the hottest account takes 1 / H(100,000) = 8.3% of the
    picks, and 95 operations in 100 write."""
    import random
    from benchmarks.traffic import smallbank
    params = Cell(CELL).params
    ops = smallbank.draw_operations(random.Random(2 ** 31 + 5), params, 20000)
    assert ops == smallbank.draw_operations(
        random.Random(2 ** 31 + 5), params, 20000)
    assert ops != smallbank.draw_operations(random.Random(6), params, 20000)
    picks = [a for op, args in ops
             for a in args[:2 if op in ("send_payment", "amalgamate") else 1]]
    hottest = max(set(picks), key=picks.count)
    assert 0.075 < picks.count(hottest) / len(picks) < 0.091
    assert 0.94 < sum(op != "balance" for op, _ in ops) / len(ops) < 0.96
    assert all(op not in ("send_payment", "amalgamate")
               or args[0] != args[1] for op, args in ops)
    with pytest.raises(smallbank.TrafficError):
        smallbank.draw_operations(random.Random(1),
                                  dict(params, p_write=0.5), 10)


def test_rule_takes_its_imports_from_the_standard_library():
    """Nothing of the program and nothing of the benchmark: this rule
    needs not even `cryptography` (the signature test is handed in)."""
    path = os.path.join(HERE, "references", "smallbank_mvcc.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    assert not [n for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))]


def test_rule_stops_where_the_configuration_and_the_mix_disagree():
    rule = Cell(CELL).rule()
    with pytest.raises(rule.RuleError):
        rule.Rule({"orgs": 3, "accounts": 100000}, {"accounts": 1000}, None)


def test_new_metrics_read_nothing_where_the_program_has_no_such_span():
    """A parent commit has neither span: the reducer returns None and
    the result line leaves the metric out."""
    from benchmarks.cellrun import Window
    window = Window(seconds=1.0, blocks=2, txs=16,
                    span_secs={"mvcc": 0.5}, span_counts={"mvcc": 2},
                    dispatches=[])
    for name in NEW_METRICS:
        spec, reduce_fn = reducer_for(name)
        assert reduce_fn(spec, window) is None
        window_with = Window(
            seconds=1.0, blocks=2, txs=16,
            span_secs={spec["spans"][0]: 0.008},
            span_counts={spec["spans"][0]: 2}, dispatches=[])
        assert reduce_fn(spec, window_with) == pytest.approx(0.5)


# --- the rehearsal -----------------------------------------------------------

def with_provision_seen(cell, see):
    """`cell` with `see(backlog)` called on what its generator returns,
    before the run goes on with it."""
    provision = cell.generator().provision

    def seen(*args):
        backlog = provision(*args)
        see(backlog)
        return backlog
    cell.generator = lambda: types.SimpleNamespace(provision=seen)
    return cell


def counter(name: str) -> float:
    from benchmarks.cellrun import metric_value
    return metric_value(name, absent=0.0)


def test_rehearsal_is_correct_and_the_counters_hold_the_rules_count():
    conflicts = 'fabric_ledger_mvcc_invalid_total{code="MVCC_READ_CONFLICT"}'
    before = counter(conflicts), counter("fabric_ledger_mvcc_reads_total")
    said = []
    result = drive(2 ** 31 + 4321, cell=with_provision_seen(
        the_cell(REHEARSAL), said.append))
    assert result["correct"], over_limit(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"committed_tx_s", "setup_s"}
    backlog = said[0]
    assert backlog.warm_blocks == backlog.load_blocks + 2 == 5
    assert backlog.expected_codes[11] > 0 and backlog.expected_codes[10] > 0
    # both peers of the process, the software peer and the peer under
    # test, committed the whole chain and counted it
    assert counter(conflicts) - before[0] == 2 * backlog.expected_codes[11]
    reads = sum(len(t.reads) for t in backlog.txs)
    assert counter("fabric_ledger_mvcc_reads_total") - before[1] < 2 * reads
    assert counter("fabric_ledger_mvcc_reads_total") - before[1] > reads


def test_rehearsal_traced_reads_the_two_new_metrics():
    # the rehearsal in the place of the cell the two metrics list
    bench = benchmark_json()
    bench["workloads"] = [{
        "name": REHEARSAL[0], "config": REHEARSAL[1],
        "traffic": REHEARSAL[2], "chips": 1, "why": "a test's cell"}]
    for p in bench["per_layer"]:
        if p["name"] in NEW_METRICS:
            p["workloads"] = [REHEARSAL[0]]
    cell = Cell(REHEARSAL[0], bench, root=os.path.join(HERE, "testdata"))
    result = drive(31, traced=True, cell=cell)
    assert over_limit(result) == {"trace_missing"}     # no device here
    for name in NEW_METRICS:
        assert result["metrics"][name]["value"] > 0
        assert result["metrics"][name]["unit"] == "ms/tx"
    # the two are nested in `mvcc`, which `commit_ms_per_block` reads
    both = sum(result["metrics"][n]["value"] for n in NEW_METRICS)
    per_block = result["metrics"]["commit_ms_per_block"]["value"]
    assert both * 8 < per_block


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


def test_rehearsal_with_a_generous_chaincode_is_not_correct(monkeypatch):
    """Every deposit credits one unit too many: every flag is as the
    rule's, the balances are not."""
    from fabric_mod_tpu.peer.chaincode import SmallbankContract
    honest = SmallbankContract._op_deposit_checking
    monkeypatch.setattr(
        SmallbankContract, "_op_deposit_checking",
        lambda self, stub, a, v: honest(self, stub, a, v + 1))
    result = drive(43, cell=the_cell(REHEARSAL))
    assert not result["correct"]
    assert over_limit(result) == {"state_diff"}


def test_rehearsal_with_an_altered_version_in_a_fact_is_not_correct():
    """One recorded version moved by one: the rule calls that read
    stale, the peer (whose envelope has the version that was read)
    does not."""
    cell = the_cell(REHEARSAL)

    def alter_one_version(backlog):
        # the last transaction the rule calls VALID and that read
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
    result = drive(47, cell=cell)
    assert not result["correct"]
    assert "flag_diff" in over_limit(result)
