"""The deployment `fabric-sbe-asset-500`: its files are found by name
and agree with each other, the draw makes what the mix says, the rule
holds the in-block rule of Fabric's key-level validator, and a
rehearsal of the cell (two orgs, 120 assets, blocks of 40, the
software verifier standing in: `testdata/`) is `correct` under its
rule, with every injected code present, and comes out not `correct`
where the peer or the rule leaves out the in-block rule or where the
chaincode writes another document.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_sbe.py -q -p no:cacheprovider
"""
import ast
import collections
import multiprocessing
import os
import random
import types

import pytest

from benchmarks.manifest import Cell, HERE, benchmark_json, reducer_for
from benchmarks.test_correct import drive, over_limit, the_cell
from benchmarks.test_smallbank import with_provision_seen
from fabric_mod_tpu.peer.chaincode import SbeAssetContract

CELL = "sbeasset500.backlog-sbe"
CONFIG = "fabric-sbe-asset-500"
REHEARSAL = ("rehearsalsbe.backlog-sbe", "rehearsal-sbe", "backlog-sbe")
NEW_METRICS = ("barrier_wait_ms_per_block", "key_policy_ms_per_tx")
VALID, EPF, MVCC = 0, 10, 11


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
    for word in ("asset-transfer-sbe", "CreateAsset", "TransferAsset",
                 "key policy", "two orgs", "configtx.yaml", "500"):
        assert word in entry["source"], word
    assert entry["reduced"] == cell.config["reduced"] \
        == sorted(cell.config["reduced_why"]) == ["batch_timeout", "channels"]
    assert cell.generator().__name__.endswith("traffic.sbeassets")
    assert cell.rule().__name__.endswith("references.sbe_asset_transfer")
    assert cell.file["warm_buckets"] == [2048]
    assert (cell.file["trace_blocks"], cell.file["trace_after_blocks"]) \
        == (3, 5)
    assert cell.params["warm_blocks"] == 2
    assert cell.params["provision_tx_s"] % 250 == 0
    # the two new metrics are the cell's alone, and every accepted
    # metric that lists no cells is reported here too
    new = [p for p in bench["per_layer"] if p["name"] in NEW_METRICS]
    assert len(new) == 2
    for p in new:
        assert p["workloads"] == [CELL] and p["layer"] == "validate (host)"
        assert p["moves"] == "committed_tx_s"
    assert {p["name"] for p in cell.per_layer} == {
        p["name"] for p in bench["per_layer"] if "workloads" not in p} \
        | set(NEW_METRICS)
    for other in ("default500.backlog", "smallbank.backlog-zipf"):
        assert not set(NEW_METRICS) & {
            p["name"] for p in Cell(other, bench).per_layer}


def test_the_deployment_keeps_its_sources_shapes():
    """Fabric's block cutting, `smallbank-zipf`'s guarantees and one
    more, the test network's two orgs; what the cell's traffic draws is
    what the configuration states."""
    cell = Cell(CELL)
    config, params = cell.config, cell.params
    source, settings = config["source_settings"], config["settings"]
    assert {k for k, v in source.items() if settings[k] != v} \
        == {"batch_timeout"} <= set(config["reduced"])
    for key in ("max_message_count", "absolute_max_bytes",
                "preferred_max_bytes"):
        assert settings[key] == Cell("default500.backlog").config[
            "settings"][key], key
    assert config["network"] == {"orgs": ["Org1", "Org2"]}
    assert settings["orgs"] == 2 and settings["assets"] == params["assets"]
    assert config["guarantees"][:5] == Cell(
        "smallbank.backlog-zipf").config["guarantees"][:4] + [
            config["guarantees"][4]]
    assert "ENDORSEMENT_POLICY_FAILURE" in config["guarantees"][5]
    for key in ("assets", "mix", "values", "cli", "stale_blocks", "faults",
                "envelope_bytes"):
        assert key in config["assumed"], key
    assert "written_from" in config
    assert params["mix"] == {"create": 0.25, "update": 0.5, "transfer": 0.25}
    assert (params["assets"], params["stale_blocks"]) == (10000, 0)
    for fault in ("non_owner_update", "update_after_transfer",
                  "corrupt_signature"):
        assert params[fault + "_per"] == 150
    from fabric_mod_tpu.peer.chaincode import SbeAssetContract
    assert params["chaincode"] == "sbe"
    assert set(SbeAssetContract.ARITY) == {
        "CreateAsset", "UpdateAsset", "TransferAsset", "ReadAsset"}


def test_a_block_of_the_cell_reaches_the_bucket_it_warms():
    from benchmarks.cellrun import buckets_reached
    from fabric_mod_tpu.bccsp.tpu import BUCKETS
    # 500 creators; two endorsements a create, one an update or a
    # transfer: from all single-endorsed to all creates, with the block's
    for items in (1001, 1126, 1501):
        assert buckets_reached(items, BUCKETS) == [2048]


def _live(n=1000):
    live = [f"asset{i}" for i in range(n)]
    return live, {a: ("Org1", "Org2")[i % 2] for i, a in enumerate(live)}


def test_the_draw_is_the_seeds_and_the_mixs():
    """The same seed draws the same block; over many blocks the shares
    are the mix's, an update or a transfer is the owner's, endorsed by
    the owner's peer alone, a transfer goes to the other org, a create
    is endorsed by both."""
    from benchmarks.traffic import sbeassets
    params = Cell(CELL).params
    orgs = ["Org1", "Org2"]
    live, owner_of = _live()

    def draw(seed, n_blocks=40):
        rng = random.Random(seed)
        ops, next_id = [], len(live)
        for _ in range(n_blocks):
            block, next_id = sbeassets.draw_round(
                rng, params, 500, orgs, live, owner_of, {}, next_id)
            ops += block
        return ops
    ops = draw(2 ** 31 + 5)
    assert ops == draw(2 ** 31 + 5)
    assert ops[:50] != draw(6, 1)[:50]
    shares = collections.Counter(op.name for op in ops)
    assert 0.24 < shares["CreateAsset"] / len(ops) < 0.26
    assert 0.49 < shares["UpdateAsset"] / len(ops) < 0.51
    for op in ops:
        if op.name == "CreateAsset":
            assert op.endorsers == ("Org1", "Org2")
            assert op.asset not in owner_of
            assert op.args[0] == op.asset and 1 <= int(op.args[1]) <= 10 ** 6
        else:
            assert op.endorsers == (owner_of[op.asset],) == (op.client,)
        if op.name == "TransferAsset":
            assert op.new_org == op.args[2] != owner_of[op.asset]
    with pytest.raises(sbeassets.TrafficError):
        sbeassets.draw_round(random.Random(1),
                             dict(params, mix={"create": 1.0}), 500, orgs,
                             live, owner_of, {}, 0)


def test_the_faults_are_where_the_seed_puts_them():
    """1 in 150 of each kind; an update-after-transfer in the second
    half of its block, by the org an earlier sound transfer of the
    block moves the asset to; a non-owner's update by the other org."""
    from benchmarks.traffic import sbeassets
    params = Cell(CELL).params
    rng = random.Random(2 ** 31 + 9)
    places = sbeassets.fault_places(rng, params, 500, 30)
    assert collections.Counter(places.values()) == {
        f: 100 for f in sbeassets.FAULTS}
    assert all(p % 500 >= 250 for p, f in places.items()
               if f == "update_after_transfer")
    live, owner_of = _live()
    ops, _ = sbeassets.draw_round(
        rng, params, 500, ["Org1", "Org2"], live, owner_of,
        {10: "non_owner_update", 300: "update_after_transfer",
         301: "corrupt_signature"}, len(live))
    assert ops[10].name == "UpdateAsset"
    assert ops[10].endorsers == (ops[10].client,) != (
        owner_of[ops[10].asset],)
    moved = [op for op in ops[:300] if op.name == "TransferAsset"
             and op.asset == ops[300].asset and op.fault is None]
    assert moved and ops[300].endorsers == (moved[0].new_org,) \
        == (ops[300].client,)
    assert ops[301].fault == "corrupt_signature"


def test_rule_takes_its_imports_from_the_standard_library():
    path = os.path.join(HERE, "references", "sbe_asset_transfer.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    assert [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
            for a in n.names] == ["json"]
    assert not [n for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]


def test_rule_stops_where_the_configuration_and_the_mix_disagree():
    rule = Cell(CELL).rule()
    with pytest.raises(rule.RuleError):
        rule.Rule({"orgs": 2, "assets": 10000}, {"assets": 1000}, None)


def _fact(op, args, client, endorsers, reads=()):
    part = types.SimpleNamespace
    return types.SimpleNamespace(
        ns="sbe", op=op, args=args, reads=list(reads),
        creator=part(org=client, good=True),
        endorsements=[part(org=o, good=True) for o in endorsers])


def _rule():
    return Cell(CELL).rule().Rule({"orgs": 2, "assets": 1}, {"assets": 1},
                                  lambda part: part.good)


def test_rule_fails_the_new_owners_write_in_the_block_of_the_transfer():
    """Hand-built: the transfer passes, the new owner's update after it
    in the same block is ENDORSEMENT_POLICY_FAILURE (a peer on the old
    reading, the in-block policy judging it, would record VALID or a
    read conflict there: `flag_diff`), and in the next block the new
    owner's update is VALID."""
    import json
    rule = _rule()
    code, writes = rule.judge(
        _fact("CreateAsset", ("a1", "5", "Tom"), "Org1", ["Org1", "Org2"],
              [("a1", None)]), 1, 0)
    assert code == VALID and json.loads(writes[("sbe", "a1")]) == {
        "ID": "a1", "Value": 5, "Owner": "Tom", "OwnerOrg": "Org1"}
    assert rule.judge(_fact("UpdateAsset", ("a1", "6"), "Org2", ["Org2"],
                            [("a1", (1, 0))]), 2, 0)[0] == EPF
    assert rule.judge(_fact("TransferAsset", ("a1", "Max", "Org2"), "Org1",
                            ["Org1"], [("a1", (1, 0))]), 2, 1)[0] == VALID
    assert rule.judge(_fact("UpdateAsset", ("a1", "7"), "Org2", ["Org2"],
                            [("a1", (1, 0))]), 2, 2) == (EPF, {})
    code, writes = rule.judge(_fact("UpdateAsset", ("a1", "8"), "Org2",
                                    ["Org2"], [("a1", (2, 1))]), 3, 0)
    assert code == VALID and json.loads(writes[("sbe", "a1")]) == {
        "ID": "a1", "Value": 8, "Owner": "Max", "OwnerOrg": "Org2"}


def test_rule_fails_a_write_behind_a_transfer_that_passed_and_conflicts():
    """The in-block rule reads the validator's verdict, before MVCC: a
    transfer that passes its policy and then conflicts still fails the
    owner's write after it; a transfer that fails its policy changes
    nothing."""
    rule = _rule()
    rule.judge(_fact("CreateAsset", ("a1", "5", "Tom"), "Org1",
                     ["Org1", "Org2"], [("a1", None)]), 1, 0)
    assert rule.judge(_fact("UpdateAsset", ("a1", "6"), "Org1", ["Org1"],
                            [("a1", (1, 0))]), 2, 0)[0] == VALID
    assert rule.judge(_fact("TransferAsset", ("a1", "Max", "Org2"), "Org1",
                            ["Org1"], [("a1", (1, 0))]), 2, 1)[0] == MVCC
    assert rule.judge(_fact("UpdateAsset", ("a1", "7"), "Org1", ["Org1"],
                            [("a1", (1, 0))]), 2, 2)[0] == EPF
    # a transfer endorsed by the wrong org: the owner's write stands
    assert rule.judge(_fact("TransferAsset", ("a1", "Max", "Org2"), "Org2",
                            ["Org2"], [("a1", (2, 0))]), 3, 0)[0] == EPF
    assert rule.judge(_fact("UpdateAsset", ("a1", "9"), "Org1", ["Org1"],
                            [("a1", (2, 0))]), 3, 1)[0] == VALID


def test_new_metrics_read_nothing_where_the_program_has_no_such_span():
    """A parent commit has neither span: the reducer returns None and
    the result line leaves the metric out."""
    from benchmarks.cellrun import Window
    window = Window(seconds=1.0, blocks=2, txs=16,
                    span_secs={"unpack": 0.5}, span_counts={"unpack": 2},
                    dispatches=[])
    for name in NEW_METRICS:
        spec, reduce_fn = reducer_for(name)
        assert spec["reducer"] == "span_total"
        assert reduce_fn(spec, window) is None
    spec, reduce_fn = reducer_for("barrier_wait_ms_per_block")
    assert spec["spans"] == ["stage_barrier_wait"]
    spec, reduce_fn = reducer_for("key_policy_ms_per_tx")
    assert spec["spans"] == ["key_policy_stage", "key_policy_resolve"]
    window = Window(seconds=1.0, blocks=2, txs=16,
                    span_secs={"key_policy_stage": 0.004,
                               "key_policy_resolve": 0.004},
                    span_counts={"key_policy_stage": 2,
                                 "key_policy_resolve": 9}, dispatches=[])
    assert reduce_fn(spec, window) == pytest.approx(0.5)


# --- the rehearsal -----------------------------------------------------------

def _rule_codes(cell, backlog):
    from benchmarks.reference import Signatures
    rule = cell.rule().Rule(cell.config["settings"], cell.params,
                            Signatures().counts)
    return [rule.judge(t, 1 + i // backlog.block_txs,
                       i % backlog.block_txs)[0]
            for i, t in enumerate(backlog.txs)]


def test_rehearsal_is_correct_and_every_injected_code_is_there():
    said = []
    cell = with_provision_seen(the_cell(REHEARSAL), said.append)
    result = drive(2 ** 31 + 4321, cell=cell)
    assert result["correct"], over_limit(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"committed_tx_s", "setup_s"}
    backlog = said[0]
    assert backlog.warm_blocks == backlog.load_blocks + 2 == 5
    # the generator's model (held to the software peer's flags block by
    # block) counts what the rule counts
    codes = collections.Counter(_rule_codes(the_cell(REHEARSAL), backlog))
    assert dict(codes) == {c: n for c, n in backlog.expected_codes.items()
                           if n}
    assert backlog.expected_codes[MVCC] > 0
    for fault, by_code in backlog.fault_codes.items():
        assert by_code[EPF] > 0 and by_code[VALID] == 0, fault
    # the endorsing processes are gone with the set-up
    assert multiprocessing.active_children() == []


def test_endorsing_processes_leave_the_run_cores_and_a_share_of_20():
    from benchmarks.traffic.sbeassets import endorsing_processes
    cores = len(os.sched_getaffinity(0))
    assert endorsing_processes(500) == max(1, min(8, cores - 2))
    assert endorsing_processes(40) == max(1, min(2, cores - 2))
    assert endorsing_processes(10) == 1


def test_rehearsal_traced_reads_the_two_new_metrics():
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
    # the barrier's wait nests in the slot's, which
    # `stage_blocked_ms_per_block` reads
    assert result["metrics"]["barrier_wait_ms_per_block"]["value"] <= \
        result["metrics"]["stage_blocked_ms_per_block"]["value"]


def test_rehearsal_with_no_in_block_rule_in_the_peer_is_not_correct():
    """The peer under test judges a write behind a valid transfer of its
    block by the committed policy: the old owner's write there passes
    and meets MVCC where the rule says ENDORSEMENT_POLICY_FAILURE."""
    def no_in_block_rule(channel):
        validator = channel.validator()
        sound = validator._finish_keys

        def finish_keys(action, mask, flags, tally):
            for ke in action.key_evals:
                ke.inblock = []
            return sound(action, mask, flags, tally)
        validator._finish_keys = finish_keys
    result = drive(2 ** 31 + 4321, wrap_channel=no_in_block_rule,
                   cell=the_cell(REHEARSAL))
    assert not result["correct"]
    assert "flag_diff" in over_limit(result)


def test_rehearsal_under_a_rule_without_the_in_block_rule_is_not_correct():
    """The control: a rule that forgets the block's policy changes no
    longer agrees with a sound peer."""
    cell = the_cell(REHEARSAL)
    module = cell.rule()

    class Forgetful(module.Rule):
        def policy_holds(self, asset_id, orgs):
            self.changed = set()
            return super().policy_holds(asset_id, orgs)
    cell.rule = lambda: types.SimpleNamespace(Rule=Forgetful)
    result = drive(2 ** 31 + 4321, cell=cell)
    assert not result["correct"]
    assert "flag_diff" in over_limit(result)


class KeepsTheOldOwner(SbeAssetContract):
    """TransferAsset moves the key's policy but writes the document with
    the old OwnerOrg.  At module level: the generator's endorsing
    processes import the contract class by its name."""

    def _op_TransferAsset(self, stub, asset_id, new_owner, new_owner_org):
        asset = self._asset(stub, asset_id)
        asset["Owner"] = new_owner
        self._put(stub, asset)
        stub.set_state_metadata(asset_id, "VALIDATION_PARAMETER",
                                self.owner_policy(new_owner_org))
        return b"ok"


def test_rehearsal_with_a_chaincode_that_keeps_the_old_owner_is_not_correct(
        monkeypatch):
    """The network's registry holds `KeepsTheOldOwner`: every flag is as
    the rule's, the documents are not."""
    from fabric_mod_tpu.peer import chaincode
    monkeypatch.setattr(chaincode, "SbeAssetContract", KeepsTheOldOwner)
    result = drive(43, cell=the_cell(REHEARSAL))
    assert not result["correct"]
    assert over_limit(result) == {"state_diff"}
