"""The deployment `thakkar-nof-4`: its files are found by name, its
rule judges as a flat 3-of-4 policy does, and a rehearsal of the cell
(four orgs, `OutOf(3, ...)`, blocks of 8, the software verifier
standing in: `testdata/`) is `correct` under its own rule and under no
other.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/test_nof.py -q -p no:cacheprovider
"""
import copy
import importlib

import pytest

from benchmarks.manifest import Cell, benchmark_json, reducer_for
from benchmarks.reference import SignedPart, TxFacts
from benchmarks.test_correct import drive, over_limit, the_cell

CELL = "thakkar4.backlog-nof"
REHEARSAL = ("rehearsalnof4.backlog-nof", "rehearsal-nof4", "backlog-nof")
ORGS = [f"Org{i}" for i in range(1, 5)]


def test_manifest_finds_the_cell_and_what_it_names():
    bench = benchmark_json()
    cell = Cell(CELL, bench)
    assert cell.chips == 1
    assert cell.entry["why"] == cell.file["why"]
    (entry,) = [c for c in bench["configs"] if c["name"] == "thakkar-nof-4"]
    assert entry["file"] == "benchmarks/configs/thakkar-nof-4.json"
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and "3-OutOf-4" in entry["source"]
    settings, network = cell.config["settings"], cell.config["network"]
    assert network["orgs"] == ORGS and settings["orgs"] == 4
    assert settings["policy_n"] == 3
    assert network["endorsement_policy"] == settings["endorsement_policy"]
    assert network["endorsement_policy"].startswith("OutOf(3, 'Org1.peer'")
    # the guarantees are those of the accepted 500-tx deployment
    default = Cell("default500.backlog", bench).config
    assert cell.config["guarantees"] == default["guarantees"]
    assert cell.params == {
        "chaincode": "mycc", "value_bytes": 32, "endorsements_per_tx": 3,
        "single_endorsed_per": 150, "corrupt_signature_per": 150,
        "provision_tx_s": cell.params["provision_tx_s"], "warm_blocks": 2}
    assert cell.params["provision_tx_s"] >= 400
    assert cell.file["warm_buckets"] == [2048, 8]
    assert cell.generator().__name__.endswith("traffic.backlog")
    assert cell.rule().__name__.endswith("references.n_out_of_own_key")
    assert "verify_calls_per_block" in {p["name"] for p in cell.per_layer}


def test_what_the_deployment_changes_from_its_source_is_a_listed_cut():
    """Network, policy and byte limits are the source's: every key of
    `source_settings` that `settings` changes is a cut of scale that
    `reduced` names, and the byte limits, which the program is handed
    by their defaults, are Fabric's."""
    config = Cell(CELL).config
    source, settings = config["source_settings"], config["settings"]
    changed = {k for k, v in source.items() if settings[k] != v}
    assert changed == {"batch_timeout", "peers_per_org"}
    assert changed <= set(config["reduced"])
    for key in ("orgs", "policy_n", "endorsement_policy",
                "max_message_count", "absolute_max_bytes",
                "preferred_max_bytes"):
        assert source[key] == settings[key]
    assert settings["absolute_max_bytes"] == 10 * 1024 * 1024
    assert settings["preferred_max_bytes"] == 2 * 1024 * 1024
    assert not {"absolute_max_bytes", "preferred_max_bytes"} \
        & set(config["network"])


def test_the_block_of_the_cell_reaches_the_buckets_it_warms():
    from benchmarks.cellrun import buckets_reached
    from fabric_mod_tpu.bccsp.tpu import BUCKETS
    cell = Cell(CELL)
    per_tx = 1 + cell.params["endorsements_per_tx"]
    # all 500 fully endorsed, and ten of them single-endorsed
    for items in (500 * per_tx, 500 * per_tx - 10 * (per_tx - 2)):
        assert buckets_reached(items, BUCKETS) == [2048]
    assert set(cell.file["warm_buckets"]) == {2048, 8}


def test_the_blocks_of_the_cell_close_by_count_under_fabrics_limits():
    """Five hundred of the mix's envelopes stay under Fabric's 2 MB
    PreferredMaxBytes, so the count rule closes every block: made by
    the generator on the cell's own network, twelve of them."""
    import tempfile
    from fabric_mod_tpu import e2e
    from benchmarks.cellrun import network_arguments
    cell = Cell(CELL)
    args = network_arguments(cell, e2e.Network)
    args["max_message_count"] = 4
    params = dict(cell.params, provision_tx_s=2, warm_blocks=1)
    with tempfile.TemporaryDirectory() as root:
        net = e2e.Network(root, **args)
        try:
            backlog = cell.generator().provision(
                net, params, 7, 1.0, lambda msg: None)
            limit = net.support.cutter.config.preferred_max_bytes
        finally:
            net.close()
    assert limit == cell.config["settings"]["preferred_max_bytes"]
    widest = max(len(t.env_bytes) for t in backlog.txs)
    assert 3000 < widest and 501 * widest < limit


def test_network_arguments_are_taken_by_the_program():
    from benchmarks.cellrun import network_arguments
    from fabric_mod_tpu import e2e
    args = network_arguments(Cell(CELL), e2e.Network)
    assert args == {
        "max_message_count": 500, "batch_timeout": "10s", "orgs": ORGS,
        "endorsement_policy": Cell(CELL).config["settings"][
            "endorsement_policy"]}


# --- the rule ----------------------------------------------------------------

@pytest.fixture(scope="module")
def signers():
    """A peer certificate and key for each of the four orgs and for
    an Org5 outside the channel, and a client certificate of Org3,
    made with `cryptography` alone."""
    import datetime
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.x509.oid import NameOID

    def make(org, ou):
        key = ec.generate_private_key(ec.SECP256R1())
        name = x509.Name([
            x509.NameAttribute(NameOID.ORGANIZATION_NAME, org),
            x509.NameAttribute(NameOID.ORGANIZATIONAL_UNIT_NAME, ou),
            x509.NameAttribute(NameOID.COMMON_NAME, f"{ou}0.{org}")])
        now = datetime.datetime.now(datetime.timezone.utc)
        cert = (x509.CertificateBuilder().subject_name(name)
                .issuer_name(name).public_key(key.public_key())
                .serial_number(x509.random_serial_number())
                .not_valid_before(now - datetime.timedelta(days=1))
                .not_valid_after(now + datetime.timedelta(days=1))
                .sign(key, hashes.SHA256()))
        return key, cert.public_bytes(serialization.Encoding.PEM)

    out = {org: make(org, "peer") for org in ORGS}
    out["client"] = make("Org3", "client")
    out["Org5"] = make("Org5", "peer")
    return out


def fact(signers, endorsers, corrupt=()):
    """`endorsers`: (signer's name, the org the fact states)."""
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature, encode_dss_signature)
    from benchmarks.reference import P256_N

    def part(who, org, message, bad=False):
        key, pem = signers[who]
        r, s = decode_dss_signature(
            key.sign(message, ec.ECDSA(hashes.SHA256())))
        sig = encode_dss_signature(r, min(s, P256_N - s))   # low-S
        if bad:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        return SignedPart(org, pem, message, sig)

    return TxFacts(
        env_bytes=b"", ns="mycc", key="k", value=b"v",
        creator=part("client", "client", b"payload"),
        endorsements=[part(who, org, b"prp", bad=i in corrupt)
                      for i, (who, org) in enumerate(endorsers)])


def the_rule():
    from benchmarks.reference import Signatures
    return importlib.import_module(
        "benchmarks.references.n_out_of_own_key").Rule(
        {"policy_n": 3, "orgs": 4}, {}, Signatures().counts)


@pytest.mark.parametrize("endorsers,corrupt,code", [
    ([(o, o) for o in ORGS[:2]], (), 10),
    ([(o, o) for o in ORGS[:3]], (), 0),
    ([(o, o) for o in ORGS], (), 0),
    # three endorsements, two of them one org's
    ([(o, o) for o in ORGS[:2]] + [("Org2", "Org2")], (), 10),
    # the third is a client's certificate
    ([(o, o) for o in ORGS[:2]] + [("client", "Org3")], (), 10),
    # the third signature does not count
    ([(o, o) for o in ORGS[:3]], (2,), 10),
    # a fourth endorsement makes up for it
    ([(o, o) for o in ORGS], (2,), 0),
    # the third org is none of the channel's four
    ([(o, o) for o in ORGS[:2]] + [("Org5", "Org5")], (), 10),
], ids=["2orgs", "3orgs", "4orgs", "one_org_twice", "client_cert",
        "corrupt_third", "corrupt_third_of_four", "org_outside"])
def test_rule_counts_distinct_peer_orgs(signers, endorsers, corrupt, code):
    got, writes = the_rule().judge(fact(signers, endorsers, corrupt), 1, 0)
    assert got == code
    assert writes == ({("mycc", "k"): b"v"} if code == 0 else {})


def test_rule_holds_the_creator_first(signers):
    tx = fact(signers, [(o, o) for o in ORGS[:3]])
    sig = tx.creator.signature
    tx.creator.signature = sig[:-1] + bytes([sig[-1] ^ 1])
    assert the_rule().judge(tx, 1, 0) == (4, {})


# --- the reducer -------------------------------------------------------------

def ring_of(blocks, calls_of_batch, lead=2):
    """The spans a traced run would record for `blocks` blocks, each
    with one call for its signature under `mcs_verify` and
    `calls_of_batch` calls under `device_dispatch`; the deliver thread
    runs `lead` blocks ahead, so the last `lead` blocks lack their
    batch when the run stops."""
    ring, ids = [], iter(range(10 ** 6))

    def sp(name, trace, parent=None, **attrs):
        ring.append({"trace_id": trace, "span_id": "%08x" % next(ids),
                     "parent_id": parent, "name": name, "attrs": attrs})

    def block_span(name, trace, parent, number, calls):
        # a parent ends after its children: reserve its id first
        me = "%08x" % next(ids)
        for _ in range(calls):
            sp("der_marshal", trace, me, items=9, bucket=64)
            sp("device_enqueue", trace, me, bucket=64)
        ring.append({"trace_id": trace, "span_id": me, "parent_id": parent,
                     "name": name, "attrs": {"block": number}})

    for n in range(1, blocks + 1):
        recv = "%08x" % next(ids)
        block_span("mcs_verify", f"d{n}", recv, n, 1)
        ring.append({"trace_id": f"d{n}", "span_id": recv,
                     "parent_id": None, "name": "recv",
                     "attrs": {"block": n}})
        if n > lead:
            block_span("device_dispatch", f"s{n}", None, n - lead,
                       calls_of_batch)
    # a warm-up call outside any block, and the call of a batch whose
    # `device_dispatch` had not ended when the run stopped
    sp("device_enqueue", "warm", None, bucket=2048)
    sp("device_enqueue", "cut", "ffffffff", bucket=2048)
    return ring


@pytest.mark.parametrize("blocks,calls_of_batch,value", [
    (43, 3, 4.0), (53, 1, 2.0), (854, 1, 2.0)])
def test_calls_per_block_counts_each_whole_block_by_its_parents(
        blocks, calls_of_batch, value):
    from benchmarks.reducers import calls_per_block
    spec, _ = reducer_for("verify_calls_per_block")
    calls = calls_per_block.per_block(
        ring_of(blocks, calls_of_batch), set(spec["spans"]),
        set(spec["under"]))
    # the two blocks the deliver thread ran ahead are not whole
    assert sorted(calls) == list(range(1, blocks - 1))
    assert set(calls.values()) == {1 + calls_of_batch}
    assert sum(calls.values()) / len(calls) == value


@pytest.mark.parametrize("ring", [
    [], [s for s in ring_of(5, 1) if s["name"] != "device_enqueue"],
    [s for s in ring_of(5, 1) if s["name"] != "device_dispatch"]],
    ids=["no_spans", "no_calls", "no_block_whole"])
def test_calls_per_block_reads_nothing_rather_than_zero(ring, monkeypatch):
    from fabric_mod_tpu.observability import tracing
    spec, reduce_fn = reducer_for("verify_calls_per_block")
    monkeypatch.setattr(tracing.recorder(), "recent_spans",
                        lambda limit=0: ring)
    assert reduce_fn(spec, None) is None


def test_calls_per_block_reads_the_recorder_and_not_a_full_ring(
        monkeypatch):
    from fabric_mod_tpu.observability import tracing
    spec, reduce_fn = reducer_for("verify_calls_per_block")
    ring = ring_of(12, 1)
    monkeypatch.setattr(tracing.recorder(), "recent_spans",
                        lambda limit=0: ring)
    assert reduce_fn(spec, None) == 2.0
    # a ring that is full may have dropped calls before their parents
    monkeypatch.setattr(tracing, "SPAN_RING", len(ring))
    assert reduce_fn(spec, None) is None


# --- the rehearsal -----------------------------------------------------------

def test_rehearsal_is_correct_under_its_own_rule():
    result = drive(2 ** 31 + 4321, cell=the_cell(REHEARSAL))
    assert result["correct"], over_limit(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"committed_tx_s", "setup_s"}


def test_rehearsal_traced_reads_the_new_metric():
    result = drive(31, traced=True, cell=the_cell(REHEARSAL))
    assert over_limit(result) == {"trace_missing"}     # no device here
    # the stand-in verifier opens no `device_enqueue` span: the reducer
    # finds nothing and the line leaves the metric out
    assert "verify_calls_per_block" not in result["metrics"]
    # the walk this deployment lengthens is what policy_ms_per_tx reads
    assert result["metrics"]["policy_ms_per_tx"]["value"] > 0


def test_rehearsal_is_not_correct_under_the_three_org_rule():
    """A transaction whose second of three endorsements is corrupted
    has two counting orgs: a policy failure by 3-of-4, VALID by more
    than half of three."""
    cell = the_cell(REHEARSAL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["reference"] = "majority_own_key"
    cell.config["settings"]["orgs"] = 3
    result = drive(2 ** 31 + 4321, cell=cell)
    assert not result["correct"]
    assert over_limit(result) == {"flag_diff", "state_diff"}


def test_rehearsal_with_one_flag_flipped_is_not_correct():
    def flip_one_flag(channel):
        from fabric_mod_tpu.protos import protoutil

        def commit_staged(staged):
            flags = staged.validator.finish(staged)
            if staged.block.header.number == 5:
                flags[3] = 10 if flags[3] == 0 else 0
                protoutil.set_block_txflags(staged.block, bytes(flags))
            return channel.ledger.commit_block(
                staged.block, flags, rwsets=staged.rwsets)
        channel.commit_staged = commit_staged
    result = drive(41, wrap_channel=flip_one_flag,
                   cell=the_cell(REHEARSAL))
    assert not result["correct"]
    assert "flag_diff" in over_limit(result)

