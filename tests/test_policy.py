"""Policy engine tests: DSL parsing, NOutOf evaluation semantics,
dedup + eager batch verification, implicit meta policies, application
policies.  Negative coverage mirrors the reference's cauthdsl tests
(under-threshold, duplicate identities, invalid signatures)."""
import hashlib

import pytest

from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.msp.mspimpl import Msp, MspManager
from fabric_mod_tpu.policy import (
    ApplicationPolicyEvaluator, BatchCollector, CompiledPolicy, DslError,
    PolicyManager, from_string)
from fabric_mod_tpu.policy.manager import ImplicitMetaPolicyObj
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos.protoutil import SignedData
from tests.test_msp import _lookups


@pytest.fixture(scope="module")
def world():
    """Three orgs, one signer each + an extra Org1 signer."""
    csp = SwCSP()
    orgs = {}
    msps = []
    for name in ("Org1", "Org2", "Org3"):
        ca = calib.CA(f"ca.{name.lower()}", name)
        msp = Msp(name, csp, [ca.cert])
        msps.append(msp)
        def mk(cn, ous, _ca=ca, _name=name):
            cert, key = _ca.issue(cn, _name, ous=ous)
            return SigningIdentity(_name, cert,
                                   calib.key_pem(key), csp)
        orgs[name] = dict(
            ca=ca, msp=msp,
            peer=mk(f"peer0.{name.lower()}", ["peer"]),
            admin=mk(f"admin@{name.lower()}", ["admin"]))
    ca1 = orgs["Org1"]["ca"]
    cert, key = ca1.issue("peer1.org1", "Org1", ous=["peer"])
    orgs["Org1"]["peer2"] = SigningIdentity(
        "Org1", cert, calib.key_pem(key), csp)
    mgr = MspManager(msps)
    return dict(csp=csp, orgs=orgs, mgr=mgr)


def _sd(ident, data: bytes) -> SignedData:
    return SignedData(data=data, identity=ident.serialize(),
                      signature=ident.sign_message(data))


# --- DSL parser -------------------------------------------------------------

def test_dsl_and_or_outof():
    env = from_string("AND('Org1.member', 'Org2.member')")
    assert env.rule.n_out_of.n == 2
    assert len(env.identities) == 2
    env = from_string("OR('Org1.member', 'Org2.member')")
    assert env.rule.n_out_of.n == 1
    env = from_string(
        "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')")
    assert env.rule.n_out_of.n == 2
    assert len(env.identities) == 3


def test_dsl_nested_and_dedup():
    env = from_string(
        "AND('Org1.member', OR('Org2.admin', 'Org1.member'))")
    # Org1.member used twice -> one identities entry
    assert len(env.identities) == 2
    inner = env.rule.n_out_of.rules[1]
    assert inner.n_out_of.rules[1].signed_by == 0   # dedup'd index


@pytest.mark.parametrize("bad", [
    "AND('Org1.member'", "XOR('a.b')", "AND(Org1.member)",
    "OutOf(5, 'Org1.member')", "'Org1.bogusrole'", "''",
    "AND('Org1.member') trailing",
])
def test_dsl_rejects(bad):
    with pytest.raises(DslError):
        from_string(bad)


# --- evaluation -------------------------------------------------------------

def _compiled(world, dsl):
    return CompiledPolicy(from_string(dsl), world["mgr"])


def test_two_of_three_endorsement(world):
    pol = _compiled(world, "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')")
    o = world["orgs"]
    data = b"proposal-response-payload"
    assert pol.evaluate_signed_data(
        [_sd(o["Org1"]["peer"], data), _sd(o["Org2"]["peer"], data)])
    assert pol.evaluate_signed_data(
        [_sd(o["Org2"]["peer"], data), _sd(o["Org3"]["peer"], data)])
    # under threshold
    assert not pol.evaluate_signed_data([_sd(o["Org1"]["peer"], data)])
    # wrong role
    assert not pol.evaluate_signed_data(
        [_sd(o["Org1"]["peer"], data), _sd(o["Org2"]["admin"], data)])


def test_duplicate_identity_not_double_counted(world):
    pol = _compiled(world, "AND('Org1.peer', 'Org1.peer')")
    o = world["orgs"]
    data = b"d"
    sd = _sd(o["Org1"]["peer"], data)
    # same identity twice: dedup leaves one -> AND of two fails
    assert not pol.evaluate_signed_data([sd, sd])
    # two *distinct* Org1 peers satisfy it
    assert pol.evaluate_signed_data(
        [sd, _sd(o["Org1"]["peer2"], data)])


def test_invalid_signature_rejected(world):
    pol = _compiled(world, "OR('Org1.peer')")
    o = world["orgs"]
    good = _sd(o["Org1"]["peer"], b"data")
    bad = SignedData(data=b"data", identity=good.identity,
                     signature=good.signature[:-4] + b"\x00\x00\x00\x00")
    assert not pol.evaluate_signed_data([bad])
    assert pol.evaluate_signed_data([good])


def test_foreign_identity_skipped(world):
    """An identity from an MSP the channel doesn't know is dropped
    during the dedup/validate phase, not an error."""
    pol = _compiled(world, "OR('Org1.peer')")
    evil_ca = calib.CA("ca.evil", "Evil")
    cert, key = evil_ca.issue("spy", "Evil", ous=["peer"])
    spy = SigningIdentity("EvilMSP", cert, calib.key_pem(key), world["csp"])
    assert not pol.evaluate_signed_data([_sd(spy, b"d")])


def test_single_batch_dispatch_for_many_policies(world):
    """The whole point: N policy evaluations -> ONE verify call."""
    o = world["orgs"]
    calls = []

    def counting_verify(items):
        calls.append(len(items))
        return SwCSP().verify_batch(items)

    pols = [
        _compiled(world, "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')"),
        _compiled(world, "AND('Org1.admin', 'Org2.admin')"),
        _compiled(world, "OR('Org3.peer')"),
    ]
    work = [
        [_sd(o["Org1"]["peer"], b"t0"), _sd(o["Org2"]["peer"], b"t0")],
        [_sd(o["Org1"]["admin"], b"t1"), _sd(o["Org2"]["admin"], b"t1")],
        [_sd(o["Org3"]["peer"], b"t2")],
    ]
    collector = BatchCollector()
    pending = [p.prepare(sds, collector) for p, sds in zip(pols, work)]
    mask = counting_verify(collector.items)
    results = [pd.finish(mask) for pd in pending]
    assert results == [True, True, True]
    assert calls == [5]                      # one dispatch, 5 signatures


def test_nested_noutof_trial_commit_semantics(world):
    """A failed inner OutOf branch must not consume identities
    (reference cauthdsl.go trial/commit loop)."""
    o = world["orgs"]
    # OR(AND(Org1.peer, Org2.peer), Org1.peer): with only Org1's peer
    # present the AND fails but must release Org1.peer for the second
    # branch.
    pol = _compiled(
        world, "OR(AND('Org1.peer', 'Org2.peer'), 'Org1.peer')")
    assert pol.evaluate_signed_data([_sd(o["Org1"]["peer"], b"d")])


# --- the closure walk against cauthdsl.go's rule, stated plainly ------------
#
# Principal satisfaction is a lookup table and validity a list of
# booleans: no crypto, so hundreds of seeded trees run in a blink.

class FakeIdent:
    def __init__(self, key):
        self.key = key


class FakeMgr:
    """satisfies_principal from an (ident key, principal byte) table."""

    def __init__(self, table):
        self.table = table

    def satisfies_principal(self, ident, principal):
        return self.table.get((ident.key, principal.principal[0]), False)


def _leaf(i):
    return m.SignaturePolicy(signed_by=i)


def _nout(n, *rules):
    return m.SignaturePolicy(n_out_of=m.NOutOf(n=n, rules=list(rules)))


def _envelope(rule, n_prins):
    prins = [m.MSPPrincipal(principal_classification=1,
                            principal=bytes([j])) for j in range(n_prins)]
    return m.SignaturePolicyEnvelope(rule=rule, identities=prins)


def _walk_verdict(env, table, idents, valid):
    """The program's answer: `cauthdsl._compile`'s closure behind a
    PendingEval, even slots read from the batch mask and odd slots
    carrying a host verdict, as `CompiledPolicy.prepare` builds them."""
    from fabric_mod_tpu.policy import cauthdsl
    closure = cauthdsl._compile(env.rule, env.identities, FakeMgr(table))
    mask, slots = [], []
    for i, ok in enumerate(valid):
        if i % 2 == 0:
            slots.append((len(mask), False))
            mask.append(ok)
        else:
            slots.append((None, ok))
    return cauthdsl.PendingEval(closure, idents, slots).finish(mask)


def _fabric_rule(rule, table, idents, used):
    """Fabric's cauthdsl.go `compile`, as a pure function: (satisfied,
    the used flags after).  A SignedBy takes the first identity not
    yet used that satisfies its principal.  An NOutOf runs EVERY child
    (no early exit), each on a copy of the flags as they stand; the
    copy is kept only if that child was satisfied; the node holds if
    at least n children were."""
    if rule.n_out_of is None:
        for i, ident in enumerate(idents):
            if not used[i] and table.get((ident.key, rule.signed_by)):
                return True, used[:i] + [True] + used[i + 1:]
        return False, used
    verified = 0
    for child in rule.n_out_of.rules:
        ok, trial = _fabric_rule(child, table, idents, used)
        if ok:
            verified, used = verified + 1, trial
    return verified >= rule.n_out_of.n, used


def _rule_verdict(env, table, idents, valid):
    # only identities whose signature verified reach the walk
    vid = [i for i, ok in zip(idents, valid) if ok]
    return _fabric_rule(env.rule, table, vid, [False] * len(vid))[0]


def _rand_tree(rng, n_prins, depth, max_depth, thresholds):
    """A seeded tree: a leaf with probability 0.4 below the first
    level (always at `max_depth`), else an NOutOf of 1-3 children
    whose threshold `thresholds(rng, k)` draws."""
    if depth >= max_depth or (depth > 0 and rng.random() < 0.4):
        return _leaf(rng.randrange(n_prins))
    k = rng.randrange(1, 4)
    subs = [_rand_tree(rng, n_prins, depth + 1, max_depth, thresholds)
            for _ in range(k)]
    return _nout(thresholds(rng, k), *subs)


def _depth(rule):
    if rule.n_out_of is None:
        return 0
    return 1 + max((_depth(r) for r in rule.n_out_of.rules), default=0)


def _within(rng, k):
    return rng.randrange(1, k + 1)


def _flat(rng, n_prins, k):
    return _nout(_within(rng, k),
                 *[_leaf(rng.randrange(n_prins)) for _ in range(k)])


def _chain(rng, n_prins, levels):
    """A tree at least `levels` NOutOf nodes deep: a spine of nodes,
    each with the next spine node and up to two random siblings."""
    node = _leaf(rng.randrange(n_prins))
    for _ in range(levels):
        sibs = [_rand_tree(rng, n_prins, 1, 3, _within)
                for _ in range(rng.randrange(0, 3))]
        kids = sibs + [node]
        rng.shuffle(kids)
        node = _nout(_within(rng, len(kids)), *kids)
    return node


# what a shape class does not say: one to four principals, up to five
# identities, seven signatures in ten valid
_SHAPE_DEFAULTS = dict(
    n_prins=lambda rng: rng.randrange(1, 5),
    n_id=lambda rng: rng.randrange(0, 6),
    valid=lambda rng: rng.random() < 0.7,
    check=lambda env: True)

# shape class -> its tree generator and what it overrides of the above
_SHAPES = {
    # one NOutOf over leaves: the shape of every cell's policy
    "flat": dict(tree=lambda rng, p: _flat(rng, p, rng.randrange(1, 6))),
    "nested-two-deep": dict(
        tree=lambda rng, p: _rand_tree(rng, p, 0, 2, _within)),
    "nested-past-four-deep": dict(
        tree=lambda rng, p: _chain(rng, p, rng.randrange(5, 8)),
        check=lambda env: _depth(env.rule) >= 5),
    # one or two principals under many leaves: the used flags decide
    "duplicate-principals": dict(
        n_prins=lambda rng: rng.randrange(1, 3),
        tree=lambda rng, p: _rand_tree(rng, p, 0, 3, _within)),
    # somewhere n exceeds the children: that node can never hold
    "threshold-above-children": dict(
        tree=lambda rng, p: _rand_tree(
            rng, p, 0, 3,
            lambda r, k: k + 1 if r.random() < 0.3 else _within(r, k))),
    # n = 0 always holds, and its children still consume identities
    "threshold-zero": dict(
        tree=lambda rng, p: _rand_tree(
            rng, p, 0, 3,
            lambda r, k: 0 if r.random() < 0.4 else _within(r, k))),
    "no-identities": dict(
        tree=lambda rng, p: _rand_tree(
            rng, p, 0, 3, lambda r, k: r.randrange(0, k + 1)),
        n_id=lambda rng: 0),
    "every-identity-invalid": dict(
        tree=lambda rng, p: _rand_tree(
            rng, p, 0, 3, lambda r, k: r.randrange(0, k + 1)),
        n_id=lambda rng: rng.randrange(1, 6),
        valid=lambda rng: False),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_closure_walk_is_fabrics_rule_on_seeded_trees(shape):
    """`cauthdsl._compile` against the rule of Fabric's cauthdsl.go
    written out above, over 300 seeded trees of one shape class with
    random satisfaction tables and random signature verdicts."""
    import random
    import zlib
    gen = {**_SHAPE_DEFAULTS, **_SHAPES[shape]}
    rng = random.Random(zlib.crc32(shape.encode()))
    seen = set()
    for _ in range(300):
        n_prins = gen["n_prins"](rng)
        env = _envelope(gen["tree"](rng, n_prins), n_prins)
        assert gen["check"](env)
        n_id = gen["n_id"](rng)
        idents = [FakeIdent(i) for i in range(n_id)]
        table = {(i, j): rng.random() < 0.5
                 for i in range(n_id) for j in range(n_prins)}
        valid = [gen["valid"](rng) for _ in range(n_id)]
        want = _rule_verdict(env, table, idents, valid)
        assert _walk_verdict(env, table, idents, valid) is want, \
            (env, table, valid)
        seen.add(want)
    # both answers occur in every class: with no identity that counts,
    # only a tree whose root holds at threshold 0 is satisfied
    assert seen == {True, False}


A, B = _leaf(0), _leaf(1)


@pytest.mark.parametrize("rule,table,valid,want", [
    # id0 satisfies A and B, id1 only A: greedy gives id0 to A, B
    # finds nobody, though the matching id1->A, id0->B exists
    pytest.param(_nout(2, A, B),
                 {(0, 0): True, (0, 1): True, (1, 0): True},
                 [True, True], False, id="greedy-is-not-maximal-matching"),
    # the inner OutOf(1, A, A) keeps running after its threshold is
    # met and consumes BOTH identities: the outer A finds nobody
    pytest.param(_nout(2, _nout(1, A, A), A),
                 {(0, 0): True, (1, 0): True},
                 [True, True], False, id="no-early-exit"),
    pytest.param(A, {(0, 0): True}, [False], False,
                 id="invalid-identity-never-satisfies"),
    pytest.param(A, {(0, 0): True}, [True], True,
                 id="the-same-identity-valid-does"),
])
def test_closure_walk_named_edge_cases(rule, table, valid, want):
    """The greedy used-flag edge cases with their literal answers (a
    failed child does not consume:
    test_nested_noutof_trial_commit_semantics, above)."""
    env = _envelope(rule, 2)
    idents = [FakeIdent(i) for i in range(len(valid))]
    assert _walk_verdict(env, table, idents, valid) is want
    assert _rule_verdict(env, table, idents, valid) is want


def test_compile_policy_bytes_memoized(world):
    from fabric_mod_tpu.policy.manager import compile_policy_bytes
    env_bytes = from_string(
        "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')").encode()
    a = compile_policy_bytes(env_bytes, world["mgr"], 3)
    assert compile_policy_bytes(env_bytes, world["mgr"], 3) is a
    # the config sequence keys the memo
    assert compile_policy_bytes(env_bytes, world["mgr"], 4) is not a


# --- implicit meta + manager ------------------------------------------------

def _org_writers(world):
    return {
        name: CompiledPolicy(from_string(f"OR('{name}.member')"),
                             world["mgr"])
        for name in ("Org1", "Org2", "Org3")
    }


def test_implicit_meta_majority(world):
    o = world["orgs"]
    subs = list(_org_writers(world).values())
    maj = ImplicitMetaPolicyObj(subs, m.ImplicitMetaRule.MAJORITY)
    assert maj.threshold == 2
    data = b"config-update"
    assert maj.evaluate_signed_data(
        [_sd(o["Org1"]["peer"], data), _sd(o["Org2"]["peer"], data)])
    assert not maj.evaluate_signed_data([_sd(o["Org3"]["peer"], data)])
    any_ = ImplicitMetaPolicyObj(subs, m.ImplicitMetaRule.ANY)
    assert any_.evaluate_signed_data([_sd(o["Org3"]["peer"], data)])
    all_ = ImplicitMetaPolicyObj(subs, m.ImplicitMetaRule.ALL)
    assert not all_.evaluate_signed_data(
        [_sd(o["Org1"]["peer"], data), _sd(o["Org2"]["peer"], data)])


def test_empty_implicit_meta_never_passes(world):
    """ANY over zero sub-policies must fail closed (threshold pinned
    at 1 like the reference), never authorize everything."""
    o = world["orgs"]
    empty_any = ImplicitMetaPolicyObj([], m.ImplicitMetaRule.ANY)
    assert empty_any.threshold == 1
    from fabric_mod_tpu.policy import BatchCollector
    col = BatchCollector()
    pending = empty_any.prepare([_sd(o["Org1"]["peer"], b"x")], col)
    assert pending.finish([]) is False


def test_empty_implicit_meta_all_fails_closed(world):
    for rule in (m.ImplicitMetaRule.ALL, m.ImplicitMetaRule.MAJORITY):
        empty = ImplicitMetaPolicyObj([], rule)
        assert empty.threshold == 1
        from fabric_mod_tpu.policy import BatchCollector
        pend = empty.prepare([_sd(world["orgs"]["Org1"]["peer"], b"x")],
                             BatchCollector())
        assert pend.finish([]) is False


def test_collector_dedups_identical_items(world):
    """A meta policy handing the same signatures to N sub-policies must
    not multiply the device batch."""
    o = world["orgs"]
    subs = list(_org_writers(world).values())
    meta = ImplicitMetaPolicyObj(subs, m.ImplicitMetaRule.ANY)
    from fabric_mod_tpu.policy import BatchCollector
    col = BatchCollector()
    sds = [_sd(o["Org1"]["peer"], b"d"), _sd(o["Org2"]["peer"], b"d")]
    pend = meta.prepare(sds, col)
    assert len(col.items) == 2               # 3 sub-policies, 2 unique sigs
    mask = SwCSP().verify_batch(col.items)
    assert pend.finish(mask) is True


# --- one resolution per implicit meta evaluation -----------------------------

def _per_sub(pol, sds, col):
    """Each leaf resolves the signature set itself, as the reference
    does for every sub-policy."""
    from fabric_mod_tpu.policy.manager import _MetaPending
    if isinstance(pol, CompiledPolicy):
        return pol.prepare(sds, col)
    return _MetaPending([_per_sub(s, sds, col) for s in pol._subs],
                        pol.threshold)


def _meta_tree(world, shape):
    subs = list(_org_writers(world).values())
    if shape != "nested":
        return ImplicitMetaPolicyObj(subs, getattr(m.ImplicitMetaRule, shape))
    # /Channel/Writers over Application/Writers over each org's
    # Writers, beside an orderer-side meta over one two-org leaf
    app = ImplicitMetaPolicyObj(subs, m.ImplicitMetaRule.MAJORITY)
    ordr = ImplicitMetaPolicyObj(
        [_compiled(world, "AND('Org1.peer', 'Org2.peer')")],
        m.ImplicitMetaRule.ANY)
    return ImplicitMetaPolicyObj(
        [app, ordr, ImplicitMetaPolicyObj([], m.ImplicitMetaRule.ANY)],
        m.ImplicitMetaRule.MAJORITY)


def _signature_sets(world):
    """Signature sets that mix every way an endorsement can drop out
    of, or repeat within, a set."""
    from cryptography.hazmat.primitives.asymmetric import ec
    o = world["orgs"]
    data = b"prp||endorser"
    p1, p2, p3 = (_sd(o[n]["peer"], data) for n in ("Org1", "Org2", "Org3"))
    garbage = SignedData(data=data, identity=b"\x0a\x03Org9\x12\x01z",
                         signature=p1.signature)
    rogue_ca = calib.CA("ca.rogue", "Org1")
    cert, key = rogue_ca.issue("peer9.org1", "Org1", ous=["peer"])
    rogue = _sd(SigningIdentity("Org1", cert, calib.key_pem(key),
                                world["csp"]), data)
    cert, key = o["Org2"]["ca"].issue(
        "p384.org2", "Org2", ous=["peer"],
        key=ec.generate_private_key(ec.SECP384R1()))
    p384 = _sd(SigningIdentity("Org2", cert, calib.key_pem(key),
                               world["csp"]), data)

    def flipped(sd):
        sig = bytearray(sd.signature)
        sig[-1] ^= 1
        return SignedData(data=sd.data, identity=sd.identity,
                          signature=bytes(sig))
    return {
        "single": [p1],
        "two": [p1, p2],
        "three": [p3, p2, p1],
        "none-valid": [flipped(p1), garbage, rogue],
        "duplicate": [p1, p1, p2],
        "undeserializable": [garbage, p2, p3],
        "fails-validate": [rogue, p3],
        "flipped": [flipped(p1), p2, p3],
        "host-verdict": [p384, p1],
        "host-verdict-flipped": [flipped(p384), p3],
        "mixed": [p2, garbage, p2, rogue, flipped(p3), p384, p1],
    }


@pytest.mark.parametrize("shape", ["ANY", "ALL", "MAJORITY", "nested"])
def test_shared_resolution_matches_per_sub_prepare(world, shape):
    """One resolution bound to every leaf gives each set the verdict
    the per-sub-policy path gives, and stages the same items in the
    same order, over a block's worth of evaluations in one batch."""
    meta = _meta_tree(world, shape)
    sets = _signature_sets(world)
    shared_col, per_col = BatchCollector(), BatchCollector()
    shared = [meta.prepare(sds, shared_col) for sds in sets.values()]
    per = [_per_sub(meta, sds, per_col) for sds in sets.values()]
    assert shared_col.items == per_col.items
    assert per_col.shared_resolutions == 0
    leaves = 4 if shape == "nested" else 3
    distinct = sum(len({sd.identity for sd in sds})
                   for sds in sets.values())
    assert shared_col.shared_resolutions == (leaves - 1) * distinct
    assert shared_col.requests < per_col.requests
    mask = world["csp"].verify_batch(shared_col.items)
    got = [p.finish(mask) for p in shared]
    assert got == [p.finish(mask) for p in per]
    assert True in got and False in got


@pytest.mark.parametrize("case", ["two", "mixed"])
def test_meta_tree_on_two_managers_resolves_once_per_manager(
        world, monkeypatch, case):
    """Leaves bound to two managers: each manager deserializes the set
    once, for its own leaves only, and the verdict and staged items
    are the per-sub-policy path's."""
    other = MspManager(world["mgr"].msps())
    meta = ImplicitMetaPolicyObj(
        [_compiled(world, "OR('Org1.member')"),
         _compiled(world, "OR('Org3.member')"),
         CompiledPolicy(from_string("OR('Org2.member')"), other)],
        m.ImplicitMetaRule.ALL)
    calls = []
    deserialize = MspManager.deserialize_identity
    monkeypatch.setattr(
        MspManager, "deserialize_identity",
        lambda self, raw: calls.append(self) or deserialize(self, raw))
    sds = _signature_sets(world)[case]
    col, per_col = BatchCollector(), BatchCollector()
    pend = meta.prepare(sds, col)
    distinct = len({sd.identity for sd in sds})
    assert calls.count(world["mgr"]) == calls.count(other) == distinct
    assert col.shared_resolutions == distinct
    per = _per_sub(meta, sds, per_col)
    assert col.items == per_col.items
    mask = world["csp"].verify_batch(col.items)
    assert pend.finish(mask) == per.finish(mask)


def test_meta_endorsement_resolves_each_endorsement_once(world, monkeypatch):
    """Under the three-org MAJORITY Endorsement policy each distinct
    endorsement is deserialized, validated and staged once per
    prepare, not once per sub-policy."""
    from fabric_mod_tpu.msp.identities import Identity
    bundle = _bundle(world)
    pol = bundle.policy(ENDORSEMENT)
    staged = []
    verify_item = Identity.verify_item
    monkeypatch.setattr(
        Identity, "verify_item",
        lambda self, msg, sig: staged.append(self) or
        verify_item(self, msg, sig))
    o = world["orgs"]
    endorsers = [o["Org1"]["peer"], o["Org2"]["peer"]]
    col = BatchCollector()
    before = _lookups()
    pendings = [pol.prepare([_sd(e, b"tx%d" % i) for e in endorsers], col)
                for i in range(5)]
    looked = {}
    for (cache, _), v in _lookups().items():
        looked[cache] = looked.get(cache, 0) + v - before.get((cache, _), 0)
    assert looked["deserialize"] == 10 and looked["validate"] == 10
    assert len(staged) == 10 and len(col.items) == 10
    assert col.requests == 10 and col.shared_resolutions == 20
    mask = world["csp"].verify_batch(col.items)
    assert all(p.finish(mask) for p in pendings)


@pytest.mark.parametrize("policy", ["meta-majority", "flat-signature"])
def test_validator_counts_shared_resolutions_once_a_block(world, policy):
    """fabric_policy_meta_shared_resolutions_total: 4 a two-endorsement
    transaction under the MAJORITY Endorsement policy, 0 under a flat
    SIGNATURE policy that no meta policy wraps."""
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.peer import TxValidator, ValidationInfoProvider
    from fabric_mod_tpu.peer.txvalidator import _stage_metrics
    bundle = _bundle(world)
    if policy == "meta-majority":
        validator = _validator_of(bundle, world)
    else:
        flat = m.ApplicationPolicy(signature_policy=from_string(
            "OutOf(2, 'Org1.peer', 'Org2.peer', 'Org3.peer')"))
        validator = TxValidator(
            "cachech", bundle.msp_manager,
            ApplicationPolicyEvaluator(bundle.msp_manager,
                                       bundle.policy_manager,
                                       sequence=bundle.sequence),
            FakeBatchVerifier(world["csp"]),
            ValidationInfoProvider(flat.encode()))
    o = world["orgs"]
    endorsers = [o["Org1"]["peer"], o["Org3"]["peer"]]
    envs = [_signed_tx(world, endorsers, key=f"s{i}") for i in range(5)]
    _staged, _dedup, _raw, _fallback, counter = _stage_metrics()
    before = counter.value
    assert _flags(validator, envs) == bytes([V.VALID] * 5)
    assert counter.value - before == (20 if policy == "meta-majority" else 0)


def test_channel_policy_reference_not_stale(world):
    """Replacing a named channel policy must take effect on the next
    evaluation (the reference re-resolves per call)."""
    o = world["orgs"]
    app = PolicyManager("Application", policies={
        "Endorsement": _compiled(world, "OR('Org1.peer')")})
    root = PolicyManager("Channel")
    root.add_sub_manager(app)
    ref = m.ApplicationPolicy(
        channel_config_policy_reference="/Channel/Application/Endorsement")
    ev = ApplicationPolicyEvaluator(world["mgr"], root)
    sds = [_sd(o["Org1"]["peer"], b"d")]
    assert ev.evaluate(ref.encode(), sds)
    # config update tightens the policy to 2-of-2
    app.add_policy("Endorsement",
                   _compiled(world, "AND('Org1.peer', 'Org2.peer')"))
    assert not ev.evaluate(ref.encode(), sds)


def test_policy_manager_paths(world):
    writers = _org_writers(world)
    app = PolicyManager("Application")
    for name, pol in writers.items():
        org_mgr = PolicyManager(name, policies={"Writers": pol})
        app.add_sub_manager(org_mgr)
    app.resolve_implicit_meta("Writers", m.ImplicitMetaPolicy(
        sub_policy="Writers", rule=m.ImplicitMetaRule.ANY))
    root = PolicyManager("Channel")
    root.add_sub_manager(app)
    pol = root.get_policy("/Channel/Application/Writers")
    assert pol is not None
    o = world["orgs"]
    assert pol.evaluate_signed_data([_sd(o["Org2"]["peer"], b"x")])
    assert root.get_policy("/Channel/Application/Nope") is None
    assert root.get_policy("/Other/Thing") is None
    assert app.get_policy("Writers") is pol


def test_application_policy_evaluator(world):
    o = world["orgs"]
    inline = m.ApplicationPolicy(
        signature_policy=from_string("AND('Org1.peer', 'Org2.peer')"))
    ev = ApplicationPolicyEvaluator(world["mgr"])
    data = b"prp||endorser"
    assert ev.evaluate(inline.encode(), [
        _sd(o["Org1"]["peer"], data), _sd(o["Org2"]["peer"], data)])
    assert not ev.evaluate(inline.encode(), [_sd(o["Org1"]["peer"], data)])

    # channel policy reference
    app = PolicyManager("Application", policies={
        "Endorsement": CompiledPolicy(
            from_string("OR('Org3.peer')"), world["mgr"])})
    root = PolicyManager("Channel")
    root.add_sub_manager(app)
    ref = m.ApplicationPolicy(
        channel_config_policy_reference="/Channel/Application/Endorsement")
    ev2 = ApplicationPolicyEvaluator(world["mgr"], root)
    assert ev2.evaluate(ref.encode(), [_sd(o["Org3"]["peer"], b"z")])
    assert not ev2.evaluate(ref.encode(), [_sd(o["Org1"]["peer"], b"z")])


# --- one identity cache per channel config (msp/cache.py in the bundle) -----

ENDORSEMENT = "/Channel/Application/Endorsement"
V = m.TxValidationCode


def _misses_since(before):
    return {k[0]: v - before.get(k, 0) for k, v in _lookups().items()
            if k[1] == "miss"}


def _genesis_config(world, crls=None, roots=None):
    """The standard three-org channel config over the world's CAs;
    `crls` {org: [CRL DER]} and `roots` {org: CA} replace an org's."""
    from fabric_mod_tpu.channelconfig import genesis
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    orgs = []
    for name in sorted(world["orgs"]):
        ca = (roots or {}).get(name, world["orgs"][name]["ca"])
        orgs.append(genesis.org_group(
            name, [calib.cert_pem(ca.cert)],
            crls_der=(crls or {}).get(name, ())))
    oca = world["orgs"]["Org1"]["ca"]
    root = genesis.channel_group(
        genesis.application_group(orgs, sorted(world["orgs"])),
        genesis.orderer_group(
            [genesis.org_group("OrdererOrg", [calib.cert_pem(oca.cert)])],
            ["OrdererOrg"]))
    block = genesis.config_block("cachech", genesis.genesis_config(root))
    return config_from_block(block)[1]


def _bundle(world, uncached=False, **kw):
    """A Bundle of the world's config; `uncached` compiles the same
    policy tree against the bare MspManager, as before the cache moved
    into the bundle."""
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig import bundle as bundle_mod
    config = _genesis_config(world, **kw)
    if not uncached:
        return Bundle("cachech", config, world["csp"])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bundle_mod, "CachedMsp", lambda mgr: mgr)
        return Bundle("cachech", config, world["csp"])


def _validator_of(bundle, world):
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.peer import TxValidator, ValidationInfoProvider
    ref = m.ApplicationPolicy(channel_config_policy_reference=ENDORSEMENT)
    return TxValidator(
        "cachech", bundle.msp_manager,
        ApplicationPolicyEvaluator(bundle.msp_manager, bundle.policy_manager,
                                   sequence=bundle.sequence),
        FakeBatchVerifier(world["csp"]),
        ValidationInfoProvider(ref.encode()))


def _signed_tx(world, endorsers, creator=None, key="k"):
    from fabric_mod_tpu.ledger.rwsetutil import RWSetBuilder
    from fabric_mod_tpu.protos import protoutil
    b = RWSetBuilder()
    b.add_write("mycc", key, b"v")
    return protoutil.create_signed_tx(
        "cachech", "mycc", b.build().encode(),
        creator or world["orgs"]["Org1"]["admin"], endorsers)


def _flags(validator, envs):
    from fabric_mod_tpu.protos import protoutil
    block = protoutil.new_block(1, b"", envs)
    flags = validator.validate(block)
    assert bytes(protoutil.block_txflags(block)) == bytes(flags)
    return bytes(flags)


def test_bundle_policies_share_one_identity_cache(world, monkeypatch):
    """Every policy of the bundle is compiled against the bundle's
    cached manager, so N evaluations of the MAJORITY Endorsement policy
    over the same two endorsers walk each chain a fixed number of
    times, not 3 x N; the creator path hits the same cache."""
    from fabric_mod_tpu.msp import mspimpl
    from fabric_mod_tpu.msp.cache import CachedMsp
    bundle = _bundle(world)
    mgr = bundle.msp_manager
    assert isinstance(mgr, CachedMsp)
    assert {msp.mspid for msp in mgr.msps()} == {
        "Org1", "Org2", "Org3", "OrdererOrg"}
    assert mgr.get("Org2") is not None
    pol = bundle.policy(ENDORSEMENT)
    assert isinstance(pol, ImplicitMetaPolicyObj) and len(pol._subs) == 3
    assert all(sub._msp_mgr is mgr for sub in pol._subs)
    assert bundle.policy(
        "/Channel/Orderer/OrdererOrg/Writers")._msp_mgr is mgr

    links = []
    check_link = mspimpl._check_link
    monkeypatch.setattr(
        mspimpl, "_check_link",
        lambda child, issuer: links.append(1) or check_link(child, issuer))
    o = world["orgs"]
    endorsers = [o["Org1"]["peer"], o["Org2"]["peer"]]
    before = _lookups()
    col = BatchCollector()
    pendings = [pol.prepare([_sd(e, b"tx%d" % i) for e in endorsers], col)
                for i in range(40)]
    mask = world["csp"].verify_batch(col.items)
    assert all(p.finish(mask) for p in pendings)
    # per endorser: one walk to validate, one inside the principal
    # match of its own org's sub-policy; 40 x 3 x 2 before the cache
    assert len(links) == 4
    # principal: Org1's leaf stops at the first endorser, the others
    # try both
    assert _misses_since(before) == {
        "deserialize": 2, "validate": 2, "principal": 5}

    # the creator path (validator, endorser, gossip) is the same object
    ident = mgr.deserialize_identity(o["Org1"]["peer"].serialize())
    mgr.validate(ident)
    assert len(links) == 4
    assert _misses_since(before)["validate"] == 2


def test_second_block_of_the_same_identities_adds_hits_only(world):
    validator = _validator_of(_bundle(world), world)
    o = world["orgs"]
    endorsers = [o["Org1"]["peer"], o["Org2"]["peer"]]
    envs = [_signed_tx(world, endorsers, key=f"a{i}") for i in range(6)]
    assert _flags(validator, envs) == bytes([V.VALID] * 6)
    before = _lookups()
    envs = [_signed_tx(world, endorsers, key=f"b{i}") for i in range(6)]
    assert _flags(validator, envs) == bytes([V.VALID] * 6)
    after = _lookups()
    assert _misses_since(before) == {
        "deserialize": 0, "validate": 0, "principal": 0}
    # 6 creators + 6 x 2 endorsers, each resolved once for the three
    # sub-policies of the MAJORITY Endorsement policy
    assert after[("validate", "hit")] - before[("validate", "hit")] == 18


def _crl_revoking(ca, cert):
    import datetime
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    now = datetime.datetime.now(datetime.timezone.utc)
    crl = (x509.CertificateRevocationListBuilder()
           .issuer_name(ca.cert.subject)
           .last_update(now).next_update(now + datetime.timedelta(days=7))
           .add_revoked_certificate(
               x509.RevokedCertificateBuilder()
               .serial_number(cert.serial_number)
               .revocation_date(now).build())
           .sign(ca.key, hashes.SHA256()))
    return crl.public_bytes(serialization.Encoding.DER)


@pytest.mark.parametrize("change", ["revoked", "re-rooted"])
def test_config_update_starts_cold_and_refuses(world, tmp_path, change):
    """A config update is a new Bundle, hence a new, cold cache: the
    identity the old config's cache holds as valid is refused under a
    config that revoked it or replaced its org's root."""
    pytest.importorskip("cryptography.x509")
    from fabric_mod_tpu import e2e
    from fabric_mod_tpu.msp.mspimpl import MSPValidationError
    o = world["orgs"]
    peer2 = o["Org2"]["peer"]
    old = _bundle(world)
    endorsers = [o["Org1"]["peer"], peer2]
    envs = [_signed_tx(world, endorsers, key=f"k{i}") for i in range(3)]
    assert _flags(_validator_of(old, world), envs) == bytes([V.VALID] * 3)
    old.msp_manager.validate(
        old.msp_manager.deserialize_identity(peer2.serialize()))

    if change == "revoked":
        new = _bundle(world, crls={"Org2": [_crl_revoking(
            o["Org2"]["ca"], peer2.cert)]})
    else:
        new = _bundle(world, roots={"Org2": calib.CA("ca.org2.new", "Org2")})
    assert new.msp_manager is not old.msp_manager
    before = _lookups()
    ident = new.msp_manager.deserialize_identity(peer2.serialize())
    with pytest.raises(MSPValidationError):
        new.msp_manager.validate(ident)
    assert _misses_since(before) == {
        "deserialize": 1, "validate": 1, "principal": 0}
    assert _flags(_validator_of(new, world), envs) == bytes(
        [V.ENDORSEMENT_POLICY_FAILURE] * 3)
    # the old bundle's cache is untouched by the new one
    old.msp_manager.validate(
        old.msp_manager.deserialize_identity(peer2.serialize()))

    # the channel installs the bundle's own manager: no second wrapper
    net = e2e.Network(str(tmp_path))
    try:
        chan = net.channel
        assert chan.validator()._msp_mgr is chan.bundle().msp_manager
        chan._install_bundle(new)
        assert chan.validator()._msp_mgr is new.msp_manager
    finally:
        net.close()


def test_cached_and_uncached_bundles_give_identical_txflags(world):
    """One block of valid, single-endorsed, corrupted-signature,
    unknown-MSP, wrong-chain and expired endorsers (and creators): the
    bundle with the cache and the same policy tree compiled against the
    bare manager write the same txflags, twice over (cold, then warm)."""
    import dataclasses
    import datetime
    from fabric_mod_tpu.protos import protoutil
    csp, o = world["csp"], world["orgs"]
    p1, p2, p3 = (o[n]["peer"] for n in ("Org1", "Org2", "Org3"))
    past = (datetime.datetime.now(datetime.timezone.utc)
            - datetime.timedelta(days=1))

    def signer(mspid, ca, cn, **kw):
        cert, key = ca.issue(cn, mspid, ous=["peer"], **kw)
        return SigningIdentity(mspid, cert, calib.key_pem(key), csp)

    evil = calib.CA("ca.evil", "Evil")
    stranger = signer("NopeMSP", evil, "peer0.nope")
    wrong_chain = signer("Org2", evil, "peer0.org2")
    expired = signer("Org2", o["Org2"]["ca"], "old.org2", not_after=past)

    def corrupted(env):
        payload = protoutil.unmarshal_envelope_payload(env)
        tx = protoutil.extract_endorser_tx(payload)
        cap = m.ChaincodeActionPayload.decode(tx.actions[0].payload)
        e1 = cap.action.endorsements[1]
        cap.action.endorsements[1] = dataclasses.replace(
            e1, signature=e1.signature[:-1]
            + bytes([e1.signature[-1] ^ 1]))
        tx.actions[0] = m.TransactionAction(payload=cap.encode())
        return protoutil.sign_envelope(
            m.Payload(header=payload.header, data=tx.encode()),
            o["Org1"]["admin"])

    envs = [
        _signed_tx(world, [p1, p2], key="valid"),
        _signed_tx(world, [p1], key="single"),
        corrupted(_signed_tx(world, [p1, p2], key="corrupt")),
        _signed_tx(world, [p1, stranger], key="unknown-msp"),
        _signed_tx(world, [p1, wrong_chain], key="wrong-chain"),
        _signed_tx(world, [p1, expired], key="expired"),
        _signed_tx(world, [p2, p3, expired, stranger], key="valid-3"),
        _signed_tx(world, [p1, p2], creator=expired, key="c-expired"),
        _signed_tx(world, [p1, p2], creator=stranger, key="c-unknown"),
        _signed_tx(world, [p1, p2], creator=wrong_chain, key="c-chain"),
    ]
    cached = _validator_of(_bundle(world), world)
    bare = _validator_of(_bundle(world, uncached=True), world)
    assert isinstance(bare._msp_mgr, MspManager)
    want = _flags(bare, envs)
    assert want[0] == want[6] == V.VALID
    assert set(want[1:6]) == {V.ENDORSEMENT_POLICY_FAILURE}
    assert V.VALID not in want[7:]
    for _ in range(2):
        assert _flags(cached, envs) == want
