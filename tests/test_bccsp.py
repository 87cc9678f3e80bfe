"""BCCSP provider tests: sw/tpu agreement, keystore, batching service."""
import threading

import numpy as np
import pytest

from fabric_mod_tpu.bccsp import factory, sw, tpu
from fabric_mod_tpu.bccsp.api import VerifyItem


@pytest.fixture(scope="module")
def swcsp():
    return sw.SwCSP()


def test_sign_verify_roundtrip(swcsp):
    key = swcsp.key_gen("P256")
    digest = swcsp.hash(b"hello fabric")
    sig = swcsp.sign(key, digest)
    assert swcsp.verify(key.public_key(), sig, digest)
    assert not swcsp.verify(key.public_key(), sig, swcsp.hash(b"other"))
    assert sw.is_low_s(sig)  # provider always emits low-S


def test_high_s_rejected(swcsp):
    key = swcsp.key_gen("P256")
    digest = swcsp.hash(b"msg")
    r, s = sw.decode_dss_signature(swcsp.sign(key, digest))
    high = sw.encode_dss_signature(r, sw._ORDERS["P256"] - s)
    assert not swcsp.verify(key.public_key(), high, digest)


@pytest.mark.skipif(not sw.HAVE_CRYPTOGRAPHY,
                    reason="P-384 is outside the pure-python fallback")
def test_p384_roundtrip(swcsp):
    key = swcsp.key_gen("P384")
    digest = swcsp.hash(b"msg", "SHA384")
    sig = swcsp.sign(key, digest)
    assert swcsp.verify(key.public_key(), sig, digest)


def test_keystore_roundtrip(tmp_path):
    csp = sw.SwCSP(str(tmp_path))
    key = csp.key_gen("P256", ephemeral=False)
    fresh = sw.SwCSP(str(tmp_path))
    loaded = fresh.get_key(key.ski())
    assert loaded is not None and loaded.private()
    digest = fresh.hash(b"stored key works")
    assert fresh.verify(loaded.public_key(), fresh.sign(loaded, digest), digest)


@pytest.mark.skipif(not sw.HAVE_CRYPTOGRAPHY,
                    reason="AES is outside the pure-python fallback")
def test_aes_roundtrip(swcsp):
    key = swcsp.key_gen("AES256")
    ct = swcsp.encrypt(key, b"secret payload")
    assert swcsp.decrypt(key, ct) == b"secret payload"
    assert ct[16:] != b"secret payload"


def _make_items(csp, n, tamper=()):
    items = []
    for i in range(n):
        key = csp.key_gen("P256")
        digest = csp.hash(f"message {i}".encode())
        sig = csp.sign(key, digest)
        if i in tamper:
            digest = csp.hash(b"TAMPERED")
        items.append(VerifyItem(digest, sig, key.public_xy()))
    return items


def test_tpu_provider_matches_sw(swcsp):
    csp = tpu.TpuCSP()
    items = _make_items(swcsp, 6, tamper={1, 4})
    got = csp.verify_batch(items)
    expect = swcsp.verify_batch(items)
    assert got == expect == [True, False, True, True, False, True]


def test_tpu_provider_rejects_garbage_der(swcsp):
    csp = tpu.TpuCSP()
    good = _make_items(swcsp, 1)[0]
    bad = VerifyItem(good.digest, b"\x30\x02\x01\x01", good.public_xy)
    assert csp.verify_batch([good, bad]) == [True, False]


def test_batching_service_concurrent(swcsp):
    service = tpu.BatchingVerifyService(
        verifier=tpu.FakeBatchVerifier(swcsp), deadline_s=0.01)
    items = _make_items(swcsp, 8, tamper={3})
    results = [None] * len(items)

    def worker(i):
        results[i] = service.verify(items[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(items))]
    [t.start() for t in threads]
    [t.join() for t in threads]
    service.close()
    assert results == [True, True, True, False, True, True, True, True]


def test_factory_selection(tmp_path):
    assert isinstance(factory.new_provider({"default": "SW"}), sw.SwCSP)
    assert isinstance(factory.new_provider({"default": "TPU"}), tpu.TpuCSP)
    with pytest.raises(ValueError):
        factory.new_provider({"default": "HSM"})
    assert factory.get_default() is factory.get_default()


# --- key tables: which program a lane takes --------------------------------

def _keyed_items(csp, keys, n, tamper=()):
    """n items, lane i signed by keys[i % len(keys)]."""
    items = []
    for i in range(n):
        digest = csp.hash(f"keyed {i} {id(keys)}".encode())
        sig = csp.sign(keys[i % len(keys)], digest)
        if i in tamper:
            digest = csp.hash(b"TAMPERED")
        items.append(VerifyItem(digest, sig, keys[i % len(keys)].public_xy()))
    return items


def _counter(name):
    from fabric_mod_tpu.observability.metrics import default_provider
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return 0.0


def test_key_table_slots_are_reused_least_recent_first(swcsp, monkeypatch):
    monkeypatch.setattr(tpu, "KEY_SLOTS", 4)
    monkeypatch.setattr(tpu, "NEW_TABLES_PER_BATCH", 2)
    tables = tpu.KeyTables()
    a, b, c, d, e, f = (swcsp.key_gen("P256").public_xy() for _ in range(6))
    built0 = _counter("fabric_bccsp_key_tables_built_total")

    slot, ok, tabled, dev1 = tables.assign([a, b, a, None])
    assert list(tabled) == [True] * 4           # no key: tabled and False
    assert list(ok) == [True, True, True, False]
    assert slot[0] == slot[2] != slot[1]
    slot_a, slot_b = int(slot[0]), int(slot[1])
    assert dev1.shape == (64, 3, 30, 4 * 16)

    # over the budget: the keys with most lanes get the tables
    slot, ok, tabled, dev2 = tables.assign([c, d, d, e, e, e])
    assert list(tabled) == [False, True, True, True, True, True]
    assert dev2 is not dev1                     # a fresh array, never a write
    slot_of_d = slot[1]
    assert len({slot_a, slot_b, int(slot_of_d), int(slot[3])}) == 4

    # a known key keeps its slot and a batch of known keys puts nothing
    slot, ok, tabled, dev3 = tables.assign([b, e])
    assert dev3 is dev2 and list(tabled) == [True, True]
    assert int(slot[0]) == slot_b

    slot_d = int(slot_of_d)

    # full: the least recently used key of those NOT in the batch at
    # hand gives its slot up (d: b and e were used since, a is in the
    # batch and keeps its own)
    slot, ok, tabled, _ = tables.assign([c, a])
    assert list(tabled) == [True, True]
    assert (int(slot[0]), int(slot[1])) == (slot_d, slot_a)
    # every slot's key is in the batch: the new key waits, on the ladder
    slot, ok, tabled, _ = tables.assign([a, f, b, c, e])
    assert list(tabled) == [True, False, True, True, True]
    # alone, it takes the slot of the key used longest ago (a)
    slot, ok, tabled, _ = tables.assign([f])
    assert list(tabled) == [True] and int(slot[0]) == slot_a
    assert _counter("fabric_bccsp_key_tables_built_total") - built0 == 6

    # full, and a key never met: it costs no build and evicts nobody
    # (a signer that passes by); met again, it is a signer that repeats
    g = swcsp.key_gen("P256").public_xy()
    slot, ok, tabled, dev4 = tables.assign([g, g, b])
    assert list(tabled) == [False, False, True]
    assert _counter("fabric_bccsp_key_tables_built_total") - built0 == 6
    slot, ok, tabled, dev5 = tables.assign([g])
    assert list(tabled) == [True] and dev5 is not dev4
    assert _counter("fabric_bccsp_key_tables_built_total") - built0 == 7

    # a key that is no curve point holds a slot, marked: its lanes are
    # False and it is not looked at again
    bad = a[:63] + bytes([a[63] ^ 1])
    tables = tpu.KeyTables()
    for _ in range(2):
        slot, ok, tabled, _ = tables.assign([bad, b])
        assert list(tabled) == [True, True] and list(ok) == [False, True]


def test_batch_over_the_table_budget_splits_and_merges_in_lane_order(
        swcsp, monkeypatch):
    from fabric_mod_tpu.observability import tracing
    monkeypatch.setattr(tpu, "NEW_TABLES_PER_BATCH", 2)
    verifier = tpu.TpuVerifier(cache_size=0)
    keys = [swcsp.key_gen("P256") for _ in range(4)]
    # lanes by key: 0 1 2 3 0 1 0 (key 0: three lanes, key 1: two)
    items = _keyed_items(swcsp, keys, 6, tamper={1, 2}) \
        + _keyed_items(swcsp, keys[:1], 1)
    lanes0 = {p: _counter('fabric_bccsp_key_table_lanes_total{path="%s"}' % p)
              for p in ("table", "ladder")}
    with tracing.active():
        tracing.recorder().reset()
        resolve = verifier.verify_many_async(items)
        got = resolve()
        spans = tracing.recorder().recent_spans(limit=1 << 20)
    assert list(got) == swcsp.verify_batch(items) \
        == [True, False, False, True, True, True, True]
    assert (resolve.table_lanes, resolve.ladder_lanes) == (5, 2)
    # a device call each: its own marshal and enqueue
    marshals = [s["attrs"] for s in spans if s["name"] == "der_marshal"]
    assert sorted(a["items"] for a in marshals) == [2, 5]
    assert len([s for s in spans if s["name"] == "device_enqueue"]) == 2
    for path, lanes in (("table", 5), ("ladder", 2)):
        assert _counter('fabric_bccsp_key_table_lanes_total{path="%s"}'
                        % path) - lanes0[path] == lanes
    # the two keys left over have tables by their second batch
    again = verifier.verify_many_async(items[2:4])
    assert (again.table_lanes, again.ladder_lanes) == (2, 0)
    assert list(again()) == [False, True]
    verifier.close()


def test_keys_churn_through_the_slots_without_a_compile(swcsp):
    """More keys than slots, eight new ones a batch: the tables' array
    keeps its one shape, so no batch mints a program; and once the
    slots are full a key is built a table at its second batch only."""
    from fabric_mod_tpu.observability import tracing
    tracing.install_compile_counter()
    verifier = tpu.TpuVerifier(cache_size=0)
    first = _make_items(swcsp, 8, tamper={5})
    verifier.warm(first)                     # both programs, bucket 8
    compiles = tracing.compile_count()
    built0 = _counter("fabric_bccsp_key_tables_built_total")
    for rnd in range(tpu.KEY_SLOTS // 8 - 1):            # the free slots
        items = _make_items(swcsp, 8, tamper={rnd % 8})
        resolve = verifier.verify_many_async(items)
        assert list(resolve()) == [i != rnd % 8 for i in range(8)]
        assert (resolve.table_lanes, resolve.ladder_lanes) == (8, 0)
    late = _make_items(swcsp, 8, tamper={2})
    for lanes in ((0, 8), (8, 0), (8, 0)):               # full
        resolve = verifier.verify_many_async(late)
        assert list(resolve()) == [i != 2 for i in range(8)]
        assert (resolve.table_lanes, resolve.ladder_lanes) == lanes
    # the first batch's keys lost their slots to them
    resolve = verifier.verify_many_async(first)
    assert list(resolve()) == [i != 5 for i in range(8)]
    assert (resolve.table_lanes, resolve.ladder_lanes) == (0, 8)
    assert _counter("fabric_bccsp_key_tables_built_total") - built0 \
        == tpu.KEY_SLOTS - 8 + 8
    assert tracing.compile_count() == compiles
    verifier.close()


def test_two_threads_dispatch_through_one_verifiers_tables(swcsp):
    """Several threads dispatch (the stage thread, the MCS gate, gossip):
    each call runs against the tables as they stood when its slots were
    assigned, whatever the other thread builds meanwhile."""
    import sys
    verifier = tpu.TpuVerifier(cache_size=0)
    verifier.verify_many(_make_items(swcsp, 8))          # the program
    batches = [[_make_items(swcsp, 8, tamper={(t + rnd) % 8})
                for rnd in range(6)] for t in range(2)]
    results = [[], []]
    errors = []

    def worker(t):
        try:
            pending = [verifier.verify_many_async(b) for b in batches[t]]
            results[t] = [list(r()) for r in pending]
        except BaseException as e:      # reported below
            errors.append(e)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(2)]
        [t.start() for t in threads]
        [t.join(timeout=300) for t in threads]
    finally:
        sys.setswitchinterval(was)
    assert not errors and not any(t.is_alive() for t in threads)
    for t in range(2):
        assert results[t] == [[i != (t + rnd) % 8 for i in range(8)]
                              for rnd in range(6)]
    verifier.close()


@pytest.mark.parametrize("broken", ["put", "build"])
def test_a_fault_while_a_key_gets_its_table_leaves_the_tables_whole(
        swcsp, monkeypatch, broken):
    """The put of the tables' array is a device transfer and can fail
    like any: that batch degrades to software, and the next one must
    not run the new key's lanes against an array that lacks its table
    (valid signatures refused, or a signature of the key that held
    the slot accepted under the new key's name)."""
    from fabric_mod_tpu import faults
    from fabric_mod_tpu.ops import p256
    verifier = tpu.TpuVerifier(cache_size=0,
                               fallback=swcsp.verify_batch)
    tables = verifier._tables
    others = [swcsp.key_gen("P256").public_xy()
              for _ in range(tpu.KEY_SLOTS - 2)]
    for i in range(0, len(others), tpu.NEW_TABLES_PER_BATCH):
        tables.assign(others[i:i + tpu.NEW_TABLES_PER_BATCH])
    a, b, c = (swcsp.key_gen("P256") for _ in range(3))
    old = _keyed_items(swcsp, [a, b], 4, tamper={1})
    assert list(verifier.verify_many(old)) == swcsp.verify_batch(old)
    tables.assign(others)                   # a is used longest ago now
    # c met once with the slots full: the ladder; then it is due a's slot
    new = _keyed_items(swcsp, [c, b], 4, tamper={2})
    assert list(verifier.verify_many(new)) == swcsp.verify_batch(new)
    fallbacks = _counter("fabric_bccsp_sw_fallback_batches_total")
    if broken == "put":
        with faults.active(faults.FaultPlan().add(
                "bccsp.device.tables", nth=1, kind="device")):
            got = verifier.verify_many(new)
        assert _counter("fabric_bccsp_sw_fallback_batches_total") \
            == fallbacks + 1
    else:
        def no_table(x, y):
            raise MemoryError("no table today")
        with monkeypatch.context() as m:
            m.setattr(p256, "key_table", no_table)
            with pytest.raises(MemoryError):
                verifier.verify_many(new)
        got = verifier.verify_many(new)
    assert list(got) == swcsp.verify_batch(new)
    # c's lanes under c's table, and a signature BY a under c's name
    # (the slot was a's) refused like any other
    forged = [VerifyItem(it.digest, it.signature, c.public_xy())
              for it in _keyed_items(swcsp, [a], 2)]
    again = new + forged
    resolve = verifier.verify_many_async(again)
    assert list(resolve()) == swcsp.verify_batch(again) \
        == [True, True, False, True, False, False]
    assert (resolve.table_lanes, resolve.ladder_lanes) == (6, 0)
    assert a.public_xy() not in tables._slot_of
    assert len(tables._free) + len(tables._slot_of) == tpu.KEY_SLOTS
    verifier.close()


def test_warm_runs_both_programs_at_the_items_bucket(swcsp):
    """A process that serves loads the ladder too before it serves:
    which program a lane takes depends on the signers traffic brings."""
    verifier = tpu.TpuVerifier(cache_size=0)
    calls = []
    device_call = verifier._device_call

    def spy(items, tabled):
        calls.append((len(items), tabled is None))
        return device_call(items, tabled)
    verifier._device_call = spy
    verifier.warm(_make_items(swcsp, 8))
    assert calls == [(8, False), (8, True)]
    verifier.close()
