"""The tracing + flight-recorder layer (observability/tracing.py).

Contract under test, in order of importance:

1. FMT_TRACE unset is a BEHAVIORAL no-op: span() returns one shared
   no-op singleton (zero allocation), nothing lands in the recorder,
   and a commit-path run produces byte-identical verdicts + state
   fingerprints to an armed run.
2. Context propagates across the real async seams: the
   BatchingVerifyService GuardedQueue handoff (submit -> flusher) and
   Future resolution (flusher -> resolver), the commitpipe
   stage->commit handoff (StagedBlock carries its timeline), and —
   slow-marked — broadcast across OS processes via the gRPC metadata
   carrier.
3. The flight-recorder ring is bounded under sustained load, and the
   Chrome trace-event export is schema-valid (Perfetto-loadable).
4. One timeline: a span keeps self time and thread CPU time, the
   pipeline's waits are spans (`spannames.WAIT_SPANS`), the provider's
   spans nest under the seam that called them, and an armed span's
   `ts` lies on the clock of an open `jax.profiler` session.
"""
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from fabric_mod_tpu.observability import tracing


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Every test starts from an empty recorder and an unarmed gate
    (the suite may run with FMT_TRACE exported — the armed-lane smoke
    slice does exactly that — so save/restore, don't assume)."""
    prev = tracing.armed()
    tracing.enable(False)
    tracing.recorder().reset()
    yield
    tracing.enable(prev)
    tracing.recorder().reset()


# ---------------------------------------------------------------------------
# 1. unarmed: the zero-cost contract
# ---------------------------------------------------------------------------

def test_unarmed_span_is_shared_noop_singleton():
    s1 = tracing.span("a", block=1)
    s2 = tracing.span("b")
    assert s1 is s2                        # no allocation, one object
    with s1 as got:
        assert got is s1
        got.set(anything="goes")           # no-op surface
    assert tracing.recorder().span_count() == 0
    assert tracing.current_ctx() is None
    assert tracing.start_timeline("c", 0) is None
    tracing.finish_timeline(None)          # no-op, no raise
    with tracing.timeline_scope(None):
        pass
    assert tracing.recorder().timeline_count() == 0
    # note_event/auto_dump are flag reads when unarmed
    tracing.note_event("k", "d")
    tracing.auto_dump("r")
    assert tracing.recorder().events() == []
    assert tracing.recorder().dumps() == []
    assert tracing.inject() is None


def test_armed_span_nesting_parents_and_ring():
    with tracing.active():
        with tracing.span("parent", block=3) as p:
            ctx = tracing.current_ctx()
            assert ctx == p.ctx
            with tracing.span("child") as c:
                assert c.trace_id == p.trace_id
                assert c.parent_id == p.span_id
        assert tracing.current_ctx() is None
    spans = tracing.recorder().recent_spans()
    assert [s["name"] for s in spans] == ["child", "parent"]
    assert spans[0]["parent_id"] == spans[1]["span_id"]
    # per-name totals accumulated (the bench attribution surface)
    totals = tracing.substage_totals()
    assert totals["parent"]["count"] == 1
    # explicit cross-thread parenting via the carrier
    with tracing.active():
        with tracing.span("grand") as g:
            carrier = g.ctx
        with tracing.span("adopted", parent=carrier) as a:
            assert a.trace_id == carrier.trace_id


def test_injectable_clock_drives_span_durations():
    class FakeClock:
        t = 100.0

        def __call__(self):
            return self.t

    clk = FakeClock()
    tracing.set_clock(clk)
    try:
        with tracing.active():
            with tracing.span("timed"):
                clk.t += 2.5
        got = tracing.recorder().recent_spans()[-1]
        assert got["dur"] == pytest.approx(2.5)
        assert got["ts"] == pytest.approx(100.0)
    finally:
        tracing.set_clock(time.time)


def test_inject_extract_roundtrip_and_malformed():
    with tracing.active():
        with tracing.span("root") as r:
            md = tracing.inject()
            assert md == [(tracing.TRACE_METADATA_KEY,
                           f"{r.trace_id}-{r.span_id}")]
            got = tracing.extract(md)
            assert got == r.ctx
    assert tracing.extract(None) is None
    assert tracing.extract([("other", "x")]) is None
    assert tracing.extract([(tracing.TRACE_METADATA_KEY, "garbage")]) \
        is None
    assert tracing.extract(object()) is None   # never raises


# ---------------------------------------------------------------------------
# 2. propagation across the real async seams
# ---------------------------------------------------------------------------

def test_verify_service_propagates_ctx_through_queue_and_future():
    """submit() on the caller thread -> GuardedQueue -> flusher thread
    (verify.flush span) -> in-flight queue -> resolver thread
    (verify.resolve span): all three spans share ONE trace id, linked
    parent -> child across both handoffs."""
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import (BatchingVerifyService,
                                          FakeBatchVerifier)
    from fabric_mod_tpu.utils.fixtures import make_verify_items

    items, expect = make_verify_items(4, n_keys=2, seed=b"trace")
    svc = BatchingVerifyService(FakeBatchVerifier(SwCSP()),
                                deadline_s=0.001)
    try:
        with tracing.active():
            with tracing.span("client_submit") as root:
                got = svc.verify_many(items, timeout=60)
        assert [bool(v) for v in got] == [bool(e) for e in expect]
        spans = tracing.recorder().recent_spans()
        flushes = [s for s in spans if s["name"] == "verify.flush"]
        resolves = [s for s in spans if s["name"] == "verify.resolve"]
        assert flushes and resolves
        # every flush rode the submitter's trace, parented under it
        # (the deadline flusher may have split the items into several
        # batches — each one must stitch)
        assert all(s["trace_id"] == root.trace_id
                   and s["parent_id"] == root.span_id
                   for s in flushes)
        flush_ids = {s["span_id"] for s in flushes}
        assert all(s["trace_id"] == root.trace_id
                   and s["parent_id"] in flush_ids
                   for s in resolves)
    finally:
        svc.close()


def test_verify_service_unarmed_untraced():
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import (BatchingVerifyService,
                                          FakeBatchVerifier)
    from fabric_mod_tpu.utils.fixtures import make_verify_items

    items, expect = make_verify_items(3, n_keys=2, seed=b"untraced")
    svc = BatchingVerifyService(FakeBatchVerifier(SwCSP()),
                                deadline_s=0.001)
    try:
        got = svc.verify_many(items, timeout=60)
        assert [bool(v) for v in got] == [bool(e) for e in expect]
    finally:
        svc.close()
    assert tracing.recorder().span_count() == 0


@pytest.fixture(scope="module")
def commitpipe_world():
    import bench
    return bench._commitpipe_world(7, 2)


def _run_commitpipe(world, root, depth):
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.peer import (PipelinedCommitter,
                                     ValidatorCommitTarget)
    from fabric_mod_tpu.protos import messages as m

    blocks, make_committer, _barriers = world
    led, validator = make_committer(FakeBatchVerifier(SwCSP()),
                                    str(root))
    flags = []
    pipe = PipelinedCommitter(
        ValidatorCommitTarget(validator, led), depth=depth,
        on_commit=lambda _b, f: flags.append(list(f)))
    for raw in blocks:
        pipe.submit(m.Block.decode(raw))
    pipe.flush()
    pipe.close()
    return flags, led.state_fingerprint()


def test_commitpipe_armed_vs_unarmed_differential(commitpipe_world,
                                                  tmp_path):
    """The acceptance differential: FMT_TRACE armed produces byte-
    identical txflags + state fingerprint to unarmed, records one
    flight-recorder timeline per block carrying the named sub-stages,
    and unarmed records NOTHING."""
    off_flags, off_fp = _run_commitpipe(commitpipe_world,
                                        tmp_path / "off", 3)
    assert tracing.recorder().span_count() == 0
    assert tracing.recorder().timeline_count() == 0

    with tracing.active():
        on_flags, on_fp = _run_commitpipe(commitpipe_world,
                                          tmp_path / "on", 3)
    assert on_flags == off_flags
    assert on_fp == off_fp

    blocks, _mc, _b = commitpipe_world
    tls = tracing.recorder().timelines()
    assert len(tls) == len(blocks)         # one timeline per block
    assert [t["block"] for t in tls] == list(range(len(blocks)))
    # each timeline carries the stage-side AND commit-side sub-stages:
    # the StagedBlock carried it across the thread handoff
    for t in tls:
        names = {s["name"] for s in t["subs"]}
        assert {"unpack", "device_dispatch", "verdict_await",
                "policy_finish", "mvcc", "ledger_write"} <= names, \
            f"block {t['block']} timeline incomplete: {names}"
    # sub-stage totals cover the named commit-path split
    totals = tracing.substage_totals()
    for name in ("unpack", "verdict_await", "policy_finish", "mvcc",
                 "ledger_write"):
        assert totals[name]["count"] >= len(blocks)


def test_sync_committer_records_timeline(commitpipe_world, tmp_path):
    from fabric_mod_tpu.bccsp.sw import SwCSP
    from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
    from fabric_mod_tpu.peer import Committer
    from fabric_mod_tpu.protos import messages as m

    blocks, make_committer, _ = commitpipe_world
    led, validator = make_committer(FakeBatchVerifier(SwCSP()),
                                    str(tmp_path / "sync"))
    committer = Committer(validator, led)
    with tracing.active():
        committer.store_block(m.Block.decode(blocks[0]))
    tls = tracing.recorder().timelines()
    assert len(tls) == 1 and tls[0]["consumer"] == "sync"
    names = {s["name"] for s in tls[0]["subs"]}
    assert {"unpack", "verdict_await", "policy_finish", "mvcc",
            "ledger_write"} <= names


# ---------------------------------------------------------------------------
# 4. one timeline: self time, CPU time, the waits, the profiler's clock
# ---------------------------------------------------------------------------

def test_self_time_of_a_parent_with_two_children():
    now = [50.0]
    tracing.set_clock(lambda: now[0])
    try:
        with tracing.active():
            with tracing.span("device_dispatch"):
                now[0] += 1.0
                with tracing.span("der_marshal"):
                    now[0] += 3.0
                    with tracing.span("body_decode"):   # a grandchild
                        now[0] += 0.5
                now[0] += 1.0
                with tracing.span("device_enqueue"):
                    now[0] += 2.0
                now[0] += 1.5
    finally:
        tracing.set_clock(time.time)
    spans = {s["name"]: s for s in tracing.recorder().recent_spans()}
    assert spans["device_dispatch"]["dur"] == pytest.approx(9.0)
    # less its two children; the grandchild is the child's to lose
    assert spans["device_dispatch"]["self"] == pytest.approx(3.5)
    assert spans["der_marshal"]["self"] == pytest.approx(3.0)
    assert spans["device_enqueue"]["self"] == pytest.approx(2.0)
    totals = tracing.substage_totals()
    assert set(totals["device_dispatch"]) == {"secs", "count",
                                              "self_secs", "cpu_secs"}
    assert totals["device_dispatch"]["secs"] == pytest.approx(9.0)
    assert totals["device_dispatch"]["count"] == 1
    # the self times add up to the wall time: nothing twice
    assert sum(t["self_secs"] for t in totals.values()) == \
        pytest.approx(9.0)


def test_self_time_ignores_spans_of_other_threads():
    gate_in, gate_out = threading.Event(), threading.Event()

    def other():
        with tracing.span("mvcc"):
            gate_in.set()
            gate_out.wait(5.0)

    with tracing.active():
        with tracing.span("unpack"):
            t = threading.Thread(target=other)
            t.start()
            gate_in.wait(5.0)
            time.sleep(0.02)
            gate_out.set()
            t.join()
    spans = {s["name"]: s for s in tracing.recorder().recent_spans()}
    assert spans["mvcc"]["dur"] >= 0.02
    assert spans["unpack"]["self"] == pytest.approx(
        spans["unpack"]["dur"], abs=1e-6)


def test_cpu_time_of_a_busy_loop_and_of_a_sleep():
    with tracing.active():
        with tracing.span("unpack"):
            end = time.perf_counter() + 0.05
            while time.perf_counter() < end:
                pass
        with tracing.span("verdict_await"):
            time.sleep(0.05)
    totals = tracing.substage_totals()
    busy, parked = totals["unpack"], totals["verdict_await"]
    assert 0 < busy["cpu_secs"] <= busy["secs"] + 1e-3
    assert busy["cpu_secs"] > 0.5 * busy["secs"]
    assert parked["cpu_secs"] < 0.5 * parked["secs"]


def test_armed_commit_pipeline_records_its_waits(commitpipe_world,
                                                 tmp_path):
    from fabric_mod_tpu.observability import spannames
    waits = {"submit_wait", "stage_wait_block", "stage_wait_slot",
             "commit_wait_staged"}
    assert waits | {"verdict_await"} == spannames.WAIT_SPANS
    assert spannames.WAIT_SPANS <= spannames.DECLARED_SPANS
    with tracing.active():
        _run_commitpipe(commitpipe_world, tmp_path / "waits", 2)
    blocks, _mc, _b = commitpipe_world
    totals = tracing.substage_totals()
    for name in waits:
        assert totals[name]["count"] >= len(blocks), name
    spans = tracing.recorder().recent_spans(limit=1 << 20)
    by_thread = {}
    for s in spans:
        if s["name"] in waits:
            by_thread.setdefault(s["name"], set()).add(s["thread"])
    # each wait is recorded where the waiting happens: submit on the
    # caller's thread, the other three on the pipeline's two workers
    assert by_thread["stage_wait_block"] == by_thread["stage_wait_slot"]
    assert by_thread["commit_wait_staged"].isdisjoint(
        by_thread["stage_wait_block"])
    assert by_thread["submit_wait"] == {threading.current_thread().name}
    slot = [s for s in spans if s["name"] == "stage_wait_slot"]
    assert sorted(s["attrs"]["block"] for s in slot) == \
        list(range(len(blocks)))


def _software_ladder(d, r, s, qx, qy, mesh=None, lazy=False):
    """`p256.batch_verify`'s verdicts from OpenSSL: the provider's own
    marshal, enqueue and resolve seams run, the XLA compile does not."""
    import numpy as np
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, utils
    big = lambda row: int.from_bytes(bytes(row), "big")   # noqa: E731
    mask = np.zeros(len(d), bool)
    for i in range(len(d)):
        try:
            key = ec.EllipticCurvePublicNumbers(
                big(qx[i]), big(qy[i]), ec.SECP256R1()).public_key()
            key.verify(utils.encode_dss_signature(big(r[i]), big(s[i])),
                       bytes(d[i]),
                       ec.ECDSA(utils.Prehashed(hashes.SHA256())))
            mask[i] = True
        except (InvalidSignature, ValueError):
            pass                            # a pad lane, or a bad one
    return (lambda: mask) if lazy else mask


def test_provider_spans_nest_under_the_seam_that_called(monkeypatch):
    from fabric_mod_tpu import e2e
    from fabric_mod_tpu.bccsp.tpu import TpuVerifier
    from fabric_mod_tpu.ops import p256
    monkeypatch.setattr(p256, "batch_verify", _software_ladder)
    verifier = TpuVerifier(cache_size=0)
    try:
        with tracing.active():
            e2e.run_pipeline(6, verifier)
    finally:
        verifier.close()
    spans = tracing.recorder().recent_spans(limit=1 << 20)
    by_id = {s["span_id"]: s for s in spans}

    def parent_name(s):
        parent = by_id.get(s["parent_id"])
        return parent["name"] if parent else None

    def children(s):
        return {c["name"] for c in spans if c["parent_id"] == s["span_id"]}

    checks = [s for s in spans if s["name"] == "mcs_verify"]
    assert checks and all(parent_name(s) == "recv" for s in checks)
    # the host half of the gate only: no device call opens under it;
    # the block signature rides the block's own dispatch
    assert all(not children(s) for s in checks)
    dispatches = [s for s in spans if s["name"] == "device_dispatch"]
    assert dispatches and all(
        {"der_marshal", "device_enqueue"} <= children(s)
        for s in dispatches)
    assert all(s["attrs"]["block_sigs"] == 1 for s in dispatches)
    assert {s["attrs"]["block"] for s in dispatches} == \
        {s["attrs"]["block"] for s in checks}
    for s in spans:
        if s["name"] in ("der_marshal", "device_enqueue") \
                and s["thread"] == dispatches[0]["thread"]:
            assert parent_name(s) == "device_dispatch"
    enq = [s for s in spans if s["name"] == "device_enqueue"]
    assert all(s["attrs"]["bucket"] >= 8 for s in enq)
    # recv's self time is the pull: what is left beside the check
    recv = by_id[checks[0]["parent_id"]]
    assert recv["self"] == pytest.approx(
        recv["dur"] - checks[0]["dur"], abs=1e-5)
    for s in dispatches:
        nested = sum(c["dur"] for c in spans
                     if c["parent_id"] == s["span_id"])
        assert 0 < nested <= s["dur"]
        assert s["self"] == pytest.approx(s["dur"] - nested, abs=1e-5)


def test_armed_span_lies_on_a_profiler_sessions_clock(tmp_path):
    """A span's `ts` is the wall clock a `jax.profiler` session stamps
    its own start and stop with (plane `Task Environment`, ns since
    the epoch): `ts * 1e9 - profile_start_time` puts an armed span on
    the trace's clock, inside the session it ran under.  The session
    needs no host tracer for that, and the span leaves no event of
    its own in the trace."""
    import jax
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.active():
            with tracing.span("recv", block=41):
                with tracing.span("mcs_verify", block=41):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(found) == 1
    session, names = {}, set()
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name == "Task Environment":
            session = {k: v for k, v in plane.stats}
        for line in plane.lines:
            names |= {ev.name for ev in line.events}
    assert not names & {"recv", "mcs_verify"}
    length = session["profile_stop_time"] - session["profile_start_time"]
    assert 0 < length < 60e9
    spans = {s["name"]: s for s in tracing.recorder().recent_spans()}
    for name in ("recv", "mcs_verify"):
        start = spans[name]["ts"] * 1e9 - session["profile_start_time"]
        end = start + spans[name]["dur"] * 1e9
        assert 0 <= start <= end <= length, (name, start, end, length)
    assert spans["mcs_verify"]["attrs"]["block"] == 41


def test_tracing_module_load_imports_no_jax():
    code = ("import sys\n"
            "from fabric_mod_tpu.observability import tracing\n"
            "with tracing.span('unpack', block=1):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'unarmed span imported jax'\n")
    env = dict(os.environ)
    env.pop("FMT_TRACE", None)
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))


def test_one_shot_profile_window_is_gone_and_span_names_lint_clean():
    from fabric_mod_tpu.analysis import engine
    from fabric_mod_tpu.analysis.rules import SpanNameRule
    from fabric_mod_tpu.utils import knobs
    # (the names in halves: the tree is to hold them nowhere)
    with pytest.raises(KeyError, match="undeclared knob"):
        knobs.get_str("FMT_TRACE_" + "JAX_PROFILE")
    assert not hasattr(tracing, "device_profile_" + "capture")
    assert not hasattr(tracing, "jax_profile_dir")
    # both ways: every literal is declared, every declaration is used
    result = engine.run(rules=[SpanNameRule()], docs_check=False)
    assert [f.render() for f in result.findings
            if f.rule == "span-names"] == []


# ---------------------------------------------------------------------------
# 3. flight recorder + export + endpoints
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_under_sustained_load():
    with tracing.active():
        for i in range(tracing.FLIGHT_RING * 3):
            tl = tracing.start_timeline("load", i)
            with tracing.timeline_scope(tl):
                with tracing.span("unpack"):
                    pass
            tracing.finish_timeline(tl)
    rec = tracing.recorder()
    assert rec.timeline_count() == tracing.FLIGHT_RING
    got = rec.timelines()
    # the ring keeps the NEWEST timelines
    assert got[-1]["block"] == tracing.FLIGHT_RING * 3 - 1
    assert got[0]["block"] == tracing.FLIGHT_RING * 2
    # span ring bounded too
    assert rec.span_count() <= tracing.SPAN_RING


def test_auto_dump_and_fault_breadcrumbs():
    from fabric_mod_tpu import faults

    with tracing.active():
        plan = faults.FaultPlan().add("trace.test.point", mode="drop")
        with faults.active(plan):
            assert faults.point("trace.test.point") is True
        events = tracing.recorder().events()
        assert any(e["kind"] == "fault"
                   and "trace.test.point" in e["detail"]
                   for e in events)
        assert tracing.recorder().dumps()  # the fault auto-dumped


def test_soak_error_attaches_flight_dump():
    from fabric_mod_tpu.soak.invariants import SoakError

    with tracing.active():
        tl = tracing.start_timeline("deliver", 42)
        with tracing.timeline_scope(tl):
            with tracing.span("mvcc"):
                pass
        tracing.finish_timeline(tl)
        err = SoakError("convergence failed")
        text = str(err)
        assert "flight recorder" in text
        assert "block 42" in text and "mvcc=" in text
    # unarmed: the message stays the PR 8 shape
    err = SoakError("convergence failed")
    assert "flight recorder" not in str(err)


def test_chrome_trace_export_schema(tmp_path):
    with tracing.active():
        with tracing.span("unpack", block=1):
            with tracing.span("device_dispatch", items=8):
                pass
    out = tmp_path / "trace.json"
    n = tracing.export_chrome_trace(str(out))
    assert n >= 4                          # 2 spans + async pair + meta
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert {"ph", "pid", "tid", "name"} <= set(ev)
        assert ev["ph"] in ("X", "b", "e", "M")
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["dur"], (int, float))
            assert ev["dur"] >= 0
    # device dispatches exported as matched async begin/end slices
    begins = [e for e in events if e["ph"] == "b"]
    ends = [e for e in events if e["ph"] == "e"]
    assert len(begins) == 1 and len(ends) == 1
    assert begins[0]["id"] == ends[0]["id"]
    assert begins[0]["cat"] == "device"
    assert doc["otherData"]["xla_compiles"] >= 0


def test_ops_server_trace_and_flight_endpoints():
    from fabric_mod_tpu.observability import (HealthRegistry,
                                              MetricsProvider,
                                              OperationsServer)

    with tracing.active():
        with tracing.span("unpack", block=9) as sp:
            trace_id = sp.trace_id
        tl = tracing.start_timeline("deliver", 9)
        tracing.finish_timeline(tl)
        srv = OperationsServer(provider=MetricsProvider(),
                               health=HealthRegistry())
        srv.start()
        host, port = srv.addr
        base = f"http://{host}:{port}"
        try:
            doc = json.load(urllib.request.urlopen(base + "/trace"))
            assert doc["armed"] is True
            assert any(s["name"] == "unpack" for s in doc["spans"])
            filt = json.load(urllib.request.urlopen(
                base + f"/trace?trace_id={trace_id}&limit=10"))
            assert filt["spans"]
            assert all(s["trace_id"] == trace_id
                       for s in filt["spans"])
            flight = json.load(urllib.request.urlopen(base + "/flight"))
            assert flight["armed"] is True
            assert any(t["block"] == 9 for t in flight["timelines"])
            assert "totals" in flight and "unpack" in flight["totals"]
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# 4. cross-process stitching (procnet, slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_procnet_broadcast_trace_stitches_across_processes(tmp_path,
                                                           monkeypatch):
    """FMT_TRACE armed in BOTH the client (this process) and the
    orderer processes: the broadcast client injects its trace context
    as gRPC stream metadata, the orderer's broadcast handler parents
    its spans under it, and the orderer's /trace endpoint serves spans
    carrying the CLIENT's trace id — one stitched trace across the
    process boundary."""
    from tests.test_procnet import ProcNet, _wait

    monkeypatch.setenv("FMT_TRACE", "1")   # inherited by spawned nodes
    net = ProcNet(tmp_path)
    try:
        net.start_all()
        assert _wait(net.leader_known_by_all, t=90)
        with tracing.active():
            with tracing.span("client_tx") as root:
                net.submit_txs(net.leader(), 0, 3)
            trace_id = root.trace_id
        assert net.peer_caught_up("p0")

        def orderer_saw_trace():
            for oid in net.o_ids:
                try:
                    doc = json.load(urllib.request.urlopen(
                        f"http://127.0.0.1:{net.oops[oid]}"
                        f"/trace?trace_id={trace_id}", timeout=2))
                except Exception:
                    continue
                if any(s["name"] == "broadcast.handle"
                       for s in doc["spans"]):
                    return True
            return False
        assert _wait(orderer_saw_trace, t=30), \
            "no orderer served broadcast.handle spans under the " \
            "client's trace id"
        # the peer side records commit timelines of its own (the
        # deliver consumer's flight recorder)
        def peer_flight():
            doc = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{net.pops['p0']}/flight",
                timeout=2))
            return bool(doc["timelines"])
        assert _wait(peer_flight, t=30)
    finally:
        net.teardown()
