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
5. The collector's pauses: armed, every collection is a span
   `gc_pause` nested under the span open on its thread; unarmed,
   `gc.callbacks` holds nothing of the tracer's; a collection inside
   the recorder's or a histogram's lock neither deadlocks nor is lost.
   (Any armed test may see a pause: the others filter `gc_pause` out
   where they count spans or children.)
"""
import collections
import gc
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
    spans = [s for s in tracing.recorder().recent_spans()
             if s["name"] != "gc_pause"]
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
        (got,) = [s for s in tracing.recorder().recent_spans()
                  if s["name"] == "timed"]
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
    ring = tracing.recorder().recent_spans()
    spans = {s["name"]: s for s in ring}
    assert spans["mvcc"]["dur"] >= 0.02
    # less only what collected on this thread inside it
    paused = sum(s["dur"] for s in ring if s["name"] == "gc_pause"
                 and s["parent_id"] == spans["unpack"]["span_id"])
    assert spans["unpack"]["self"] == pytest.approx(
        spans["unpack"]["dur"] - paused, abs=1e-5)


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
        return {c["name"] for c in spans if c["parent_id"] == s["span_id"]
                and c["name"] != "gc_pause"}

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
    assert recv["self"] == pytest.approx(recv["dur"] - sum(
        c["dur"] for c in spans if c["parent_id"] == recv["span_id"]),
        abs=1e-5)
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


# ---------------------------------------------------------------------------
# 5. the collector's pauses (`gc_pause`)
# ---------------------------------------------------------------------------

def _full_pauses(ring):
    return [s for s in ring
            if s["name"] == "gc_pause" and s["attrs"]["generation"] == 2]


def test_a_collection_inside_a_span_is_a_gc_pause_nested_under_it():
    hist = tracing._substage_hist().with_labels("gc_pause")
    observed = hist.count
    with tracing.active():
        with tracing.span("unpack") as parent:
            gc.collect()
        # nothing drained it yet but the span's own exit: a collection
        # outside every span waits in the inbox for `totals()`
        gc.collect()
        assert tracing.recorder()._pauses
        totals = tracing.recorder().totals()
        assert not tracing.recorder()._pauses
    ring = tracing.recorder().recent_spans(limit=1 << 20)
    (inside,) = [p for p in _full_pauses(ring)
                 if p["parent_id"] == parent.span_id]
    assert inside["trace_id"] == parent.trace_id
    # (an automatic full collection would be another: rare, harmless)
    outside = [p for p in _full_pauses(ring) if p["parent_id"] is None][-1]
    for p in (inside, outside):
        assert p["thread"] == threading.current_thread().name
        assert set(p["attrs"]) == {"generation", "collected",
                                   "uncollectable"}
        assert p["self"] == p["dur"] and 0 <= p["cpu"]
    assert parent.ts <= inside["ts"]
    assert inside["ts"] + inside["dur"] <= parent.ts + parent.dur + 1e-6
    # the pause is no longer the parent's own time
    nested = sum(s["dur"] for s in ring
                 if s["parent_id"] == parent.span_id)
    assert nested >= inside["dur"]
    assert parent.self_dur == pytest.approx(parent.dur - nested, abs=1e-5)
    assert totals["gc_pause"]["count"] == len(
        [s for s in ring if s["name"] == "gc_pause"])
    assert totals["gc_pause"]["secs"] >= inside["dur"] + outside["dur"] \
        - 1e-5
    assert hist.count - observed == totals["gc_pause"]["count"]


def test_a_pause_joins_the_block_timeline_of_its_thread():
    with tracing.active():
        tl = tracing.start_timeline("deliver", 5)
        with tracing.timeline_scope(tl):
            with tracing.span("mvcc"):
                gc.collect()
        tracing.finish_timeline(tl)
    (got,) = tracing.recorder().timelines()
    names = [s["name"] for s in got["subs"]]
    assert "gc_pause" in names and names[-1] == "mvcc"


def test_a_pause_charges_only_what_lies_inside_its_parent():
    """A closing span (its `dur` set, not yet popped) keeps the part of
    a pause after its end; self time never goes below zero."""
    now = [10.0]
    tracing.set_clock(lambda: now[0])
    try:
        with tracing.active():
            with tracing.span("unpack") as sp:
                now[0] += 1.0
                sp.dur = 1.0            # as `__exit__` sets it first
                tracing._on_gc("start", {"generation": 0})
                now[0] += 0.5
                tracing._on_gc("stop", {"generation": 0, "collected": 0,
                                        "uncollectable": 0})
                assert sp._child == 0.0
                sp.dur = 0.0
                tracing._on_gc("start", {"generation": 1})
                now[0] += 5.0
                tracing._on_gc("stop", {"generation": 1, "collected": 3,
                                        "uncollectable": 0})
                assert sp._child == pytest.approx(5.0)
    finally:
        tracing.set_clock(time.time)
    spans = tracing.recorder().recent_spans()
    (unpack,) = [s for s in spans if s["name"] == "unpack"]
    assert unpack["dur"] == pytest.approx(6.5)
    assert unpack["self"] == pytest.approx(1.5)
    assert sorted(s["attrs"]["generation"] for s in spans
                  if s["name"] == "gc_pause" and s["ts"] >= 10.0) == [0, 1]


@pytest.mark.parametrize("armed_at_import", [False, True])
def test_the_hook_is_in_gc_callbacks_exactly_while_armed(armed_at_import):
    code = ("import gc\n"
            "before = list(gc.callbacks)\n"
            "from fabric_mod_tpu.observability import tracing\n"
            "hooked = [cb for cb in gc.callbacks if cb not in before]\n"
            f"assert hooked == ([tracing._on_gc] if {armed_at_import} "
            "else []), hooked\n"
            "tracing.enable(False)\n"
            "assert gc.callbacks == before\n")
    env = dict(os.environ)
    env.pop("FMT_TRACE", None)
    if armed_at_import:
        env["FMT_TRACE"] = "1"
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    # in this process: once when armed, however often armed; gone when
    # disarmed, by `enable` and by `active`'s exit alike
    hooked = lambda: gc.callbacks.count(tracing._on_gc)  # noqa: E731
    assert hooked() == 0
    tracing.enable(True)
    tracing.enable(True)
    assert hooked() == 1
    tracing.enable(False)
    assert hooked() == 0
    with tracing.active():
        assert hooked() == 1
        with tracing.active(False):
            assert hooked() == 0
        assert hooked() == 1
    assert hooked() == 0
    gc.collect()
    assert "gc_pause" not in tracing.recorder().totals()
    assert tracing.recorder().span_count() == 0


class _CollectOnce:
    """A seam that forces one full collection while its lock is held."""

    def __init__(self):
        self._lock = threading.Lock()
        self.left = 1
        self.seen = []

    def collect_locked(self):
        if self.left:
            self.left -= 1
            gc.collect()


class _CollectingDeque(collections.deque):
    seam = None

    def append(self, item):
        self.seam.collect_locked()     # under the recorder's lock
        super().append(item)


class _CollectingHist(_CollectOnce):
    def with_labels(self, *values):
        return self

    def observe(self, value):
        with self._lock:
            self.collect_locked()
            self.seen.append(value)


@pytest.mark.parametrize("where", ["recorder", "histogram"])
def test_a_collection_inside_a_critical_section_is_kept(where,
                                                        monkeypatch):
    rec = tracing.recorder()
    if where == "recorder":
        seam = _CollectOnce()
        ring = _CollectingDeque(rec._spans, maxlen=rec._spans.maxlen)
        ring.seam = seam
        monkeypatch.setattr(rec, "_spans", ring)
    else:
        seam = _CollectingHist()
        monkeypatch.setattr(tracing, "_substage_hist", lambda: seam)

    def work():
        with tracing.active():
            with tracing.span("mvcc"):
                pass

    worker = threading.Thread(target=work, name="gc-seam")
    worker.start()
    worker.join(30.0)
    assert not worker.is_alive(), "a collection under a lock deadlocked"
    assert seam.left == 0
    pause = [p for p in _full_pauses(rec.recent_spans(limit=1 << 20))
             if p["thread"] == "gc-seam"][0]
    assert rec.totals()["gc_pause"]["count"] >= 1
    if where == "histogram":
        assert any(abs(d - pause["dur"]) <= 1e-6 for d in seam.seen)


def test_a_collection_at_every_allocation_loses_no_pause():
    """Threshold 1: collections inside every seam of the recorder, on
    more threads than cores switching every 10 us; every collection the
    interpreter reports is a span."""
    stops = []

    def count(phase, info):
        if phase == "stop":
            stops.append(info["generation"])

    def work(n):
        for i in range(n):
            with tracing.span("unpack", block=i):
                with tracing.span("mvcc"):
                    pass

    was_enabled, threshold = gc.isenabled(), gc.get_threshold()
    switch = sys.getswitchinterval()
    with tracing.active():
        gc.disable()
        before = tracing.recorder().totals().get(
            "gc_pause", {"count": 0})["count"]
        gc.callbacks.append(count)
        gc.set_threshold(1, 10, 1000)
        sys.setswitchinterval(1e-5)
        gc.enable()
        try:
            workers = [threading.Thread(target=work, args=(8,))
                       for _ in range((os.cpu_count() or 1) + 2)]
            for t in workers:
                t.start()
            for t in workers:
                t.join(60.0)
            assert not any(t.is_alive() for t in workers)
        finally:
            gc.disable()
            sys.setswitchinterval(switch)
            gc.set_threshold(*threshold)
            gc.callbacks.remove(count)
        after = tracing.recorder().totals()["gc_pause"]["count"]
        if was_enabled:
            gc.enable()
    assert len(stops) > 100
    assert after - before == len(stops)


def test_the_callback_records_no_lock_order_edge():
    from fabric_mod_tpu import concurrency
    from fabric_mod_tpu.concurrency import RegisteredLock, lock_registry
    outer = RegisteredLock("test.gc.outer")
    with concurrency.armed():
        with tracing.active():
            with outer:
                edges = lock_registry().edge_count()
                gc.collect()
                assert lock_registry().edge_count() == edges
    assert _full_pauses(tracing.recorder().recent_spans())


def _layer_metric(name):
    """(spec, reduce) of one of the two metrics of `gc_pause`, with its
    entry in `BENCHMARK.json` checked."""
    from benchmarks import manifest
    bench = manifest.benchmark_json()
    (entry,) = [p for p in bench["per_layer"] if p["name"] == name]
    assert entry["moves"] == "committed_tx_s"
    assert entry["layer"] == "interpreter"
    assert "workloads" not in entry              # every cell
    return manifest.reducer_for(name)


def test_gc_pause_ms_per_block_reads_the_recorded_pauses():
    import dataclasses
    spec, reduce_fn = _layer_metric("gc_pause_ms_per_block")
    assert spec["spans"] == ["gc_pause"]

    @dataclasses.dataclass
    class Window:
        blocks: int
        txs: int
        span_secs: dict
        span_counts: dict

    with tracing.active():
        t0 = tracing.recorder().totals()
        for _ in range(3):
            gc.collect()
        t1 = tracing.recorder().totals()

    def delta(key):
        return {n: t1[n][key] - t0.get(n, {key: 0})[key] for n in t1}
    window = Window(4, 2000, delta("secs"), delta("count"))
    assert window.span_counts["gc_pause"] >= 3
    assert reduce_fn(spec, window) == pytest.approx(
        1e3 * window.span_secs["gc_pause"] / 4)
    # a program that records no pause (the parent): no number, no raise
    assert reduce_fn(spec, Window(4, 2000, {}, {})) is None


def test_idle_under_gc_pct_reads_a_recorded_ring_beside_made_up_programs(
        monkeypatch):
    """Two programs sent by two `device_enqueue` spans, an `unpack`
    between them with one full collection in it: of the idle time, the
    pause is `gc_pause`'s and no longer `unpack`'s."""
    import types
    from benchmarks import manifest, timeline
    from benchmarks.reducers import idle_under
    spec, reduce_fn = _layer_metric("idle_under_gc_pct")
    unpack_spec, _ = manifest.reducer_for("idle_under_unpack_pct")
    with tracing.active():
        with tracing.span("device_enqueue"):
            time.sleep(0.001)
        with tracing.span("unpack"):
            time.sleep(0.005)
            gc.collect()
            time.sleep(0.005)
        with tracing.span("device_enqueue"):
            time.sleep(0.001)
    ring = tracing.recorder().recent_spans(limit=1 << 20)
    enq = [s for s in ring if s["name"] == "device_enqueue"]
    (unpack,) = [s for s in ring if s["name"] == "unpack"]
    (pause,) = _full_pauses(ring)
    start_ns = enq[0]["ts"] * 1e9 - 1e6
    on_trace = lambda ts: ts * 1e9 - start_ns  # noqa: E731
    programs = [("jit__verify_core_tables_impl", on_trace(s["ts"]) + 1e5,
                 on_trace(s["ts"]) + 5e5) for s in enq]
    monkeypatch.setattr(timeline, "find_session_xplane",
                        lambda since: "made-up")
    monkeypatch.setattr(timeline, "read_session", lambda path: (
        programs, (start_ns, on_trace(enq[-1]["ts"]) * 2 + start_ns),
        None))
    window_s = (programs[-1][2] - programs[0][1]) / 1e9
    window = types.SimpleNamespace(
        trace=types.SimpleNamespace(window_s=window_s))
    idle_under.view_of_run.cache_clear()
    try:
        gc_pct = reduce_fn(spec, window)
        unpack_pct = reduce_fn(unpack_spec, window)
        # the harness's arithmetic by hand: the stretch is the two
        # programs and what lies between; the pause is all idle
        assert gc_pct == pytest.approx(
            100.0 * pause["dur"] / window_s, rel=0.02, abs=0.05)
        assert unpack_pct == pytest.approx(
            100.0 * (unpack["dur"] - pause["dur"]) / window_s,
            rel=0.02, abs=0.05)
        # a span the run never recorded: nothing
        assert reduce_fn(dict(spec, spans=["no_such_span"]), window) \
            is None
    finally:
        idle_under.view_of_run.cache_clear()

