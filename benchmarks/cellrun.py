"""One run of one cell: set-up, the measured window, the comparison.

`run.py` looks for the chip and calls `run_cell`; the tests call
`run_cell` without that look, with the timed path broken underneath.

What the program gives: the system under test (`peer.Channel` driven
by `DeliverClient.run`, with `TpuVerifier` and its device programs),
its spans (`tracing.recorder().totals()`), its counters and the names
the profiler's trace gives its kernels.  Everything else is the
benchmark's: traffic, clocks, the trace's reduction, the reference and
the comparison.
"""
import dataclasses
import gc
import inspect
import os
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks import reference
from benchmarks.manifest import Cell, reducer_for
from benchmarks.reducers import idle_under


class RunFailure(RuntimeError):
    """The run cannot produce a result line at all."""


def find_chip(chips: int) -> Optional[dict]:
    """The device as JAX reports it, or None unless it is a TPU with
    at least `chips` chips.  Nothing is measured off the chip."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"benchmarks: {chips} TPU chip(s) needed; jax reports "
              f"{device}. Nothing is measured off the chip.",
              file=sys.stderr)
        return None
    return device


def refuse_fallback(items):
    raise RunFailure(
        f"the device verifier fell back to software for {len(items)} "
        f"item(s): a device error or an open circuit")


def metric_value(name: str, absent: Optional[float] = None) -> float:
    """One sample of the process's own /metrics exposition.  The
    device verifier registers its counters when it is built; a test's
    stand-in verifier has none (`absent`)."""
    from fabric_mod_tpu.observability.metrics import default_provider
    for line in default_provider().render_prometheus().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    if absent is None:
        raise RunFailure(f"counter {name} is not exposed")
    return absent


def closing_rule(block, cfg) -> str:
    """Which of the cutter's rules closed this block, from what it
    holds; the timer is what is left when neither limit was hit."""
    n = len(block.data.data)
    if n >= cfg.max_message_count:
        return "count"
    size = sum(len(d) for d in block.data.data)
    if size + max(len(d) for d in block.data.data) > cfg.preferred_max_bytes:
        return "bytes"
    return "timer"


class Stamps:
    """`on_commit` of the peer under test: the benchmark's clock.

    The window opens at the commit event of the last warm-up block
    (t0) and closes at the first commit event at or after
    t0 + seconds (t1).  At both events the program's span totals and
    compile count are copied, in the committing thread, so the deltas
    belong to the window exactly."""

    def __init__(self, warm_last: int, seconds: float, snapshot: Callable):
        self.warm_last = warm_last
        self.seconds = seconds
        self.snapshot = snapshot
        self.events: List[tuple] = []      # (number, n_txs, perf_counter)
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self.snap0 = self.snap1 = None
        self.cv = threading.Condition()

    def __call__(self, block) -> None:
        now = time.perf_counter()
        num = block.header.number
        with self.cv:
            self.events.append((num, len(block.data.data), now))
            if self.t0 is None:
                if num == self.warm_last:
                    self.t0, self.snap0 = now, self.snapshot()
            elif self.t1 is None and now >= self.t0 + self.seconds:
                self.t1, self.snap1 = now, self.snapshot()
            self.cv.notify_all()

    def close_dry(self) -> None:
        """The backlog ran out before t0 + seconds: the window ends
        at the last commit event."""
        with self.cv:
            if self.t1 is None:
                self.t1, self.snap1 = self.events[-1][2], self.snapshot()

    def wait(self, pred: Callable[[], bool], timeout_s: float) -> bool:
        with self.cv:
            return self.cv.wait_for(pred, timeout=timeout_s)

    def window_events(self) -> List[tuple]:
        return [e for e in self.events if self.t0 < e[2] <= self.t1]


@dataclasses.dataclass
class Window:
    """What the reducers of per-layer metrics read."""
    seconds: float
    blocks: int
    txs: int
    span_secs: Dict[str, float]
    span_counts: Dict[str, int]
    dispatches: List[tuple]            # (items, bucket) of the window
    trace: object = None               # reduce.TraceSummary | None
    session: object = None             # timeline.Session | None
    ring: List[dict] = dataclasses.field(default_factory=list)
    device_kind: str = ""


def warm_bucket(verifier, bucket: int, seed: int, strict: bool, say) -> None:
    """One direct call at this bucket, checked against the fixture's
    expectation; the first call loads (or compiles) the program."""
    from fabric_mod_tpu.utils.fixtures import make_verify_items
    items, expect = make_verify_items(
        bucket, n_keys=16, invalid_every=8, seed=b"bench-%d" % seed)
    t0 = time.perf_counter()
    got = np.asarray(verifier.verify_many(items), bool)
    dt = time.perf_counter() - t0
    wrong = int((got != np.asarray(expect, bool)).sum())
    say(f"warm bucket {bucket}: first call {dt:.2f}s, {wrong} lane(s) "
        f"differ from the fixture")
    if wrong and strict:
        raise RunFailure(
            f"bucket {bucket}: {wrong} device verdict(s) differ from the "
            f"fixture's expectation while warming")


def real_items_of(tx_facts) -> int:
    """Signatures a block's validation verifies: the creator's and each
    endorsement's, per transaction."""
    return sum(1 + len(t.endorsements) for t in tx_facts)


def buckets_reached(items: int, buckets) -> List[int]:
    """The device programs one batch of `items` signatures runs, as
    `bccsp/tpu.py` reckons: chunks of the widest bucket, each in the
    least bucket that holds it."""
    chunks = [min(buckets[-1], items - i)
              for i in range(0, items, buckets[-1])]
    return [min(b for b in buckets if b >= n) for n in chunks]


def network_arguments(cell: Cell, network_class) -> dict:
    """What the configuration's file hands to `e2e.Network`: the block
    settings and, verbatim, its `network`.  A key the program's class
    does not take stops the run here, before set-up: that setting
    needs a change to the program first."""
    settings = cell.config["settings"]
    args = {"max_message_count": int(settings["max_message_count"]),
            "batch_timeout": settings["batch_timeout"]}
    taken = set(inspect.signature(network_class.__init__).parameters) \
        - {"self", "root_dir"} - set(args)
    for key, value in cell.config.get("network", {}).items():
        if key not in taken:
            raise RunFailure(
                f"configuration {cell.entry['config']!r}: `network` has "
                f"the key {key!r}, which the program's e2e.Network does "
                f"not take (it takes {sorted(taken)}): a setting the "
                f"program cannot pass needs a change to the program first")
        args[key] = value
    return args


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             device: dict, say, t_start: float,
             make_verifier: Optional[Callable] = None,
             strict_warm: bool = True,
             wrap_channel: Optional[Callable] = None) -> dict:
    """Returns the result object (the last line of standard output).

    `make_verifier()` builds the verifier of the peer under test; the
    default is the device verifier with a fallback that raises.
    `wrap_channel(channel)` lets a test break the timed path."""
    import jax
    from fabric_mod_tpu import concurrency, e2e
    from fabric_mod_tpu.bccsp.tpu import BUCKETS, TpuVerifier
    from fabric_mod_tpu.channelconfig import Bundle
    from fabric_mod_tpu.channelconfig.configtx import config_from_block
    from fabric_mod_tpu.ledger.kvledger import KvLedger, LedgerManager
    from fabric_mod_tpu.observability import tracing
    from fabric_mod_tpu.orderer import DeliverService
    from fabric_mod_tpu.peer.channel import Channel
    from fabric_mod_tpu.peer.deliverclient import DeliverClient
    from fabric_mod_tpu.protos import protoutil

    network_args = network_arguments(cell, e2e.Network)
    block_txs = network_args["max_message_count"]
    warm_buckets = [int(b) for b in cell.file["warm_buckets"]]
    tracing.install_compile_counter()
    verifier = (make_verifier or (lambda: TpuVerifier(
        fallback=refuse_fallback)))()

    with tempfile.TemporaryDirectory(prefix="bench-") as root:
        net = e2e.Network(os.path.join(root, "net"), **network_args)
        dev_mgr = LedgerManager(os.path.join(root, "dev-peer"))
        # the software network's own threads (its consenter loop) live
        # until `net.close()`; what the peer under test leaves is more
        live_at_rest = set(concurrency.live_registered())
        try:
            # -- set-up: the device programs load (a thread each: the
            # load is native code) while this thread makes the backlog
            warm_error: List[BaseException] = []

            def warm(bucket: int) -> None:
                try:
                    warm_bucket(verifier, bucket, seed, strict_warm, say)
                except BaseException as e:      # re-raised below
                    warm_error.append(e)

            warmers = [threading.Thread(target=warm, args=(b,),
                                        name=f"bench-warm-{b}")
                       for b in warm_buckets]
            for t in warmers:
                t.start()
            try:
                backlog = cell.generator().provision(
                    net, cell.params, seed, seconds, say)
            finally:
                for t in warmers:
                    t.join()
            if warm_error:
                raise warm_error[0]
            compiles_warm = tracing.compile_count()

            # -- the peer under test: its own ledger over the same
            # genesis, the device verifier, pulling from the orderer
            csp = net.csp
            _, config = config_from_block(net.genesis_block)
            dev_ledger = dev_mgr.create_or_open(net.channel_id)
            channel = Channel(net.channel_id, dev_ledger, verifier,
                              Bundle(net.channel_id, config, csp), csp)
            channel.init_from_genesis(net.genesis_block)
            if wrap_channel is not None:
                wrap_channel(channel)

            def snapshot():
                return (tracing.recorder().totals() if traced else {},
                        tracing.compile_count(), time.time())

            stamps = Stamps(backlog.warm_blocks, seconds, snapshot)
            client = DeliverClient(channel, DeliverService(net.support),
                                   on_commit=stamps)
            peer_error: List[BaseException] = []

            def pull() -> None:
                try:
                    client.run(idle_timeout_s=2.0)
                except BaseException as e:      # reported in `compared`
                    peer_error.append(e)
                finally:
                    with stamps.cv:
                        stamps.cv.notify_all()

            # the backlog's objects (the reference's facts, held to the
            # end) are not the peer's garbage: keep the collector from
            # walking them inside the window
            gc.collect()
            gc.freeze()
            tracing.recorder().reset()
            tracing.enable(traced)
            puller = threading.Thread(target=pull, name="bench-pull")
            puller.start()

            # -- the window
            trace_summary = session = None
            try:
                opened = stamps.wait(
                    lambda: stamps.t0 is not None or not puller.is_alive(),
                    timeout_s=300.0)
                if stamps.t0 is not None:
                    setup_s = stamps.t0 - t_start
                    say(f"window open: set-up {setup_s:.2f}s, "
                        f"{compiles_warm} compile events while warming")
                    if traced:
                        trace_summary, session = profile_window(
                            stamps, cell, seconds, root, say)
                    n_all = backlog.n_blocks

                    def done() -> bool:
                        return (stamps.t1 is not None
                                or not puller.is_alive()
                                or stamps.events[-1][0] >= n_all)
                    stamps.wait(done, timeout_s=seconds + 120.0)
                    if stamps.t1 is None and stamps.events \
                            and stamps.events[-1][0] >= n_all:
                        stamps.close_dry()
                        say(f"the backlog ran dry "
                            f"{stamps.t1 - stamps.t0:.2f}s into a window "
                            f"of {seconds}s: the rate is taken to the "
                            f"last commit event; raise provision_tx_s")
                elif not opened:
                    say("the window never opened: no commit of the last "
                        "warm-up block in 300 s")
            finally:
                client.stop()
                puller.join(timeout=120.0)
                tracing.enable(False)
            spans = tracing.recorder().recent_spans(limit=1 << 30) \
                if traced else []

            # -- after the window: counters, memory, then the comparison
            compared: Dict[str, float] = {}
            compared["peer_errors"] = len(peer_error) + int(
                puller.is_alive())
            for e in peer_error:
                say(f"the peer under test died: {e!r}")
            compared["window_not_closed"] = int(stamps.t1 is None)
            absent = None if make_verifier is None else 0.0
            compared["sw_fallback_batches"] = metric_value(
                "fabric_bccsp_sw_fallback_batches_total", absent)
            compared["device_errors"] = metric_value(
                "fabric_bccsp_device_errors_total", absent)
            breaker = getattr(verifier, "breaker", None)
            compared["breaker_open"] = int(
                breaker is not None and breaker.state != "closed")
            compared["rejected_blocks"] = len(client.rejected)
            if stamps.t1 is not None:
                compared["compiles_in_window"] = (
                    stamps.snap1[1] - stamps.snap0[1])
            device = dict(device)
            device["memory_peak_bytes"] = int(max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.local_devices()))

            # blocks: closed by count, in the buckets that were warmed
            cutter_cfg = net.support.cutter.config
            acked = [e[0] for e in stamps.events]
            timer_closed = unwarmed = 0
            warmed = set(warm_buckets)
            for num in acked:
                blk = net.support.store.get_block_by_number(num)
                if closing_rule(blk, cutter_cfg) != "count":
                    timer_closed += 1
                items = real_items_of(
                    backlog.txs[(num - 1) * block_txs: num * block_txs])
                if not warmed.issuperset(buckets_reached(items, BUCKETS)):
                    unwarmed += 1
            compared["timer_closed_blocks"] = timer_closed
            compared["unwarmed_bucket_blocks"] = unwarmed

            # the peer's ledger, closed and opened again from disk
            if hasattr(verifier, "close"):
                verifier.close()
            dev_mgr.close()
            dev_mgr = None
            t_ref = time.perf_counter()
            reopened = KvLedger(
                os.path.join(root, "dev-peer", net.channel_id),
                net.channel_id)
            try:
                read = {}
                for num in acked:
                    blk = reopened.get_block_by_number(num)
                    if blk is None:
                        continue
                    read[num] = reference.ReadBlock(
                        number=num, tx_bytes=list(blk.data.data),
                        flags=bytes(protoutil.block_txflags(blk)),
                        previous_hash=blk.header.previous_hash,
                        header_hash=protoutil.block_header_hash(
                            blk.header))
                held = {(ns, key): value
                        for ns in sorted({t.ns for t in backlog.txs})
                        for key, value, _version
                        in reopened.state.get_state_range(ns, "", "")}
                rule = cell.rule().Rule(
                    cell.config["settings"], cell.params,
                    reference.Signatures().counts)
                compared.update(reference.compare(
                    rule, backlog.txs, block_txs, acked, read, held))
            finally:
                reopened.close()
            gc.unfreeze()
            say(f"reference and comparison: {len(acked)} blocks read "
                f"back, {time.perf_counter() - t_ref:.2f}s")
            compared["threads_left"] = len(
                set(concurrency.live_registered()) - live_at_rest)

            # -- the result
            result = assemble(cell, stamps, traced, trace_summary, session,
                              spans, device, compared, t_start, say)
        finally:
            if dev_mgr is not None:
                dev_mgr.close()
            net.close()
    return result


def profile_window(stamps: Stamps, cell: Cell, seconds: float, root: str,
                   say):
    """Open the profiler at a commit event inside the window, close it
    `trace_blocks` commit events later, reduce the trace: (the trace's
    summary, None where the session wrote no trace or recorded no
    device; the session, from which the reducers read whole calls)."""
    import jax
    from benchmarks import reduce as reduce_mod, timeline
    n_blocks = int(cell.file["trace_blocks"])
    skip = int(cell.file.get("trace_after_blocks", 1))
    first = stamps.warm_last + skip
    if not stamps.wait(lambda: stamps.events[-1][0] >= first,
                       timeout_s=seconds):
        return None, None
    out_dir = os.path.join(root, "profile")
    # device planes only: the host's Python tracer slows the very
    # threads that are measured, and the programs' HLO (417 MB of code
    # for the 2048 bucket) would be written into the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=options)
    wall_a = time.time()
    begin_num = stamps.events[-1][0]
    t_a = time.perf_counter()
    try:
        stamps.wait(lambda: stamps.events[-1][0] >= begin_num + n_blocks
                    or stamps.t1 is not None, timeout_s=seconds)
        end_num = stamps.events[-1][0]
        t_b = time.perf_counter()
        wall_b = time.time()
    finally:
        jax.profiler.stop_trace()
    t_c = time.perf_counter()
    try:
        path = reduce_mod.find_xplane(out_dir)
    except FileNotFoundError as e:
        say(f"profiler window: {e}")
        return None, None
    layout: List[str] = []
    summary = reduce_mod.summarize(path, window_s=t_b - t_a, layout=layout)
    extent: List[tuple] = []
    programs, wall, _in_flight = timeline.read_session(path, extent)
    for line in layout:
        say(line)
    say(f"profiler window: blocks {begin_num + 1}..{end_num}, "
        f"{t_b - t_a:.3f}s, stop+write {t_c - t_b:.2f}s, reduce "
        f"{time.perf_counter() - t_c:.2f}s, "
        f"{summary.n_events if summary else 0} device events"
        f"{'' if summary else ', no device plane'}")
    session = None if wall is None else timeline.Session(
        programs, wall[0], (wall_a, wall_b), extent[0][1] if extent else None)
    return summary, session


def assemble(cell, stamps, traced, trace_summary, session, spans, device,
             compared, t_start, say) -> dict:
    metrics: Dict[str, dict] = {}
    attempted = failed = 0
    breakdown = None
    if stamps.t1 is not None:
        events = stamps.window_events()
        attempted = sum(e[1] for e in events)
        failed = int(compared.get("flags_missing", 0))
        span_s = stamps.t1 - stamps.t0
        values = {"committed_tx_s": attempted / span_s,
                  "setup_s": stamps.t0 - t_start}
        gaps = sorted(b[2] - a[2] for a, b in zip(
            [e for e in stamps.events if e[2] >= stamps.t0], events))
        say(f"window: {len(events)} blocks, {attempted} txs in "
            f"{span_s:.3f}s; between commit events min "
            f"{gaps[0]:.3f}s, median {gaps[len(gaps) // 2]:.3f}s, max "
            f"{gaps[-1]:.3f}s")
        if not traced:
            for e in cell.end_to_end:
                metrics[e["name"]] = {"value": values[e["name"]],
                                      "unit": e["unit"]}
        else:
            tot0, tot1 = stamps.snap0[0], stamps.snap1[0]
            names = set(tot0) | set(tot1)
            w0, w1 = stamps.snap0[2], stamps.snap1[2]
            window = Window(
                seconds=span_s, blocks=len(events), txs=attempted,
                span_secs={n: tot1.get(n, {}).get("secs", 0.0)
                           - tot0.get(n, {}).get("secs", 0.0)
                           for n in names},
                span_counts={n: tot1.get(n, {}).get("count", 0)
                             - tot0.get(n, {}).get("count", 0)
                             for n in names},
                dispatches=[(s["attrs"]["items"], s["attrs"]["bucket"])
                            for s in spans if s["name"] == "der_marshal"
                            and w0 < s["ts"] <= w1],
                trace=trace_summary, session=session, ring=spans,
                device_kind=device["kind"])
            specs = []
            for p in cell.per_layer:
                spec, reduce_fn = reducer_for(p["name"])
                specs.append(spec)
                value = reduce_fn(spec, window)
                if value is not None:
                    metrics[p["name"]] = {"value": value,
                                          "unit": p["unit"]}
            if trace_summary is not None:
                device["busy_s"] = trace_summary.busy_s
                device["window_s"] = trace_summary.window_s
                breakdown = {
                    "device_ops": trace_summary.top_ops(10),
                    "idle_gaps": next(
                        (idle_under.idle_gaps(spec, window)
                         for spec in specs
                         if spec["reducer"] == "idle_under"), [])}
            compared["trace_missing"] = int(trace_summary is None)
    limits = {name: 0 for name in compared}
    correct = all(compared[n] <= limits[n] for n in compared)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {n: {"value": compared[n], "limit": limits[n]}
                          for n in sorted(compared)}
    return result
