"""node: the process entry points — standalone orderer, standalone
peer, or the combined single-process topology.

(reference: orderer/common/server/main.go:71 `Main` for
`--role orderer`; internal/peer/node/start.go:205 `serve` for
`--role peer`; the combined role keeps the original in-process
topology for development.)

    # a raft ordering node (gRPC Broadcast/Deliver + cluster Step):
    fabric-mod-tpu node --role orderer --id o0 \
        --genesis genesis.block --crypto crypto-config \
        --listen 127.0.0.1:7050 --cluster-listen 127.0.0.1:7055 \
        --cluster-peers o0=127.0.0.1:7055,o1=...,o2=...

    # a committing peer pulling from the ordering service with
    # failover across endpoints:
    fabric-mod-tpu node --role peer --org Org1 \
        --genesis genesis.block --crypto crypto-config \
        --orderers 127.0.0.1:7050,127.0.0.1:7150

Each role exposes /metrics /healthz /logspec (and, on orderers, the
channel-participation API) on its ops address and runs until
interrupted.  The process-network test tier
(tests/test_procnet.py) spawns these as real OS processes, kills the
raft leader, and watches commit resume — the nwo model
(reference: integration/nwo/network.go:44-60).
"""
from __future__ import annotations

import itertools
import os
import signal
import threading

from fabric_mod_tpu.bccsp.sw import SwCSP
from fabric_mod_tpu.channelconfig import Bundle
from fabric_mod_tpu.channelconfig.configtx import config_from_block
from fabric_mod_tpu.config import PeerConfig, load_config
from fabric_mod_tpu.ledger.kvledger import LedgerManager
from fabric_mod_tpu.msp import ca as calib
from fabric_mod_tpu.msp.identities import SigningIdentity
from fabric_mod_tpu.observability import (
    OperationsServer, default_health, default_provider,
    get_logger, init_logging)
from fabric_mod_tpu.orderer import Broadcast, DeliverService, Registrar
from fabric_mod_tpu.peer.channel import Channel
from fabric_mod_tpu.peer.deliverclient import DeliverClient
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.concurrency.threads import RegisteredThread

log = get_logger("node")


_role_seq = itertools.count()


def _register_role_health(health, name, checker):
    """Per-instance key (name#seq): two roles hosted in one process
    (embedding, in-process tests) share the process-default registry,
    and a fixed key would let the second registration mask the
    first's failing checker — the same masking the commitpipe/breaker
    registrants key around."""
    key = f"{name}#{next(_role_seq)}"
    health.register(key, checker)
    return key


def _load_signer(crypto_dir: str, org: str, kind: str, csp):
    try:
        from cryptography import x509
    except ImportError:       # wheel-less: bccsp/_x509fallback.py
        from fabric_mod_tpu.bccsp import _x509fallback as x509
    base = os.path.join(crypto_dir, org)
    cert_path = os.path.join(base, f"{kind}s", f"{kind}0.pem")
    key_path = os.path.join(base, f"{kind}s", f"{kind}0.key")
    with open(cert_path, "rb") as f:
        cert = x509.load_pem_x509_certificate(f.read())
    with open(key_path, "rb") as f:
        key_pem = f.read()
    return SigningIdentity(org, cert, key_pem, csp)


def run_node(genesis_path: str, crypto_dir: str, orderer_org: str,
             data_dir: str, peer_cfg: PeerConfig,
             stop_event=None) -> None:
    init_logging(default_provider(), peer_cfg.log_spec)
    csp = SwCSP()
    with open(genesis_path, "rb") as f:
        genesis_block = m.Block.decode(f.read())
    cid, config = config_from_block(genesis_block)

    ingress = None
    if peer_cfg.bccsp.upper() == "TPU":
        import functools
        from fabric_mod_tpu.bccsp.tpu import (
            BatchingVerifyService, TpuVerifier)
        verifier = TpuVerifier()
        # warm EVERY bucket's device programs BEFORE serving (the
        # table program and the ladder: which one a lane takes depends
        # on the signers the traffic brings): cold XLA compiles run
        # minutes, ingress futures must never wait on them, and a
        # flush can select any bucket size
        from fabric_mod_tpu.bccsp.tpu import BUCKETS
        from fabric_mod_tpu.utils.fixtures import make_verify_items
        items, _ = make_verify_items(BUCKETS[-1], n_keys=4,
                                     seed=b"warmup")
        for bucket in BUCKETS:
            log.info("warming device verify program (bucket %d)...",
                     bucket)
            verifier.warm(items[:bucket])
        log.info("device warm")
        # ingress coalescing only pays when the device is real; the
        # whole-call timeout still allows a surprise recompile
        ingress = BatchingVerifyService(verifier)
        ingress_verify = functools.partial(ingress.verify_many,
                                           timeout=600)
    else:
        from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
        verifier = FakeBatchVerifier(csp)
        ingress_verify = None

    orderer_signer = _load_signer(crypto_dir, orderer_org, "orderer", csp)
    registrar = Registrar(os.path.join(data_dir, "orderer"),
                          orderer_signer, csp,
                          verify_many=ingress_verify)
    if registrar.get_chain(cid) is None:
        support = registrar.create_channel(genesis_block)
    else:
        support = registrar.get_chain(cid)
    broadcast = Broadcast(registrar)

    ledger_mgr = LedgerManager(os.path.join(data_dir, peer_cfg.ledger_dir))
    ledger = ledger_mgr.create_or_open(cid)
    bundle = Bundle(cid, config, csp)
    channel = Channel(cid, ledger, verifier, bundle, csp)
    if ledger.height == 0:
        channel.init_from_genesis(genesis_block)

    health = default_health()
    _register_role_health(
        health, "ledger", lambda: None if ledger.height > 0 else
        (_ for _ in ()).throw(RuntimeError("empty ledger")))
    host, _, port = peer_cfg.ops_listen_address.partition(":")
    # operations TLS (reference: core.yaml operations.tls.*); with a
    # client CA, clients must present certs
    ops_tls = None
    if peer_cfg.ops_tls_cert and peer_cfg.ops_tls_key:
        ops_tls = {"cert": peer_cfg.ops_tls_cert,
                   "key": peer_cfg.ops_tls_key,
                   "client_ca": peer_cfg.ops_tls_client_ca or None}
    # the participation API can destroy channel storage: mount it only
    # on loopback, or off-loopback strictly behind client-
    # authenticated TLS (reference: the admin server's
    # clientAuthRequired stance)
    participation = None
    loopback = (host or "127.0.0.1") in ("127.0.0.1", "localhost",
                                         "::1")
    if loopback or (ops_tls and ops_tls["client_ca"]):
        from fabric_mod_tpu.orderer.participation import (
            ChannelParticipation)
        participation = ChannelParticipation(registrar)
    else:
        log.warning(
            "ops listener on %s is not loopback and has no client-"
            "authenticated TLS (operations.tls.cert/key + "
            "clientRootCAs): channel participation API disabled",
            host)
    ops = OperationsServer(host or "127.0.0.1", int(port or 0),
                           default_provider(), health,
                           participation=participation, tls=ops_tls)
    ops.start()
    log.info("ops server on %s; channel %s at height %d",
             ops.addr, cid, ledger.height)

    client = DeliverClient(channel, DeliverService(support),
                           queue_size=peer_cfg.deliver_queue_size)
    runner = RegisteredThread(
        target=lambda: client.run(idle_timeout_s=3600.0),
        name="node-deliver", structure="cli.node")
    runner.start()

    stop = stop_event or threading.Event()
    try:
        signal.signal(signal.SIGINT, lambda *_: stop.set())
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
        from fabric_mod_tpu.observability.diag import install_signal_dump
        install_signal_dump()              # SIGUSR1 -> thread stacks
    except ValueError:
        pass                               # not the main thread (tests)
    stop.wait()
    client.stop()
    ops.stop()
    registrar.close()
    ledger_mgr.close()
    if ingress is not None:
        ingress.close()
    return broadcast


def _read_tls_dir(tls_dir):
    """Optional TLS material directory: ca.crt server.crt server.key
    [client.crt client.key].  Returns a dict of PEM bytes or None."""
    if not tls_dir:
        return None
    out = {}
    for name in ("ca.crt", "server.crt", "server.key",
                 "client.crt", "client.key"):
        path = os.path.join(tls_dir, name)
        if os.path.exists(path):
            with open(path, "rb") as f:
                out[name] = f.read()
    return out or None


def _start_ops(peer_cfg: PeerConfig, health, participation=None):
    host, _, port = peer_cfg.ops_listen_address.partition(":")
    ops_tls = None
    if peer_cfg.ops_tls_cert and peer_cfg.ops_tls_key:
        ops_tls = {"cert": peer_cfg.ops_tls_cert,
                   "key": peer_cfg.ops_tls_key,
                   "client_ca": peer_cfg.ops_tls_client_ca or None}
    loopback = (host or "127.0.0.1") in ("127.0.0.1", "localhost", "::1")
    if participation is not None and not (
            loopback or (ops_tls and ops_tls["client_ca"])):
        log.warning("ops listener on %s is not loopback and has no "
                    "client-authenticated TLS: participation API "
                    "disabled", host)
        participation = None
    ops = OperationsServer(host or "127.0.0.1", int(port or 0),
                           default_provider(), health,
                           participation=participation, tls=ops_tls)
    ops.start()
    return ops


def _install_stop_signals(stop):
    try:
        signal.signal(signal.SIGINT, lambda *_: stop.set())
        signal.signal(signal.SIGTERM, lambda *_: stop.set())
        from fabric_mod_tpu.observability.diag import install_signal_dump
        install_signal_dump()              # SIGUSR1 -> thread stacks
    except ValueError:
        pass                               # not the main thread (tests)


def run_orderer(node_id: str, genesis_path: str, crypto_dir: str,
                orderer_org: str, data_dir: str, listen: str,
                cluster_listen: str, cluster_peers: dict,
                peer_cfg: PeerConfig, tls=None, stop_event=None) -> None:
    """A standalone ordering node (reference: orderer/common/server/
    main.go:71): registrar + consenter-by-ConsensusType + gRPC
    AtomicBroadcast server + cluster Step transport + participation
    API on the ops listener."""
    init_logging(default_provider(), peer_cfg.log_spec)
    csp = SwCSP()
    with open(genesis_path, "rb") as f:
        genesis_block = m.Block.decode(f.read())
    cid, _config = config_from_block(genesis_block)
    signer = _load_signer(crypto_dir, orderer_org, "orderer", csp)

    tls = tls or {}
    transport = None
    consenters = {}
    if cluster_peers:
        from fabric_mod_tpu.orderer.cluster import GRPCRaftTransport
        from fabric_mod_tpu.orderer.raftchain import RaftChain
        transport = GRPCRaftTransport(
            node_id, dict(cluster_peers), listen_address=cluster_listen,
            server_cert=tls.get("server.crt"),
            server_key=tls.get("server.key"),
            client_ca=tls.get("ca.crt"),
            client_cert=tls.get("client.crt"),
            client_key=tls.get("client.key"))
        transport.start()
        wal_dir = os.path.join(data_dir, "raft")
        os.makedirs(wal_dir, exist_ok=True)

        def raft_factory(support, _t=transport):
            return RaftChain(
                node_id, sorted(cluster_peers), _t,
                os.path.join(wal_dir, f"{support.channel_id}.wal"),
                support)
        consenters["etcdraft"] = raft_factory

    registrar = Registrar(os.path.join(data_dir, "orderer"), signer,
                          csp, consenters=consenters)
    if registrar.get_chain(cid) is None:
        registrar.create_channel(genesis_block)

    from fabric_mod_tpu.orderer.server import OrdererServer
    server = OrdererServer(registrar, listen,
                           server_cert_pem=tls.get("server.crt"),
                           server_key_pem=tls.get("server.key"))
    server.start()

    health = default_health()
    _register_role_health(health, "registrar", lambda: None)
    from fabric_mod_tpu.orderer.participation import ChannelParticipation
    ops = _start_ops(peer_cfg, health,
                     participation=ChannelParticipation(registrar))
    log.info("orderer %s: channel %s, broadcast/deliver on port %d, "
             "ops on %s", node_id, cid, server.port, ops.addr)

    stop = stop_event or threading.Event()
    _install_stop_signals(stop)
    stop.wait()
    server.stop()
    ops.stop()
    registrar.close()
    if transport is not None:
        transport.stop()


def run_peer(org: str, genesis_path: str, crypto_dir: str,
             data_dir: str, orderer_addresses: list,
             peer_cfg: PeerConfig, tls=None, stop_event=None,
             peer_listen: str = "127.0.0.1:0") -> None:
    """A standalone committing peer (reference: internal/peer/node/
    start.go:205): ledger + channel + MCS-verified pipelined deliver
    client with endpoint failover + the gRPC endorsement service on
    `peer_listen`."""
    init_logging(default_provider(), peer_cfg.log_spec)
    csp = SwCSP()
    with open(genesis_path, "rb") as f:
        genesis_block = m.Block.decode(f.read())
    cid, config = config_from_block(genesis_block)

    if peer_cfg.bccsp.upper() == "TPU":
        from fabric_mod_tpu.bccsp.tpu import TpuVerifier
        verifier = TpuVerifier()
    else:
        from fabric_mod_tpu.bccsp.tpu import FakeBatchVerifier
        verifier = FakeBatchVerifier(csp)

    ledger_mgr = LedgerManager(os.path.join(data_dir,
                                            peer_cfg.ledger_dir))
    ledger = ledger_mgr.create_or_open(cid)
    bundle = Bundle(cid, config, csp)
    channel = Channel(cid, ledger, verifier, bundle, csp)
    if ledger.height == 0:
        channel.init_from_genesis(genesis_block)

    from fabric_mod_tpu.peer.blocksprovider import (
        Endpoint, FailoverDeliverSource)
    tls = tls or {}
    endpoints = [Endpoint(addr, server_root_pem=tls.get("ca.crt"))
                 for addr in orderer_addresses]
    source = FailoverDeliverSource(endpoints, cid)
    client = DeliverClient(channel, source,
                           queue_size=peer_cfg.deliver_queue_size)
    runner = RegisteredThread(
        target=lambda: client.run(idle_timeout_s=3600.0),
        name="peer-deliver", structure="cli.node")
    runner.start()

    # the endorsement surface (reference: core/endorser's
    # ProcessProposal service registered at node start): user
    # contract + system chaincodes + the lifecycle ceremony
    from fabric_mod_tpu.comm.grpc_comm import GRPCServer
    from fabric_mod_tpu.peer.aclmgmt import ACLProvider
    from fabric_mod_tpu.peer.deliverevents import EventDeliverServer
    from fabric_mod_tpu.peer.endorser import Endorser
    from fabric_mod_tpu.peer.endorserserver import EndorserServer
    from fabric_mod_tpu.peer.scc import build_default_registry
    peer_signer = _load_signer(crypto_dir, org, "peer", csp)
    endorser = Endorser(channel, build_default_registry(channel, ledger),
                        peer_signer)
    # one listener for every peer-facing service (endorsement + client
    # events), like the reference's single peer gRPC server
    # worker headroom: event streams park threads at the chain tip
    # (EventDeliverServer caps them at FABRIC_MOD_TPU_DELIVER_STREAMS,
    # default 40), endorsement must always find a free worker beyond
    # that cap
    pserver = GRPCServer(peer_listen,
                         server_cert_pem=tls.get("server.crt"),
                         server_key_pem=tls.get("server.key"),
                         max_workers=64)
    eserver = EndorserServer(endorser, grpc=pserver)
    acl = ACLProvider(channel.bundle, verify_many=verifier.verify_many)
    events = EventDeliverServer(cid, ledger, acl, grpc=pserver)
    pserver.start()

    health = default_health()
    _register_role_health(
        health, "ledger", lambda: None if ledger.height > 0 else
        (_ for _ in ()).throw(RuntimeError("empty ledger")))
    ops = _start_ops(peer_cfg, health)
    log.info("peer (%s): channel %s at height %d, endorser+events on "
             "port %d, orderers %s, ops on %s", org, cid, ledger.height,
             eserver.port, orderer_addresses, ops.addr)

    stop = stop_event or threading.Event()
    _install_stop_signals(stop)
    stop.wait()
    client.stop()
    # join the puller/committer before closing stores: a commit in
    # flight must not race the ledger's file handles going away
    runner.join(timeout=10)
    events.stop()           # wakes tip-parked deliver handlers first
    pserver.stop(1.0)
    ops.stop()
    ledger_mgr.close()


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="node")
    ap.add_argument("--role", choices=("combined", "orderer", "peer"),
                    default="combined")
    ap.add_argument("--genesis", required=True)
    ap.add_argument("--crypto", default="crypto-config")
    ap.add_argument("--orderer-org", default="OrdererOrg")
    ap.add_argument("--org", default="Org1", help="peer role: MSP org")
    ap.add_argument("--data", default="data")
    ap.add_argument("--config", default=None, help="core.yaml path")
    ap.add_argument("--id", default="o0", help="orderer node id")
    ap.add_argument("--listen", default="127.0.0.1:0",
                    help="orderer broadcast/deliver address")
    ap.add_argument("--cluster-listen", default="127.0.0.1:0",
                    help="orderer raft Step address")
    ap.add_argument("--cluster-peers", default="",
                    help="id=host:port,... raft cluster map")
    ap.add_argument("--orderers", default="",
                    help="peer role: comma-separated deliver endpoints")
    ap.add_argument("--peer-listen", default="127.0.0.1:0",
                    help="peer role: endorsement service address")
    ap.add_argument("--tls-dir", default="",
                    help="dir with ca.crt server.crt server.key "
                         "[client.crt client.key]")
    args = ap.parse_args(argv)
    peer_cfg = load_config(PeerConfig, args.config)
    tls = _read_tls_dir(args.tls_dir)
    if args.role == "orderer":
        peers = {}
        for part in filter(None, args.cluster_peers.split(",")):
            pid, _, addr = part.partition("=")
            peers[pid] = addr
        run_orderer(args.id, args.genesis, args.crypto,
                    args.orderer_org, args.data, args.listen,
                    args.cluster_listen, peers, peer_cfg, tls=tls)
    elif args.role == "peer":
        addrs = [a for a in args.orderers.split(",") if a]
        run_peer(args.org, args.genesis, args.crypto, args.data,
                 addrs, peer_cfg, tls=tls,
                 peer_listen=args.peer_listen)
    else:
        run_node(args.genesis, args.crypto, args.orderer_org,
                 args.data, peer_cfg)
    return 0
