"""The fault-point registry: every injection point, declared here.

``faults.point("name")`` seams are stringly-typed: a typo'd name in an
``FMT_FAULTS`` plan used to arm a rule that silently never fired — the
chaos run passed while injecting nothing.  Declaring every point in
this module (imported before the env-spec plan is armed) makes
``FaultPlan.validate()`` a complete check at arm time, and the fmtlint
``fault-points`` rule closes the other direction: a ``faults.point``
literal that is not declared here, or a declared point no production
seam references, fails the lint gate.

Tests arming synthetic points for framework units register them
scoped via :func:`declared_point` (a context manager) or pass
``validate=False`` where the point's absence is the subject under
test.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Set

# One line per production seam; keep sorted.  The lint rule
# cross-checks both directions against the tree.
DECLARED_POINTS: Set[str] = {
    "bccsp.device.dispatch",
    "bccsp.device.probe",
    "bccsp.device.resolve",
    "bccsp.device.tables",
    "commitpipe.commit",
    "commitpipe.stage",
    "deliver.failover.stream",
    "deliver.fanout",
    "deliver.stream",
    "dissemination.push",
    "dissemination.repair",
    "gossip.comm.drop",
    "gossip.comm.send",
    "orderer.admission.overload",
    "orderer.broadcast.stage",
    "orderer.raft.replicate",
    "orderer.raft.submit",
    "orderer.wal.crash",
    "orderer.wal.sync",
    "peer.ledger.crash",
    "peer.mvcc.vector",
    "sharding.dispatch",
}


def is_declared(name: str) -> bool:
    return name in DECLARED_POINTS


@contextlib.contextmanager
def declared_point(name: str) -> Iterator[str]:
    """Scoped synthetic declaration for framework unit tests."""
    added = name not in DECLARED_POINTS
    if added:
        DECLARED_POINTS.add(name)
    try:
        yield name
    finally:
        if added:
            DECLARED_POINTS.discard(name)
