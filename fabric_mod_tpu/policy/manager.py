"""Hierarchical policy manager + implicit meta policies.

(reference: common/policies/policy.go `ManagerImpl`/`GetPolicy` and
common/policies/implicitmeta.go.)  A channel's policy tree mirrors its
config tree: the root manager holds /Channel-level policies and child
managers (Application, Orderer, per-org groups), each with their own
named policies.  Implicit meta policies ("ANY Writers", "MAJORITY
Admins") aggregate the same-named sub-policy of every child group.

Every policy object speaks the two-phase prepare/finish protocol from
cauthdsl.py so a whole block's policy checks share one device batch.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from fabric_mod_tpu.policy.cauthdsl import (
    BatchCollector, CompiledPolicy, PendingEval, PolicyError)
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos.protoutil import SignedData

# Well-known policy names (reference: common/policies/policy.go:25-47)
CHANNEL_APPLICATION_READERS = "/Channel/Application/Readers"
CHANNEL_APPLICATION_WRITERS = "/Channel/Application/Writers"
CHANNEL_APPLICATION_ADMINS = "/Channel/Application/Admins"
CHANNEL_ORDERER_BLOCK_VALIDATION = "/Channel/Orderer/BlockValidation"
CHANNEL_ORDERER_WRITERS = "/Channel/Orderer/Writers"


class _MetaPending:
    def __init__(self, pendings: List, threshold: int):
        self._pendings = pendings
        self._threshold = threshold

    def finish(self, mask, valid: Optional[dict] = None) -> bool:
        # every sub-policy counted, no early exit at the threshold
        if valid is None:
            valid = {}
        got = sum(1 for p in self._pendings if p.finish(mask, valid))
        return got >= self._threshold


class ImplicitMetaPolicyObj:
    """N-of child policies, N from ANY/ALL/MAJORITY
    (reference: common/policies/implicitmeta.go NewPolicy)."""

    def __init__(self, sub_policies: Sequence, rule: int):
        self._subs = list(sub_policies)
        n = len(self._subs)
        if rule == m.ImplicitMetaRule.ANY:
            self.threshold = 1
        elif rule == m.ImplicitMetaRule.ALL:
            self.threshold = n
        elif rule == m.ImplicitMetaRule.MAJORITY:
            self.threshold = n // 2 + 1
        else:
            raise PolicyError(f"unknown implicit meta rule {rule}")
        if n == 0:
            # pinned like the reference: a meta policy over zero
            # sub-policies can never pass (threshold 0 = fail-open)
            self.threshold = 1

    def prepare(self, signed_datas: Sequence[SignedData],
                collector: BatchCollector,
                resolved: Optional[dict] = None):
        """Every sub-policy's check of one signature set.  One dict of
        resolutions serves the whole tree, so the set is deserialized,
        validated and staged once per manager; each node keeps its
        threshold and each leaf its closure."""
        if resolved is None:
            resolved = {}
        return _MetaPending(
            [s.prepare(signed_datas, collector, resolved)
             for s in self._subs],
            self.threshold)

    def evaluate_signed_data(self, signed_datas: Sequence[SignedData],
                             verify_many=None) -> bool:
        collector = BatchCollector()
        pending = self.prepare(signed_datas, collector)
        if verify_many is None:
            verify_many = _first_csp_verify(self._subs)
        mask = verify_many(collector.items)
        return pending.finish(mask)


def _first_csp_verify(policies):
    got = _find_csp_verify(policies)
    if got is None:
        raise PolicyError("no signature policy beneath this meta policy")
    return got


def batch_verifier(policy, verify_many=None):
    """Resolve the `verify_many` callable `evaluate_signed_data` would
    use for this policy object: the given one when set, else the
    policy's own CSP batch path — so batched callers (the staged
    broadcast drainer) dispatch exactly the verifier the one-shot
    path would have."""
    if verify_many is not None:
        return verify_many
    if isinstance(policy, CompiledPolicy):
        return policy._default_verify
    if isinstance(policy, ImplicitMetaPolicyObj):
        return _first_csp_verify(policy._subs)
    raise PolicyError(
        f"no batch verifier for policy type {type(policy).__name__}")


def _find_csp_verify(policies):
    for p in policies:
        if isinstance(p, CompiledPolicy):
            return p._default_verify
        if isinstance(p, ImplicitMetaPolicyObj):
            got = _find_csp_verify(p._subs)
            if got is not None:
                return got
    return None


class PolicyManager:
    """One level of the policy tree (reference: policy.go ManagerImpl)."""

    def __init__(self, name: str = "Channel",
                 policies: Optional[Dict[str, object]] = None,
                 sub_managers: Optional[Dict[str, "PolicyManager"]] = None):
        self.name = name
        self._policies = dict(policies or {})
        self._subs = dict(sub_managers or {})

    # -- construction ----------------------------------------------------
    def add_policy(self, name: str, policy) -> None:
        self._policies[name] = policy

    def add_sub_manager(self, mgr: "PolicyManager") -> None:
        self._subs[mgr.name] = mgr

    def resolve_implicit_meta(self, name: str,
                              meta: m.ImplicitMetaPolicy) -> None:
        """Materialize an implicit meta policy over the current children
        (call after the child managers/policies exist)."""
        subs = [s._policies[meta.sub_policy] for s in self._subs.values()
                if meta.sub_policy in s._policies]
        self._policies[name] = ImplicitMetaPolicyObj(subs, meta.rule)

    # -- lookup ----------------------------------------------------------
    def sub_manager(self, name: str) -> Optional["PolicyManager"]:
        return self._subs.get(name)

    def get_policy(self, path: str):
        """Absolute ("/Channel/Application/Writers") or relative
        ("Writers") lookup; None when absent."""
        if path.startswith("/"):
            parts = [p for p in path.split("/") if p]
            if not parts or parts[0] != self.name:
                return None
            mgr: Optional[PolicyManager] = self
            for part in parts[1:-1]:
                mgr = mgr.sub_manager(part) if mgr else None
            return mgr._policies.get(parts[-1]) if mgr else None
        return self._policies.get(path)


def policy_from_proto(pol: m.Policy, msp_mgr) -> object:
    """Decode a config-tree Policy proto into an evaluator (signature
    policies only here; implicit meta needs the tree context — use
    PolicyManager.resolve_implicit_meta)."""
    if pol.type == m.PolicyType.SIGNATURE:
        return compile_policy_bytes(pol.value, msp_mgr)
    raise PolicyError(f"unsupported policy type {pol.type}")


# ---------------------------------------------------------------------------
# Compiled-policy memo: one CompiledPolicy per (envelope bytes, config
# sequence) per MSP manager.  Before this memo every evaluation SITE
# (each ApplicationPolicyEvaluator instance, each bundle build, each
# gossip eligibility check) re-decoded the envelope and re-compiled
# the closure tree for bytes it had already seen; the memo makes the
# compile a dict hit.  Weak-keyed by the manager so a bundle swap
# (new MspManager) can never serve policies bound to dead trust
# roots, and the sequence key guards any manager mutated in place.
# ---------------------------------------------------------------------------

import threading as _threading
import weakref as _weakref

_COMPILE_MEMO: "_weakref.WeakKeyDictionary" = _weakref.WeakKeyDictionary()
_COMPILE_LOCK = _threading.Lock()  # fmtlint: allow[locks] -- leaf lock guarding a memo dict get-or-create, never nested
_COMPILE_MEMO_CAP = 4096


def compile_policy_bytes(policy_bytes: bytes, msp_mgr,
                         sequence: int = 0) -> CompiledPolicy:
    """SignaturePolicyEnvelope bytes -> CompiledPolicy, memoized."""
    key = (bytes(policy_bytes), sequence)
    with _COMPILE_LOCK:
        per = _COMPILE_MEMO.get(msp_mgr)
        if per is None:
            per = {}
            _COMPILE_MEMO[msp_mgr] = per
        got = per.get(key)
    if got is not None:
        return got
    env = m.SignaturePolicyEnvelope.decode(policy_bytes)
    pol = CompiledPolicy(env, msp_mgr)
    with _COMPILE_LOCK:
        if len(per) >= _COMPILE_MEMO_CAP:
            # the live set (a channel's distinct policies) is tiny
            # next to the bound; overflow means sequence churn, and
            # stale epochs never hit again — reset beats LRU here
            per.clear()
        per[key] = pol
    return pol
