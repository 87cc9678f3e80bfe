"""Application (endorsement) policy evaluation — what VSCC consumes.

(reference: core/policy/application.go:115-161
`ApplicationPolicyEvaluator.Evaluate`: an ApplicationPolicy proto is
either an inline SignaturePolicyEnvelope or a named reference into the
channel's policy manager.)
"""
from __future__ import annotations

from typing import Optional, Sequence

from fabric_mod_tpu.policy.cauthdsl import (
    BatchCollector, CompiledPolicy, PolicyError)
from fabric_mod_tpu.policy.manager import PolicyManager
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos.protoutil import SignedData


class ApplicationPolicyEvaluator:
    def __init__(self, msp_mgr,
                 channel_policy_manager: Optional[PolicyManager] = None,
                 sequence: int = 0):
        """`sequence` is the owning bundle's config sequence: it keys
        the shared compiled-policy memo (policy/manager.py), so a
        config update can never be answered from a stale compile."""
        self._msp_mgr = msp_mgr
        self._channel_mgr = channel_policy_manager
        self._sequence = sequence
        self._compiled_cache: dict = {}

    def _resolve(self, policy_bytes: bytes):
        """ApplicationPolicy bytes -> two-phase policy object.

        Inline signature policies are compile-cached by their bytes
        (immutable) on this instance, backed by the shared
        (bytes, config sequence)-keyed memo in policy/manager.py so a
        rebuilt evaluator (new validator, bench world, gossip path)
        reuses compiles instead of re-decoding; channel references are
        re-resolved on every call like the reference
        (core/policy/application.go Evaluate) so a config update that
        replaces the named policy takes effect immediately.
        """
        cached = self._compiled_cache.get(policy_bytes)
        if cached is not None:
            return cached
        ap = m.ApplicationPolicy.decode(policy_bytes)
        if ap.signature_policy is not None:
            from fabric_mod_tpu.policy.manager import compile_policy_bytes
            pol = compile_policy_bytes(ap.signature_policy.encode(),
                                       self._msp_mgr, self._sequence)
            self._compiled_cache[policy_bytes] = pol
            return pol
        if ap.channel_config_policy_reference:
            if self._channel_mgr is None:
                raise PolicyError("no channel policy manager configured")
            pol = self._channel_mgr.get_policy(
                ap.channel_config_policy_reference)
            if pol is None:
                raise PolicyError(
                    f"channel policy "
                    f"{ap.channel_config_policy_reference!r} not found")
            return pol
        raise PolicyError("empty ApplicationPolicy")

    def prepare(self, policy_bytes: bytes,
                signed_datas: Sequence[SignedData],
                collector: BatchCollector):
        return self._resolve(policy_bytes).prepare(signed_datas, collector)

    def evaluate(self, policy_bytes: bytes,
                 signed_datas: Sequence[SignedData],
                 verify_many=None) -> bool:
        return self._resolve(policy_bytes).evaluate_signed_data(
            signed_datas, verify_many)
