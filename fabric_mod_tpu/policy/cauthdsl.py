"""Signature-policy compilation and batch-first evaluation.

The L2 core (reference: common/cauthdsl/cauthdsl.go:24-92 `compile`,
common/cauthdsl/policy.go:87 `EvaluateSignedData`, and
common/policies/policy.go:365-403 `SignatureSetToValidIdentities`).

The reference's evaluation shape is already ideal for a device batch:
it *first* deduplicates identities and eagerly verifies every
signature, *then* runs the combinatorial NOutOf/SignedBy walk over the
set of validated identities.  Here that split is explicit and
two-phase so a block validator can gather the signature sets of every
policy evaluation in a block, fire ONE device batch-verify, and only
then finish each policy decision host-side:

    collector = BatchCollector()
    pending = [pol.prepare(sds, collector) for (pol, sds) in work]
    mask = verifier.verify_many(collector.items)   # one device call
    results = [p.finish(mask) for p in pending]

`CompiledPolicy.evaluate_signed_data` is the standalone convenience
that does all three steps with a single verify call of its own.

Host-side work stays host-side: identity deserialization, cert-chain
validation, and principal matching are pointer-chasing x509 logic the
MSP (with its second-chance caches) already handles; only the ECDSA
math rides the batch.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

from fabric_mod_tpu.bccsp.api import VerifyItem
from fabric_mod_tpu.protos import messages as m
from fabric_mod_tpu.protos.protoutil import SignedData


class PolicyError(Exception):
    pass


class BatchCollector:
    """Accumulates VerifyItems across many policy evaluations so they
    can be verified in one device dispatch.  Identical work items
    (same digest, signature, key and message) dedup to one batch slot:
    key-level candidates and repeated signature sets stage the same
    check more than once in a block, and verifying each repeat would
    multiply the device batch."""

    def __init__(self):
        self.items: List[VerifyItem] = []
        self.requests = 0          # add() calls incl. dedup hits — the
        self._index: dict = {}     # spread vs len(items) is staged work
        #                            the dedup saved (validator metrics)
        # identity resolutions a meta policy's leaves took from a
        # resolution already made on their manager (prepare)
        self.shared_resolutions = 0

    def add(self, item: VerifyItem) -> int:
        self.requests += 1
        # message MUST be part of the key: two raw-message items
        # (FABRIC_MOD_TPU_FUSED_HASH) share digest=b"" — deduping on
        # (digest, sig, key) alone would let a replayed signature over
        # a DIFFERENT message share the valid item's verdict slot
        key = (item.digest, item.signature, item.public_xy,
               getattr(item, "message", None))
        got = self._index.get(key)
        if got is not None:
            return got
        self.items.append(item)
        idx = len(self.items) - 1
        self._index[key] = idx
        return idx


class PendingEval:
    """A policy decision waiting on the device verdict mask.

    `slots` pairs each candidate identity with either the index of its
    VerifyItem in the collector batch or a host-computed verdict (for
    non-batchable curves).  The leaves of one meta policy that share a
    resolution hold the same `idents` and `slots` lists.
    """

    def __init__(self, closure: Callable, idents: List,
                 slots: List[tuple]):
        self._closure = closure
        self._idents = idents
        self._slots = slots                 # (batch_idx | None, host_ok)

    def finish(self, mask, valid: Optional[dict] = None) -> bool:
        """Resolve against the batch verdict mask -> policy verdict.

        `valid` maps a resolution to the identities the mask accepted:
        a meta policy hands one dict to every leaf beneath it, so a
        shared resolution is read once (the walk only reads the list)."""
        if valid is None:
            valid = {}
        ok = valid.get(id(self._slots))
        if ok is None:
            ok = valid[id(self._slots)] = [
                ident for ident, (bidx, host_ok)
                in zip(self._idents, self._slots)
                if (bool(mask[bidx]) if bidx is not None else host_ok)]
        return self._closure(ok, [False] * len(ok))


def _compile(rule: m.SignaturePolicy,
             principals: Sequence[m.MSPPrincipal],
             msp_mgr) -> Callable:
    """SignaturePolicy proto tree -> closure(idents, used) -> bool
    (reference: cauthdsl.go:24-92 — same greedy used-flag semantics)."""
    if rule.n_out_of is not None:
        n = rule.n_out_of.n
        subs = [_compile(r, principals, msp_mgr) for r in rule.n_out_of.rules]

        def node(idents, used) -> bool:
            # Trial/commit used-flag discipline, no early exit — exactly
            # the reference's loop (cauthdsl.go:45-60): a failed child
            # must not consume identities, and later children still run
            # so the committed used-set matches the reference's.
            verified = 0
            for sub in subs:
                trial = list(used)
                if sub(idents, trial):
                    verified += 1
                    used[:] = trial
            return verified >= n
        return node

    idx = rule.signed_by
    if not 0 <= idx < len(principals):
        raise PolicyError(f"identity index {idx} out of range")
    principal = principals[idx]

    def leaf(idents, used) -> bool:
        for i, ident in enumerate(idents):
            if used[i]:
                continue
            if msp_mgr.satisfies_principal(ident, principal):
                used[i] = True
                return True
        return False
    return leaf


def _resolve(signed_datas: Sequence[SignedData], msp_mgr,
             collector: BatchCollector) -> tuple:
    """-> (idents, slots, distinct identities in the set)."""
    idents: List = []
    slots: List[tuple] = []
    seen = set()
    for sd in signed_datas:
        if sd.identity in seen:
            continue                          # duplicate identity: skip
        seen.add(sd.identity)
        try:
            ident = msp_mgr.deserialize_identity(sd.identity)
        except Exception:
            continue                          # unknown MSP / bad cert
        try:
            msp_mgr.validate(ident)
        except Exception:
            continue                          # expired/revoked/untrusted
        item = ident.verify_item(sd.data, sd.signature)
        if item is not None:
            slots.append((collector.add(item), False))
        else:                                 # non-P256: host verify now
            slots.append((None, ident.verify(sd.data, sd.signature)))
        idents.append(ident)
    return idents, slots, len(seen)


class CompiledPolicy:
    """A compiled SignaturePolicyEnvelope bound to an MSP manager.

    (reference: cauthdsl/policy.go `policy` + the provider at :25)
    """

    def __init__(self, envelope: m.SignaturePolicyEnvelope, msp_mgr):
        if envelope.rule is None:
            raise PolicyError("policy envelope has no rule")
        self._msp_mgr = msp_mgr
        self._closure = _compile(envelope.rule, envelope.identities, msp_mgr)
        self.envelope = envelope

    # -- phase 1: dedup + validate + stage verifies ----------------------
    def prepare(self, signed_datas: Sequence[SignedData],
                collector: BatchCollector,
                resolved: Optional[dict] = None) -> PendingEval:
        """Dedup identities, drop undeserializable/invalid ones, stage
        each survivor's signature check into `collector` (reference:
        common/policies/policy.go:365-403, which dedups then verifies
        every signature before the policy walk).

        `resolved` maps a manager to its resolution of this same
        signature set: a meta policy hands one dict to every leaf
        beneath it, so each manager resolves the set once.  The
        reference resolves it per sub-policy with the same
        deserializer, so the answer is the same."""
        if resolved is None:
            resolved = {}
        got = resolved.get(id(self._msp_mgr))
        if got is None:
            got = resolved[id(self._msp_mgr)] = _resolve(
                signed_datas, self._msp_mgr, collector)
        else:
            collector.shared_resolutions += got[2]
        return PendingEval(self._closure, got[0], got[1])

    def satisfied_by_principals(self, idents: Sequence) -> bool:
        """Principal-only evaluation — no signatures involved (the
        reference's AccessFilter use: is this SET OF IDENTITIES inside
        the policy, e.g. collection membership checks at private-data
        dissemination time)."""
        used = [False] * len(idents)
        return self._closure(list(idents), used)

    # -- phases 1+2+3 standalone -----------------------------------------
    def evaluate_signed_data(self, signed_datas: Sequence[SignedData],
                             verify_many: Optional[Callable] = None) -> bool:
        """One-shot evaluation with its own single batch dispatch.
        `verify_many` defaults to the MSP's CSP batch path."""
        collector = BatchCollector()
        pending = self.prepare(signed_datas, collector)
        mask = (verify_many or self._default_verify)(collector.items)
        return pending.finish(mask)

    def _default_verify(self, items: Sequence[VerifyItem]):
        csp = getattr(self._msp_mgr, "csp", None)
        if csp is None:
            # fall back to any MSP's provider — they share the process CSP
            msps = self._msp_mgr.msps()
            if not msps:
                raise PolicyError("no MSPs configured")
            csp = msps[0]._csp
        return csp.verify_batch(items)
