"""Second-chance caches around an MSP (reference: msp/cache/cache.go,
msp/cache/second_chance.go): deserialize, chain-validate and match
against a principal once per distinct identity, not once per signature.

Where it lives.  `channelconfig.Bundle` wraps its `MspManager` in one
`CachedMsp` before it compiles the policy tree, so every policy of the
bundle (endorsement, BlockValidation, Writers, ACLs), the validator's
creator check, the endorser, gossip's identity mapper and discovery
share ONE cache per channel config.  A config update builds a new
bundle, hence a cold cache: an identity that the new config revoked or
re-rooted is never answered from the old one.

What a hit costs.  A dictionary probe and a counter: `Identity`
memoises its serialized bytes and the deserialize cache hands back the
same object for the same bytes, so no key is re-encoded.

The clock rule.  Roots and revocation lists are fixed per bundle; the
clock is not.  A `valid` verdict is kept with the interval in which it
holds (the chain's latest not_valid_before, its earliest
not_valid_after) and served only inside it, else the MSP validates
again.  A refusal whose cause is that interval is never kept: a
certificate not valid yet passes once it is.  Principal verdicts
follow the same rule, since a role or OU match includes validity.

`fabric_msp_cache_lookups_total{cache,result}` counts the lookups.
Identities without a certificate (idemix) pass through uncached.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional
from fabric_mod_tpu.concurrency.locks import RegisteredLock
from fabric_mod_tpu.msp import mspimpl
from fabric_mod_tpu.msp.mspimpl import (MSPValidationError,
                                        MSPValidityWindowError)
from fabric_mod_tpu.observability.metrics import (MetricOpts,
                                                  default_provider)

_LOOKUPS_OPTS = MetricOpts(
    "fabric", "msp", "cache_lookups_total",
    help="Lookups in the per-channel-config MSP caches; a miss did the "
         "work (decode, chain walk, principal match) underneath.",
    label_names=("cache", "result"))


class SecondChanceCache:
    """Clock (second-chance) eviction, thread-safe."""

    def __init__(self, capacity: int = 256):
        self.capacity = capacity
        self._lock = RegisteredLock("msp.cache._lock")
        self._data: Dict[Any, list] = {}    # key -> [value, referenced]
        self._ring: list = []
        self._hand = 0

    def get(self, key):
        # no lock on the hit path: a dict probe is atomic under the
        # interpreter lock, only `put` (under its lock) changes the
        # table, and a referenced-mark set on an entry that `put` is
        # evicting is lost with it
        ent = self._data.get(key)
        if ent is None:
            return None
        ent[1] = True
        return ent[0]

    def put(self, key, value) -> None:
        with self._lock:
            if key in self._data:
                self._data[key][0] = value
                return
            while len(self._data) >= self.capacity:
                victim = self._ring[self._hand]
                ent = self._data.get(victim)
                if ent is not None and ent[1]:
                    ent[1] = False
                    self._hand = (self._hand + 1) % len(self._ring)
                    continue
                if ent is not None:
                    del self._data[victim]
                self._ring[self._hand] = key
                self._data[key] = [value, False]
                self._hand = (self._hand + 1) % len(self._ring)
                return
            self._ring.append(key)
            self._data[key] = [value, False]


class CachedMsp:
    """Wraps an Msp (or MspManager) with caches on the three hot calls
    (reference: msp/cache/cache.go:42-49); everything else passes
    through (`msps()`, `get()`, `csp`)."""

    def __init__(self, msp, capacity: int = 256):
        self._msp = msp
        self._deser = SecondChanceCache(capacity)
        self._valid = SecondChanceCache(capacity)
        self._princ = SecondChanceCache(capacity)
        lookups = default_provider().counter(_LOOKUPS_OPTS)
        self._deser_hit, self._deser_miss, self._valid_hit, \
            self._valid_miss, self._princ_hit, self._princ_miss = (
                lookups.with_labels(cache, result)
                for cache in ("deserialize", "validate", "principal")
                for result in ("hit", "miss"))

    def __getattr__(self, name):
        return getattr(self._msp, name)

    def deserialize_identity(self, serialized: bytes):
        hit = self._deser.get(serialized)
        if hit is not None:
            self._deser_hit.add(1)
            return hit
        self._deser_miss.add(1)
        ident = self._msp.deserialize_identity(serialized)
        if getattr(ident, "cert", None) is not None:
            self._deser.put(serialized, ident)
        return ident

    def validate(self, ident) -> None:
        if getattr(ident, "cert", None) is None:
            return self._msp.validate(ident)
        self._window(ident)

    def _window(self, ident) -> mspimpl.ValidityWindow:
        """The interval in which `ident` is valid under this config,
        which holds now; raises what `validate` raises."""
        key = ident.serialize()
        cached = self._valid.get(key)
        if isinstance(cached, tuple):
            if cached[0] <= mspimpl.now_utc() <= cached[1]:
                self._valid_hit.add(1)
                return cached
        elif cached is not None:
            self._valid_hit.add(1)
            # a fresh instance: raising a shared one from two threads
            # would interleave their tracebacks on it
            raise type(cached)(*cached.args)
        self._valid_miss.add(1)
        try:
            window = self._msp.validated_window(ident)
        except MSPValidityWindowError:
            raise                        # the clock can undo it: not kept
        except MSPValidationError as e:
            self._valid.put(key, e)
            raise
        self._valid.put(key, window)
        return window

    def satisfies_principal(self, ident, principal) -> bool:
        if getattr(ident, "cert", None) is None:
            return self._msp.satisfies_principal(ident, principal)
        # the principal's two fields ARE its encoding: nothing is
        # re-encoded for the key
        key = (ident.serialize(), principal.principal_classification,
               principal.principal)
        cached = self._princ.get(key)
        if cached is not None:
            out, window = cached
            if window is None or \
                    window[0] <= mspimpl.now_utc() <= window[1]:
                self._princ_hit.add(1)
                return out
        self._princ_miss.add(1)
        # the window BEFORE the verdict: a certificate that turns valid
        # between the two is then not kept as a refusal
        try:
            window = self._window(ident)
        except MSPValidityWindowError:
            return self._msp.satisfies_principal(ident, principal)
        except MSPValidationError:
            window = None                # refused for good under this config
        out = self._msp.satisfies_principal(ident, principal)
        self._princ.put(key, (out, window))
        return out


class LocalMspRegistry:
    """Process-global local MSP + per-channel managers
    (reference: msp/mgmt/mspmgmt.go)."""

    def __init__(self):
        self._lock = RegisteredLock("msp.registry._lock")
        self._local: Optional[Any] = None
        self._chains: Dict[str, Any] = {}

    def set_local(self, msp) -> None:
        with self._lock:
            self._local = msp

    def local(self):
        with self._lock:
            if self._local is None:
                raise RuntimeError("local MSP not initialized")
            return self._local

    def manager_for_chain(self, chain_id: str, factory: Callable = None):
        with self._lock:
            mgr = self._chains.get(chain_id)
            if mgr is None and factory is not None:
                mgr = factory()
                self._chains[chain_id] = mgr
            return mgr


REGISTRY = LocalMspRegistry()
