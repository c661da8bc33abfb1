"""Tenant-keyed warm tier: an LRU of per-tenant fold planes.

The single-tenant accelerator keeps ONE set of device-resident result
planes (``parallel/accel._OrsetPlaneCache``) so the next fold on an
un-mutated state skips the sparse state walk and the full-plane upload.
A fold service cycling over thousands of tenants needs the same trick
*per tenant*, under an explicit memory budget: this tier holds each
tenant's last fold output — the ``(clock, add, rm)`` planes exactly as
the batched kernel produced them (device-resident arrays; on the CPU
backend that is host memory), plus the vocabularies they are indexed by
— keyed by the tenant state's identity and validated by the same
``_mut`` mutation-epoch token the accelerator cache uses, so ANY host
mutation (an apply, a snapshot merge, another path's writeback) silently
expires the entry.

Budget and visibility: ``byte_budget`` bounds the summed plane bytes;
inserting past it evicts least-recently-used entries first (the newest
entry itself is never evicted at insert — a single over-budget tenant
still gets exactly one cycle of reuse and then ages out normally).
``serve_warm_hits`` / ``serve_warm_misses`` / ``serve_warm_evictions`` /
``serve_warm_evicted_bytes`` counters and the ``serve_warm_bytes`` gauge
(docs/observability.md) make the tier's behavior auditable per cycle.

Entries expose the same ``members / replicas / canon / planes``
attributes as the accelerator's plane cache, so the service reuses the
accelerator's remap and pad helpers (``TpuAccelerator._remap_to_cache``,
``_cached_planes_padded``) — one implementation of the vocab-collision
guard, not two.
"""

from __future__ import annotations

import weakref
from collections import OrderedDict

from ..utils import trace

DEFAULT_BYTE_BUDGET = 256 << 20  # summed plane bytes across tenants


class WarmEntry:
    """One tenant's cached fold planes (see module docs)."""

    __slots__ = ("ref", "token", "members", "replicas", "planes", "canon",
                 "nbytes", "seal_name")

    def __init__(self, ref, token, members, replicas, planes, canon):
        self.ref = ref
        self.token = token
        self.members = members
        self.replicas = replicas
        self.planes = planes  # (clock, add, rm) arrays, padded shapes
        self.canon = canon  # member slot -> canonical packed bytes
        self.nbytes = sum(int(getattr(p, "nbytes", 0)) for p in planes)
        # content-addressed name of the sealed snapshot these planes ARE
        # (stamped after a successful seal by PlaneWarmTier.stamp_seal);
        # None until then.  When it matches the core's delta-base name,
        # the next cycle can cut the tenant's delta on device from these
        # planes and the core need not retain the host-resident base
        # bytes at all (docs/delta.md "device-cut deltas").
        self.seal_name = None


class PlaneWarmTier:
    """LRU of :class:`WarmEntry` keyed by tenant state identity.

    ``mesh_key`` pins the tier to one device-mesh identity: a tier built
    for a mesh holds device-SHARDED plane slices (the sharded mega-fold's
    outputs), which are only addressable under that same mesh — a
    service must never hand a foreign tier its entries.  The key is
    compared by identity in :meth:`compatible_with`; ``None`` = the
    single-chip tier (host/device-0 planes, the historical behavior)."""

    def __init__(self, byte_budget: int = DEFAULT_BYTE_BUDGET,
                 mesh_key=None):
        if byte_budget < 1:
            raise ValueError("byte_budget must be positive")
        self.byte_budget = int(byte_budget)
        self.mesh_key = mesh_key
        self._entries: OrderedDict[int, WarmEntry] = OrderedDict()
        self._bytes = 0

    def compatible_with(self, mesh_key) -> bool:
        """True when entries stored by this tier are addressable under
        ``mesh_key`` (identity match — mesh equality is identity in
        jax)."""
        return self.mesh_key is mesh_key

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def bytes_held(self) -> int:
        return self._bytes

    def _drop(self, key: int) -> int:
        """Forget ``key``'s entry (its plane buffers go with their last
        reference); returns the plane bytes it held, 0 for no entry."""
        entry = self._entries.pop(key, None)
        if entry is None:
            return 0
        self._bytes -= entry.nbytes
        trace.gauge("serve_warm_bytes", self._bytes)
        return entry.nbytes

    def lookup(self, state) -> WarmEntry | None:
        """The live entry for ``state``, or None (no entry, entry for a
        dead/foreign object, or the state mutated since it was stored —
        stale entries are dropped on sight, they can never be right
        again).  A hit refreshes the entry's LRU position."""
        key = id(state)
        entry = self._entries.get(key)
        if entry is None:
            trace.add("serve_warm_misses", 1)
            return None
        if entry.ref() is not state or entry.token != getattr(
            state, "_mut", None
        ):
            self._drop(key)
            trace.add("serve_warm_misses", 1)
            # refine the reason: an entry EXISTED but the state mutated
            # under it (or the id was recycled) — the mut-epoch expiry
            # the continuation fallback tests count, vs. a plain
            # never-stored / LRU-evicted miss
            trace.add("serve_warm_expired", 1)
            return None
        self._entries.move_to_end(key)
        trace.add("serve_warm_hits", 1)
        return entry

    def store(self, state, members, replicas, planes, canon=None) -> WarmEntry:
        """Record ``state``'s post-fold planes as its warm entry (token =
        the state's CURRENT ``_mut`` — call after the writeback bump),
        then evict LRU entries past the byte budget.  The weakref
        finalizer drops the entry the moment the state dies, so plane
        buffers never outlive the tenant they cache."""
        key = id(state)
        self._drop(key)

        tier_ref = weakref.ref(self)

        def _finalize(dead_ref, _key=key):
            tier = tier_ref()
            if tier is not None:
                e = tier._entries.get(_key)
                if e is not None and e.ref is dead_ref:
                    tier._drop(_key)

        entry = WarmEntry(
            weakref.ref(state, _finalize), getattr(state, "_mut", None),
            members, replicas, planes, canon if canon is not None else {},
        )
        self._entries[key] = entry
        self._bytes += entry.nbytes
        while self._bytes > self.byte_budget and len(self._entries) > 1:
            oldest = next(iter(self._entries))
            if oldest == key:
                break  # never evict the entry being inserted
            trace.add("serve_warm_evicted_bytes", self._drop(oldest))
            trace.add("serve_warm_evictions", 1)
        trace.gauge("serve_warm_bytes", self._bytes)
        return entry

    def stamp_seal(self, state, seal_name) -> bool:
        """Mark ``state``'s live warm entry as byte-identical to the
        sealed snapshot ``seal_name`` — called by the service AFTER a
        successful seal, iff the state has not mutated since the planes
        were stored.  Deliberately not a :meth:`lookup` (no hit/miss
        accounting, no LRU refresh): this is a seal-time annotation, not
        a use.  Returns False (and stamps nothing) on any doubt."""
        entry = self._entries.get(id(state))
        if (
            entry is None
            or entry.ref() is not state
            or entry.token != getattr(state, "_mut", None)
        ):
            return False
        entry.seal_name = seal_name
        return True
