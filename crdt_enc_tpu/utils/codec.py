"""Canonical msgpack codec.

All persisted CRDT state must serialize deterministically (byte-identical
across host-reference and TPU paths, and across fold orders), so every map is
emitted with lexicographically sorted keys and every container type is
normalized before packing.  msgpack's C extension does the heavy lifting.
"""

from __future__ import annotations

import logging
import threading

import msgpack

logger = logging.getLogger("crdt_enc_tpu.codec")

_native_pack = None  # resolved lazily; False = unavailable for good
_native_same = None  # likewise


def _native_fn(name: str, instead: str):
    """A function of the native state library, or False.  Disabling a
    fast path must be VISIBLE (EXC001): a binding regression would
    otherwise silently put the Python walk back on every pack.
    Logged once a function — the resolution is cached for the process,
    so the fallback decision happens exactly once too."""
    try:
        from .. import native

        return getattr(native.load_state(), name)
    except Exception as e:
        logger.warning("native %s unavailable (%r); %s", name, e, instead)
        return False


def pack(obj) -> bytes:
    """Deterministic msgpack: sorted map keys, bin type for bytes.

    The one serialiser of states, ops, delta links, checkpoint payloads,
    cursors and sort keys; a seal packs the whole state through it once
    a round.  The native canonical packer (statebuild.cpp
    ``canon_pack``) emits the bytes into one growing buffer and puts a
    map in order by sorting an index over that buffer (a record an
    entry, compared by the key's first eight packed bytes); a map whose
    keys arrive in packed order, as every map of a state opened from a
    snapshot does, is neither sorted nor copied.  Objects with types the
    native packer doesn't know (sets, numpy scalars, custom classes)
    fall through to the Python path (``_canon`` + ``packb``, the
    reference: the same bytes, several times dearer), as does an
    environment without the native build.  How it engaged is counted:
    ``canon_packs``, ``canon_maps``, ``canon_maps_sorted`` and
    ``canon_declined`` (docs/observability.md), kept by the library and
    folded into the registry wherever it is read."""
    global _native_pack
    if _native_pack is None:
        _native_pack = _native_fn(
            "canon_pack",
            "using the Python canonicalization path for all packs",
        )
        if _native_pack:
            _register_canon_counters()
    if _native_pack:
        out = _native_pack(obj)
        if out is not None:
            return out
    return msgpack.packb(_canon(obj), use_bin_type=True)


#: the native packer's process totals, in statebuild.cpp
#: ``canon_counters``'s order: calls, maps emitted, maps whose keys arrived
#: out of packed order (sorted and permuted), calls that declined (the
#: Python path ran)
CANON_COUNTERS = (
    "canon_packs", "canon_maps", "canon_maps_sorted", "canon_declined"
)


_canon_totals = None  # statebuild.cpp ``canon_counters``, once resolved
_canon_folded = [0] * len(CANON_COUNTERS)  # what the registry was handed
_canon_fold_lock = threading.Lock()  # between readers


def _register_canon_counters() -> None:
    """Have the registry fold the packer's totals in wherever it is read
    (``record.on_read``, as ``obs.runtime``'s collector totals are): a
    pack bumps four plain integers under the interpreter lock it already
    holds and makes no ``trace.add``."""
    global _canon_totals
    from . import trace

    _canon_totals = _native_fn("canon_counters", "the packer goes uncounted")
    if _canon_totals:
        trace.on_read(_fold_canon_counters)  # once, however many callers


def _fold_canon_counters() -> None:
    """The growth since the last fold.  All four are published once any
    has grown, so a healthy process reads ``canon_declined`` 0 rather
    than nothing."""
    from . import trace

    with _canon_fold_lock:
        now = _canon_totals()
        if now == tuple(_canon_folded):
            return
        grown = {
            name: now[i] - _canon_folded[i]
            for i, name in enumerate(CANON_COUNTERS)
        }
        _canon_folded[:] = now
    trace.fold(grown)


def canon_same(a, b) -> bool | None:
    """``True`` only if ``pack(a) == pack(b)``, found without packing
    either (statebuild.cpp ``canon_same``): the two graphs walked
    together under the packer's own type table, a map by one lookup a
    key.  ``False`` where a difference was found.  ``None`` where it
    cannot say cheaply — a type the native packer declines, a map with a
    ``bool`` or ``float`` key (Python's hashing makes ``1``, ``True`` and
    ``1.0`` one key; their bytes differ), the packer's depth limit, no
    native build — and the caller compares the bytes."""
    global _native_same
    if _native_same is None:
        _native_same = _native_fn(
            "canon_same", "every comparison packs both sides"
        )
    return _native_same(a, b) if _native_same else None


def pack_array(packed_items) -> bytes:
    """``pack([a, b, …])`` from the items' own ``pack`` bytes: an array
    is its header followed by its elements, and canonical form is
    per-element, so a large element packed once (the state, which the
    delta plan already holds as bytes) is never walked again."""
    items = list(packed_items)
    if len(items) > 15:
        raise ValueError("pack_array: fixarray only")
    return b"".join([bytes([0x90 | len(items)])] + items)


def unpack(data: bytes):
    """Decode canonical msgpack.  Arrays come back as tuples (use_list=False)
    so that composite map keys — e.g. (replica, counter) dots — stay hashable."""
    return msgpack.unpackb(
        bytes(data), raw=False, strict_map_key=False, use_list=False
    )


def _canon(obj, as_key: bool = False):
    # scalar fast path first: the overwhelming majority of nodes are
    # scalars/bytes and pack() sits on every hot path (seal,
    # canonical_bytes, sort keys), so per-node isinstance chains add up
    t = obj.__class__
    if t is int or t is bytes or t is str or obj is None or t is bool or t is float:
        return obj
    if isinstance(obj, dict):
        # Sort by the packed key bytes so ordering is type-stable.
        items = [(_canon(k, as_key=True), _canon(v)) for k, v in obj.items()]
        items.sort(key=lambda kv: msgpack.packb(kv[0], use_bin_type=True))
        return {k: v for k, v in items}
    if isinstance(obj, (list, tuple)):
        # Map keys must stay hashable; tuples pack identically to lists.
        seq = [_canon(x, as_key=as_key) for x in obj]
        return tuple(seq) if as_key else seq
    if isinstance(obj, (bytes, bytearray, memoryview)):
        return bytes(obj)
    return obj
