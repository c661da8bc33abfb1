"""Canonical msgpack codec.

All persisted CRDT state must serialize deterministically (byte-identical
across host-reference and TPU paths, and across fold orders), so every map is
emitted with lexicographically sorted keys and every container type is
normalized before packing.  msgpack's C extension does the heavy lifting.
"""

from __future__ import annotations

import logging

import msgpack

logger = logging.getLogger("crdt_enc_tpu.codec")

_native_pack = None  # resolved lazily; False = unavailable for good
_native_same = None  # likewise


def _native_fn(name: str, instead: str):
    """A function of the native state library, or False.  Disabling a
    fast path must be VISIBLE (EXC001): a binding regression would
    otherwise silently put ~400ms back on every canonical_bytes call.
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

    Hot path (sealing a compacted state, canonical_bytes in every
    equality check): the native canonical packer (statebuild.cpp
    ``canon_pack``) emits the identical bytes in one C pass — the
    Python ``_canon`` walk + ``packb`` cost ~400ms on a 100k-replica
    state.  Objects with types the native packer doesn't know (sets,
    numpy scalars, custom classes) fall through to the Python path, as
    does an environment without the native build."""
    global _native_pack
    if _native_pack is None:
        _native_pack = _native_fn(
            "canon_pack",
            "using the Python canonicalization path for all packs",
        )
    if _native_pack:
        out = _native_pack(obj)
        if out is not None:
            return out
    return msgpack.packb(_canon(obj), use_bin_type=True)


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
