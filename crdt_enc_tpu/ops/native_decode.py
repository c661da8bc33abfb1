"""Batched native decode: decrypted op payloads → columnar arrays.

The bulk front end (SURVEY.md §7 step 6, §2.2 "decode op files directly
into pre-allocated arrays without Python-object churn"): each payload is
the msgpack body of one op file; the C++ decoder flattens every payload
into shared (kind, member-span, actor, counter) arrays, and member spans
are interned *vectorized* — grouped by span length, ``np.unique(axis=0)``
over byte matrices — so no per-row Python executes on the million-op path.

Returns None when a payload defeats the native decoder (unknown actor,
non-canonical encoding); callers fall back to the per-op Python path.
"""

from __future__ import annotations

import ctypes

import numpy as np

from .. import native
from ..utils import codec

_i8p = ctypes.POINTER(ctypes.c_int8)
_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)


def decode_orset_payload_batch(payloads: list, actors_sorted: list):
    """Decode many ORSet op payloads against a sorted actor table.

    Returns ``(kind, member_idx, actor_idx, counter, members)`` — flat
    int arrays over all payloads' rows plus the interned member-object
    list (first-appearance order) — or None to request Python fallback.
    """
    part = decode_orset_payload_spans(payloads, actors_sorted)
    if part is None:
        return None
    return intern_orset_spans(part)


def _shared_buffer_of(payloads):
    """The single object every memoryview payload slices, or None.

    The batch decrypt hands out zero-copy views of one cleartext buffer
    (``decrypt_blobs``); spotting that here lets the decoder skip
    re-joining what is already contiguous memory."""
    first = payloads[0] if payloads else None
    if type(first) is not memoryview:
        return None
    obj = first.obj
    for p in payloads:
        if type(p) is not memoryview or p.obj is not obj or not p.contiguous:
            return None
    return obj


def decode_orset_payload_spans(payloads, actors_sorted: list, cache=None):
    """Native decode of one payload chunk to raw span columns.

    ``payloads`` is a list of blob bytes or of views into the batch
    decrypt's one cleartext buffer (``_shared_buffer_of``).

    ``cache`` (optional dict the caller owns for the life of one actor
    table, e.g. a payload stream): reuses the flattened actor table and
    its native hash index across chunks — rebuilding both per chunk at
    100k actors costs more than the decode.

    Returns ``(buf, kind, moff, mlen, actor, counter)`` — member values
    stay as (offset, length) spans into ``buf``, interned by
    ``intern_orset_spans`` — or None to request Python fallback.
    """
    lib = native.load()
    n_payloads = len(payloads)
    if n_payloads == 0:
        return (
            np.zeros(0, np.uint8),
            np.zeros(0, np.int8),
            np.zeros(0, np.uint64),
            np.zeros(0, np.uint64),
            np.zeros(0, np.int32),
            np.zeros(0, np.int32),
        )
    lens = np.array([len(p) for p in payloads], np.uint64)
    big = _shared_buffer_of(payloads)
    if big is not None:
        # every payload is a view into ONE buffer (the batch decrypt's
        # packed cleartext): address arithmetic recovers the offsets —
        # no join of the whole chunk
        base0 = np.frombuffer(big, np.uint8).ctypes.data
        bases = np.fromiter(
            (np.frombuffer(p, np.uint8).ctypes.data - base0
             for p in payloads),
            np.uint64, count=n_payloads,
        )
    else:
        big = b"".join(payloads)
        bases = np.zeros(n_payloads, np.uint64)
        np.cumsum(lens[:-1], out=bases[1:])
    buf = np.frombuffer(big, np.uint8)
    bp = buf.ctypes.data_as(native.u8p)
    if cache is not None and "actors" in cache:
        actors_flat, slots = cache["actors"]
    else:
        actors_flat = b"".join(actors_sorted)
        # hash index over the actor table: one probe per op instead of a
        # 17-deep binary search at 100k actors (~2x the decode cost)
        n_slots = 8
        while n_slots < 2 * max(len(actors_sorted), 1):
            n_slots *= 2
        slots = np.empty(n_slots, np.int32)
        lib.actor_hash_build(
            native.in_ptr(actors_flat)[0], len(actors_sorted),
            slots.ctypes.data_as(_i32p), n_slots,
        )
        if cache is not None:
            cache["actors"] = (actors_flat, slots)
    ap, _a = native.in_ptr(actors_flat)
    basep = bases.ctypes.data_as(native.u64p)
    lenp = lens.ctypes.data_as(native.u64p)

    # single-pass growable decode: validates framing and emits rows in
    # one msgpack walk (the old count+decode protocol parsed everything
    # twice — ~half the decode cost at 100k-file scale)
    n_rows = np.zeros(1, np.int64)
    handle = lib.orset_decode_batch_grow(
        bp, basep, lenp, n_payloads, ap, len(actors_sorted),
        slots.ctypes.data_as(_i32p), len(slots),
        n_rows.ctypes.data_as(_i64p),
    )
    if not handle:
        return None
    taken = False
    try:
        total = int(n_rows[0])
        kind = np.zeros(total, np.int8)
        moff = np.zeros(total, np.uint64)
        mlen = np.zeros(total, np.uint64)
        actor = np.zeros(total, np.int32)
        counter = np.zeros(total, np.int32)
        taken = True  # take() frees the handle even if a copy would fail
        lib.orset_decode_take(
            handle,
            kind.ctypes.data_as(_i8p),
            moff.ctypes.data_as(native.u64p),
            mlen.ctypes.data_as(native.u64p),
            actor.ctypes.data_as(_i32p),
            counter.ctypes.data_as(_i32p),
        )
    finally:
        if not taken:  # e.g. MemoryError sizing the output arrays
            lib.orset_decode_drop(handle)
    return buf, kind, moff, mlen, actor, counter


def intern_orset_spans(part, *, with_bytes: bool = False):
    """Intern the member spans of one chunk from
    ``decode_orset_payload_spans``.  Returns the same tuple as
    ``decode_orset_payload_batch``; with ``with_bytes`` a sixth element
    carries each unique member's WIRE bytes (the interning key), so a
    session-level remap can recognize an already-seen member with one
    bytes-dict hit instead of an object intern + canonical re-pack per
    chunk."""
    buf, kind, moff, mlen, actor, counter = part
    if len(kind) == 0:
        if with_bytes:
            return kind, np.zeros(0, np.int32), actor, counter, [], []
        return kind, np.zeros(0, np.int32), actor, counter, []
    if with_bytes:
        member_idx, members, member_bytes = intern_spans(
            buf, moff, mlen, return_bytes=True
        )
        return kind, member_idx, actor, counter, members, member_bytes
    member_idx, members = intern_spans(buf, moff, mlen)
    return kind, member_idx, actor, counter, members


def intern_spans(buf: np.ndarray, off: np.ndarray, length: np.ndarray,
                 *, return_bytes: bool = False):
    """Span interning: rows → dense member indices + decoded unique member
    objects.  The native open-addressing hash pass costs one linear scan
    (the numpy fallback below sorts 8 bytes per row — measured ~8× slower
    at the 8M-row e2e scale); unique spans then decode via codec, a few
    thousand objects at most.  ``return_bytes`` adds the unique spans'
    raw wire bytes as a third element (one small ``bytes`` per unique
    member — the caller's cross-chunk dedup key)."""
    n = len(off)
    if n == 0:
        if return_bytes:
            return np.zeros(0, np.int32), [], []
        return np.zeros(0, np.int32), []
    if (np.asarray(length) == 0).any():
        raise ValueError("empty member span")
    try:
        lib = native.load()
        off64 = np.ascontiguousarray(off, np.uint64)
        len64 = np.ascontiguousarray(length, np.uint64)
        cap = 1 << max(11, (2 * n - 1).bit_length())
        table = np.full(cap, -1, np.int64)
        idx = np.zeros(n, np.int32)
        uniq_off = np.zeros(n, np.uint64)
        uniq_len = np.zeros(n, np.uint64)
        bp = buf.ctypes.data_as(native.u8p)
        got = lib.intern_spans_native(
            bp, off64.ctypes.data_as(native.u64p),
            len64.ctypes.data_as(native.u64p), n,
            table.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), cap,
            idx.ctypes.data_as(_i32p),
            uniq_off.ctypes.data_as(native.u64p),
            uniq_len.ctypes.data_as(native.u64p), n,
        )
    except RuntimeError:  # native lib unavailable
        got = -1
    if got >= 0:
        if return_bytes:
            # bytes-only mode: do NOT decode the unique spans — the
            # session remap recognizes seen spans by bytes and decodes
            # only genuinely new members (codec.unpack per distinct
            # member per STREAM, not per chunk — measured ~10ms of the
            # config-5 wall as pure re-decode of already-known members)
            mv = memoryview(np.ascontiguousarray(buf))
            spans = [
                bytes(mv[int(o) : int(o) + int(ln)])
                for o, ln in zip(
                    uniq_off[:got].tolist(), uniq_len[:got].tolist()
                )
            ]
            return idx, None, spans
        mv = memoryview(np.ascontiguousarray(buf))
        members = [
            codec.unpack(mv[int(o) : int(o) + int(ln)])
            for o, ln in zip(uniq_off[:got].tolist(), uniq_len[:got].tolist())
        ]
        return idx, members
    if return_bytes:
        idx, members, spans = _intern_spans_numpy(
            buf, off, length, return_bytes=True
        )
        return idx, members, spans
    return _intern_spans_numpy(buf, off, length)


def _intern_spans_numpy(buf: np.ndarray, off: np.ndarray, length: np.ndarray,
                        *, return_bytes: bool = False):
    """Vectorized fallback: groups rows by span length; spans of ≤ 8 bytes
    (the overwhelmingly common case — small ints, short bytes) pack into
    uint64 so ``np.unique`` sorts scalars (~10× faster than the byte-matrix
    ``axis=0`` path, which argsorts rows); longer spans take the matrix
    path."""
    n = len(off)
    member_idx = np.zeros(n, np.int32)
    members: list = []
    spans: list = []
    off = off.astype(np.int64)
    length = length.astype(np.int64)
    for L in np.unique(length):
        Li = int(L)
        sel = np.flatnonzero(length == L)
        if Li == 0:
            # zero-length span cannot be valid msgpack; caller's decoder
            # never emits it, but guard anyway
            raise ValueError("empty member span")
        # gather rows × L bytes in one fancy index
        mat = buf[off[sel][:, None] + np.arange(Li)[None, :]]
        base = len(members)
        if Li <= 8:
            # pack the L bytes big-endian into one uint64 per row (same
            # order as byte-wise comparison, so unique order matches)
            packed = np.zeros(len(sel), np.uint64)
            for b in range(Li):
                packed = (packed << np.uint64(8)) | mat[:, b].astype(np.uint64)
            uniq, inv = np.unique(packed, return_inverse=True)
            for u in uniq:
                raw = int(u).to_bytes(Li, "big")
                members.append(codec.unpack(raw))
                spans.append(raw)
        else:
            uniq, inv = np.unique(mat, axis=0, return_inverse=True)
            for u in uniq:
                raw = u.tobytes()
                members.append(codec.unpack(raw))
                spans.append(raw)
        member_idx[sel] = base + inv.astype(np.int32)
    if return_bytes:
        return member_idx, members, spans
    return member_idx, members


def decode_counter_payload_batch(payloads: list, actors_sorted: list):
    """Decode many counter op payloads.  Returns ``(sign, actor_idx,
    counter)`` flat arrays or None for Python fallback."""
    lib = native.load()
    if not payloads:
        return np.zeros(0, np.int8), np.zeros(0, np.int32), np.zeros(0, np.int32)
    big = b"".join(payloads)
    buf = np.frombuffer(big, np.uint8)
    actors_flat = b"".join(actors_sorted)
    ap, _a = native.in_ptr(actors_flat)

    lens = np.array([len(p) for p in payloads], np.uint64)
    bases = np.zeros(len(payloads), np.uint64)
    np.cumsum(lens[:-1], out=bases[1:])

    # one native call; every op costs >1 encoded byte, so total payload
    # bytes bounds the row count
    cap = max(len(big), 1)
    sign = np.zeros(cap, np.int8)
    actor = np.zeros(cap, np.int32)
    counter = np.zeros(cap, np.int32)
    got = lib.counter_decode_batch(
        buf.ctypes.data_as(native.u8p),
        bases.ctypes.data_as(native.u64p),
        lens.ctypes.data_as(native.u64p),
        len(payloads),
        ap,
        len(actors_sorted),
        sign.ctypes.data_as(_i8p),
        actor.ctypes.data_as(_i32p),
        counter.ctypes.data_as(_i32p),
    )
    if got < 0:
        return None
    return sign[:got], actor[:got], counter[:got]
