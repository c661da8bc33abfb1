"""XChaCha20-Poly1305 cryptor backend over the native C++ implementation.

Wire format mirrors the reference cipher backend
(crdt-enc-xchacha20poly1305/src/lib.rs:40-102): a 32-byte random key tagged
with the key version; encrypt draws a random 24-byte XNonce, seals with
XChaCha20-Poly1305, and wraps ``EncBox{nonce, enc_data}`` as msgpack inside a
version-tagged envelope.  Crypto runs off the event loop in the default
thread pool (the reference's spawn_blocking, lib.rs:30,48,81); the C call
holds no Python state so threads scale to the pool width.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import os
import secrets

from .. import native

logger = logging.getLogger("crdt_enc_tpu.xchacha")

_warned_no_native_lens = False


def _warn_no_native_lens(exc: Exception) -> None:
    """Log the native-lengths-pass fallback ONCE per process: the slow
    path must be visible (a binding regression would otherwise silently
    erase the optimization — ADVICE r5), but a box that simply cannot
    build the C-API library must not spam every bulk decrypt."""
    global _warned_no_native_lens
    if not _warned_no_native_lens:
        _warned_no_native_lens = True
        logger.warning(
            "native bytes_lens_join unavailable (%r); using the Python "
            "lengths/join fallback for bulk decrypt", exc
        )
from ..core.cryptor import Cryptor
from ..utils import VersionBytes, codec
from ..utils.versions import XCHACHA_DATA_VERSION_1, XCHACHA_KEY_VERSION_1

KEY_LEN = 32
NONCE_LEN = 24
TAG_LEN = 16


class AeadError(Exception):
    """Authentication failed: wrong key or tampered ciphertext."""


def _check_key(key: bytes) -> None:
    # the native code reads exactly 32 bytes; a short corrupt key blob must
    # fail here, not read past the buffer (reference errors the same way,
    # crdt-enc-xchacha20poly1305 lib.rs:43-45)
    if len(key) != KEY_LEN:
        raise AeadError(f"invalid key length {len(key)}; expected {KEY_LEN}")


def seal_raw(key: bytes, nonce: bytes, data: bytes) -> bytes:
    """The bare AEAD: XChaCha20-Poly1305 seal with an explicit nonce,
    returning ``ct ‖ tag`` (no envelope) — for callers speaking a foreign
    framing, e.g. the reference-remote importer."""
    _check_key(key)
    if len(nonce) != NONCE_LEN:
        raise AeadError(f"invalid nonce length {len(nonce)}")
    lib = native.load()
    kp, _k = native.in_ptr(key)
    np_, _n = native.in_ptr(nonce)
    pp, _p = native.in_ptr(data)
    op, out = native.out_buf(len(data) + TAG_LEN)
    lib.xchacha20poly1305_encrypt(kp, np_, None, 0, pp, len(data), op)
    return out.tobytes()


def open_raw(key: bytes, nonce: bytes, ct: bytes) -> bytes:
    """Inverse of :func:`seal_raw`; raises AeadError on tag mismatch."""
    _check_key(key)
    if len(nonce) != NONCE_LEN or len(ct) < TAG_LEN:
        raise AeadError("malformed nonce/ciphertext")
    lib = native.load()
    kp, _k = native.in_ptr(key)
    np_, _n = native.in_ptr(nonce)
    cp, _c = native.in_ptr(ct)
    op, out = native.out_buf(len(ct) - TAG_LEN)
    rc = lib.xchacha20poly1305_decrypt(kp, np_, None, 0, cp, len(ct), op)
    if rc != 0:
        raise AeadError("authentication failed (wrong key or tampered data)")
    return out.tobytes()


def encrypt_blob(key: bytes, data: bytes) -> bytes:
    """Synchronous seal: data → raw-serialized versioned EncBox envelope."""
    _check_key(key)
    lib = native.load()
    nonce = secrets.token_bytes(NONCE_LEN)
    kp, _k = native.in_ptr(key)
    np_, _n = native.in_ptr(nonce)
    pp, _p = native.in_ptr(data)
    op, out = native.out_buf(len(data) + TAG_LEN)
    lib.xchacha20poly1305_encrypt(kp, np_, None, 0, pp, len(data), op)
    box = codec.pack([nonce, out.tobytes()])
    return VersionBytes(XCHACHA_DATA_VERSION_1, box).serialize()


def decrypt_blob(key: bytes, blob: bytes) -> bytes:
    """Synchronous open: raises AeadError on tag mismatch."""
    _check_key(key)
    lib = native.load()
    # any malformed framing is an auth failure to callers — attacker-shaped
    # input must surface as AeadError, never a raw msgpack/codec exception
    try:
        vb = VersionBytes.deserialize(blob).ensure_version(XCHACHA_DATA_VERSION_1)
        nonce, ct = codec.unpack(vb.content)
        nonce, ct = bytes(nonce), bytes(ct)
    except Exception as e:
        raise AeadError(f"malformed EncBox: {e}") from e
    if len(nonce) != NONCE_LEN or len(ct) < TAG_LEN:
        raise AeadError("malformed EncBox")
    kp, _k = native.in_ptr(key)
    np_, _n = native.in_ptr(nonce)
    cp, _c = native.in_ptr(ct)
    op, out = native.out_buf(len(ct) - TAG_LEN)
    rc = lib.xchacha20poly1305_decrypt(kp, np_, None, 0, cp, len(ct), op)
    if rc != 0:
        raise AeadError("authentication failed (wrong key or tampered data)")
    return out.tobytes()


def decrypt_blobs_packed(key: bytes, blobs: list, n_threads: int = 0):
    """Bulk open to ONE cleartext buffer: ``(buffer, offsets)`` with
    ``offsets`` a ``(n+1,)`` uint64 array (blob i's cleartext is
    ``buffer[offsets[i]:offsets[i+1]]``).  This is the zero-overhead
    shape — the columnar decoders take a packed buffer directly, so at
    100k-tiny-file scale nothing materializes 100k Python objects
    between decrypt and decode (measured: the per-blob memoryview list
    cost ~4x the crypto itself).  Returns None to request the per-blob
    fallback in ``decrypt_blobs``."""
    import numpy as np

    _check_key(key)
    lib = native.load()
    n = len(blobs)
    if n == 0:
        return b"", np.zeros(1, np.uint64)
    if n_threads <= 0:
        n_threads = min(32, os.cpu_count() or 1)

    nonce_offs = np.zeros(n, np.uint64)
    ct_offs = np.zeros(n, np.uint64)
    ct_lens = np.zeros(n, np.uint64)
    vp, _v = native.in_ptr(XCHACHA_DATA_VERSION_1)
    blens = np.empty(n, np.uint64)
    total_in = -1
    try:  # one C-API pass for the lengths (round 5: np.fromiter over
        # 83k Python len() calls cost ~5ms of the config-5 decrypt).
        # expected_n bounds the blens write: a list grown since len()
        # was taken returns -1 instead of running past the array
        slib = native.load_state()
        total_in = int(slib.bytes_lens_join(
            blobs, blens.ctypes.data_as(native.u64p), None, 0, n
        ))
    except (OSError, AttributeError, RuntimeError) as e:
        # expected unavailability only (dlopen/build failure, missing
        # symbol) — anything else is a regression that must surface, not
        # silently retire the fast path (ADVICE r5, low)
        _warn_no_native_lens(e)
    if total_in < 0:  # non-bytes elements or no native lib
        blens = np.fromiter((len(b) for b in blobs), np.uint64, count=n)
    # Pointer-array vs join: skipping the join is a pure memcpy win for
    # LARGE blobs (~40ms per 60MB on this host), but TINY blobs decrypt
    # ~1.3x FASTER from one contiguous buffer (scattered 300B heap reads
    # lose on cache/TLB locality — measured both ways).  Gate on mean
    # blob size; 8KB is comfortably past the crossover.
    use_ptrs = (
        int(blens.sum()) >= 8192 * n
        and all(type(b) is bytes for b in blobs)
    )
    if use_ptrs:
        # pointer-array parse: blobs stay in their own buffers — no join
        # of the whole batch.  The parse emits ABSOLUTE addresses; the
        # scatter below resolves them against a NULL base.
        import ctypes

        ptrs = (ctypes.c_char_p * n)(*blobs)
        total_clear = int(lib.encbox_parse_batch_ptrs(
            ptrs, blens.ctypes.data_as(native.u64p), n, vp,
            nonce_offs.ctypes.data_as(native.u64p),
            ct_offs.ctypes.data_as(native.u64p),
            ct_lens.ctypes.data_as(native.u64p),
        ))
        bp = ctypes.cast(0, native.u8p)
        _b = blobs  # keep every blob alive through the scatter call
    else:
        if total_in >= 0:
            # native join straight into one buffer (skips b"".join's
            # second list walk; same single-memcpy-per-blob cost).  The
            # join is element-count- and capacity-bounded and its return
            # is verified against the lengths pass: pure Python ran
            # between the two ctypes calls, so a caller that mutated
            # ``blobs`` in that window must land on a clean restart, not
            # a heap overrun or a partially-filled buffer (ADVICE r5,
            # medium)
            big = np.empty(total_in, np.uint8)
            joined = int(slib.bytes_lens_join(
                blobs, blens.ctypes.data_as(native.u64p),
                big.ctypes.data_as(native.u8p), total_in, n,
            ))
            if joined != total_in:
                # blobs changed between the passes: EVERY derived array
                # above (blens, n itself) is stale — restart on a
                # private snapshot of the list (the bytes elements are
                # immutable, so the snapshot cannot race again)
                return decrypt_blobs_packed(key, list(blobs), n_threads)
            bp = big.ctypes.data_as(native.u8p)
            _b = big
        else:
            big = b"".join(blobs)
            bp, _b = native.in_ptr(big)
        # offsets AFTER the join, from the same pass that packed the
        # buffer (the join refreshes blens in place): even a mutation
        # that preserved n and the total cannot leave boffs misaligned
        # with big — the frames parse exactly as packed
        boffs = np.zeros(n + 1, np.uint64)
        np.cumsum(blens, out=boffs[1:])
        total_clear = int(lib.encbox_parse_batch(
            bp, boffs.ctypes.data_as(native.u64p), n, vp,
            nonce_offs.ctypes.data_as(native.u64p),
            ct_offs.ctypes.data_as(native.u64p),
            ct_lens.ctypes.data_as(native.u64p),
        ))
    if total_clear >= 0:
        out_offs = np.zeros(n, np.uint64)
        np.cumsum(ct_lens[:-1] - TAG_LEN, out=out_offs[1:])
        op, out = native.out_buf(total_clear)
        kp, _k = native.in_ptr(key)
        ok = np.zeros(n, np.uint8)
        failures = lib.encbox_decrypt_scatter_mt(
            kp, bp,
            nonce_offs.ctypes.data_as(native.u64p),
            ct_offs.ctypes.data_as(native.u64p),
            ct_lens.ctypes.data_as(native.u64p),
            n, op,
            out_offs.ctypes.data_as(native.u64p),
            ok.ctypes.data_as(native.u8p), n_threads,
        )
        if failures:
            bad = int(np.flatnonzero(ok == 0)[0])
            raise AeadError(
                f"authentication failed on {failures}/{n} blobs (first: #{bad})"
            )
        offs = np.zeros(n + 1, np.uint64)
        np.cumsum(ct_lens - TAG_LEN, out=offs[1:])
        return out, offs
    return None


def decrypt_blobs(key: bytes, blobs: list, n_threads: int = 0) -> list:
    """Bulk open: parse every EncBox envelope and decrypt, all natively.

    Returns a list of **memoryviews** (both paths, so callers can't come
    to depend on bytes by accident): zero-copy slices of one shared
    cleartext buffer.  Treat them as transient — each view pins the whole
    buffer, and they are unhashable — and ``bytes(view)`` anything you
    keep.  Bulk pipelines should prefer ``decrypt_blobs_packed``, which
    skips this per-blob view materialization entirely."""
    import numpy as np

    _check_key(key)
    lib = native.load()
    n = len(blobs)
    if n == 0:
        return []
    packed = decrypt_blobs_packed(key, blobs, n_threads)
    if packed is not None:
        out, offs = packed
        view = memoryview(out)
        lo_hi = offs.tolist()
        return [
            view[int(lo_hi[i]) : int(lo_hi[i + 1])] for i in range(n)
        ]
    if n_threads <= 0:
        n_threads = min(32, os.cpu_count() or 1)

    # slow path: per-blob parse with index-precise errors
    nonces = bytearray(NONCE_LEN * n)
    cts = []
    offsets = np.zeros(n + 1, np.uint64)
    out_offsets = np.zeros(n, np.uint64)
    total_ct = 0
    for i, blob in enumerate(blobs):
        try:
            vb = VersionBytes.deserialize(blob).ensure_version(
                XCHACHA_DATA_VERSION_1
            )
            nonce, ct = codec.unpack(vb.content)
            nonce, ct = bytes(nonce), bytes(ct)
        except Exception as e:
            raise AeadError(f"malformed EncBox at index {i}: {e}") from e
        if len(nonce) != NONCE_LEN or len(ct) < TAG_LEN:
            raise AeadError(f"malformed EncBox at index {i}")
        nonces[i * NONCE_LEN : (i + 1) * NONCE_LEN] = nonce
        cts.append(ct)
        out_offsets[i] = total_ct - TAG_LEN * i
        total_ct += len(ct)
        offsets[i + 1] = total_ct
    ct_buf = b"".join(cts)
    kp, _k = native.in_ptr(key)
    np_, _n = native.in_ptr(bytes(nonces))
    cp, _c = native.in_ptr(ct_buf)
    op, out = native.out_buf(total_ct - TAG_LEN * n)
    ok = np.zeros(n, np.uint8)
    failures = lib.xchacha20poly1305_decrypt_batch_mt(
        kp,
        np_,
        cp,
        offsets.ctypes.data_as(native.u64p),
        n,
        op,
        out_offsets.ctypes.data_as(native.u64p),
        ok.ctypes.data_as(native.u8p),
        n_threads,
    )
    if failures:
        bad = int(np.flatnonzero(ok == 0)[0])
        raise AeadError(
            f"authentication failed on {failures}/{n} blobs (first: #{bad})"
        )
    res = []
    for i in range(n):
        lo = int(out_offsets[i])
        hi = lo + (int(offsets[i + 1] - offsets[i]) - TAG_LEN)
        res.append(memoryview(out)[lo:hi])
    return res


class XChaChaCryptor(Cryptor):
    async def gen_key(self) -> VersionBytes:
        return VersionBytes(XCHACHA_KEY_VERSION_1, secrets.token_bytes(KEY_LEN))

    async def encrypt(self, key: VersionBytes, data: bytes) -> bytes:
        return await asyncio.to_thread(self.encrypt_fn(key), data)

    def encrypt_fn(self, key: VersionBytes):
        """Sync seal twin for the seal tail's one worker job; the
        envelope ``encrypt`` produces (it is written over this)."""
        key.ensure_version(XCHACHA_KEY_VERSION_1)
        return functools.partial(encrypt_blob, key.content)

    async def decrypt(self, key: VersionBytes, data: bytes) -> bytes:
        key.ensure_version(XCHACHA_KEY_VERSION_1)
        return await asyncio.to_thread(decrypt_blob, key.content, data)

    async def decrypt_batch(self, key: VersionBytes, blobs: list) -> list:
        key.ensure_version(XCHACHA_KEY_VERSION_1)
        return await asyncio.to_thread(decrypt_blobs, key.content, blobs)

    def decrypt_batch_fn(self, key: VersionBytes):
        """Sync bulk-decrypt twin for the fold service (one thread hop
        for many tenants); identical bytes to ``decrypt_batch``."""
        key.ensure_version(XCHACHA_KEY_VERSION_1)
        material = key.content

        def call(blobs: list) -> list:
            return decrypt_blobs(material, blobs)

        return call
