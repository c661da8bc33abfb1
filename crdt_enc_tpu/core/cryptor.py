"""Cryptor port: abstract AEAD over opaque byte blobs.

Mirrors the reference Cryptor trait (crdt-enc/src/cryptor.rs:11-27): key
generation plus encrypt/decrypt, where keys and ciphertexts are VersionBytes
so cipher formats can rotate independently of everything else.
"""

from __future__ import annotations

from abc import ABC, abstractmethod

from ..utils import VersionBytes


class Cryptor(ABC):
    @abstractmethod
    async def gen_key(self) -> VersionBytes:
        """Fresh random key material, tagged with the cipher's key version."""

    @abstractmethod
    async def encrypt(self, key: VersionBytes, data: bytes) -> bytes:
        """Seal ``data``; returns the raw-serialized cipher envelope (a
        VersionBytes tagged with the cipher's data version)."""

    @abstractmethod
    async def decrypt(self, key: VersionBytes, data: bytes) -> bytes:
        """Open a cipher envelope produced by ``encrypt``."""

    async def decrypt_batch(self, key: VersionBytes, blobs: list) -> list:
        """Open many envelopes sealed with one key.  Default: sequential
        loop; bulk backends override with a parallel native path (the
        decrypt front end of streaming compaction, SURVEY.md §7 step 6)."""
        return [await self.decrypt(key, b) for b in blobs]

    def decrypt_batch_fn(self, key: VersionBytes):
        """Optional SYNC twin of :meth:`decrypt_batch`: a plain callable
        ``(blobs) -> clears`` bound to ``key``, or None when this cipher
        has no GIL-releasing sync path.  The multi-tenant fold service
        uses it to run MANY tenants' decrypts inside ONE worker-thread
        hop — per-tenant ``asyncio.to_thread`` round-trips (~1ms each on
        a busy box) otherwise dominate a cycle over thousands of small
        tenants.  Must be semantically identical to ``decrypt_batch``;
        backends that override one must keep the other in step."""
        return None

    def encrypt_fn(self, key: VersionBytes):
        """Optional SYNC twin of :meth:`encrypt`: a plain callable
        ``(data) -> envelope`` bound to ``key``, or None.  The seal tail
        (``Core._compact_seal``) seals a tenant's snapshot, delta and
        checkpoint inside ONE worker-thread job when the cryptor and the
        storage both offer sync twins (:mod:`.twins`), instead of one
        ``asyncio.to_thread`` round-trip per blob.  Must produce what
        ``encrypt`` produces; a backend keeps the two in step by writing
        ``encrypt`` over this callable.  A cryptor without it, or a
        subclass that overrides ``encrypt`` alone, is awaited on the
        loop as before."""
        return None

    async def init(self, core) -> None: ...

    async def set_remote_meta(self, meta) -> None:
        """Converged config register changed.  Concurrent ``read_remote``
        calls may deliver snapshots out of order — MERGE the register
        (it is a CRDT), never replace local state with it."""
