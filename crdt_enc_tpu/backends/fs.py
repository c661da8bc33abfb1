"""Filesystem storage backend — the production port over a synced directory.

Rebuilds crdt-enc-tokio (crdt-enc-tokio/src/lib.rs) on asyncio + thread
offload:

* layout: ``local/meta-data.msgpack`` (lib.rs:51), ``remote/meta/<hash>``
  (lib.rs:79), ``remote/states/<hash>`` (lib.rs:139),
  ``remote/ops/<actor-hex>/<N>`` (lib.rs:247-257);
* immutable content-addressed writes: SHA3-256 of the blob, base32-nopad
  name, ``O_CREAT|O_EXCL`` then fsync of file and directory
  (write_content_addressible_file, lib.rs:403-432) — a replay of the same
  content is a no-op, a name collision with different content is an error;
* op logs scan densely from the first requested version until the first
  missing file (lib.rs:254-269); actors fan out concurrently (lib.rs:274);
* missing directories/files read as empty/None and removes tolerate
  already-gone files (lib.rs:376-401, 434-440) — the sync tool may race us.

Durability beyond the reference: op-file writes go through a same-directory
tmp file + fsync + atomic rename (the reference left this as a TODO,
lib.rs:343-344), so a crash mid-write can never leave a torn op file where
the dense version scan would find it.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import contextvars
import functools
import itertools
import logging
import os
import uuid

from ..core.storage import Storage
from ..models.vclock import Actor
from ..utils import trace
from .memory import content_name

FS_CONCURRENCY = 32  # reference buffer_unordered(32), crdt-enc-tokio lib.rs:112

logger = logging.getLogger("crdt_enc_tpu.fs")

_warned_native_scan = False  # the no-toolchain fallback warns once, not per scan


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_tmp(d: str, data: bytes) -> str:
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".tmp-{uuid.uuid4().hex}")
    fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
    try:
        os.write(fd, data)
        os.fsync(fd)
    finally:
        os.close(fd)
    return tmp


def _write_file_atomic(path: str, data: bytes) -> None:
    """tmp + fsync + rename (last-writer-wins — for the mutable local meta)."""
    d = os.path.dirname(path)
    tmp = _write_tmp(d, data)
    os.rename(tmp, path)
    _fsync_dir(d)


def _write_file_new(
    path: str, data: bytes, *, relink_vanished_collider: bool = True
) -> None:
    """Immutable publish: tmp + fsync, then ``os.link`` — which fails with
    EEXIST atomically, unlike an exists-check + rename (TOCTOU) or rename
    itself (silent clobber).  An existing file with identical content is an
    idempotent content-addressed replay; different content is an error.

    Concurrent-GC tolerance: another replica's compactor may remove the
    colliding file — or the whole emptied directory (``remove_ops``
    rmdir's an emptied actor dir) — between any two steps here.  A
    vanished DIRECTORY always retries (``makedirs`` recreates it; the
    name was never observable with other content).  A vanished
    COLLIDER retries only for content-addressed names
    (``relink_vanished_collider=True``: same name ⇒ same bytes, so the
    relink republishes identical content).  Version-addressed op files
    pass False: the collider existed moments ago, so a peer may have
    folded it into a snapshot — republishing DIFFERENT content at that
    version would be invisible to every cursor already past it, a
    silent write loss; the burned version surfaces as
    ``FileExistsError`` and the producer's probe loop picks the next
    one.  The retry is bounded — each round needs a fresh removal, and
    removals need fresh content to collect."""
    d = os.path.dirname(path)
    for _ in range(8):
        try:
            tmp = _write_tmp(d, data)
        except FileNotFoundError:
            continue  # dir rmdir'd between makedirs and the tmp open
        try:
            try:
                os.link(tmp, path)
            except FileExistsError:
                try:
                    with open(path, "rb") as f:
                        if f.read() == data:
                            return
                except FileNotFoundError:
                    if relink_vanished_collider:
                        continue  # content-addressed: relink same bytes
                    raise FileExistsError(
                        f"{path}: version burned by a GC'd concurrent "
                        "write; probe forward"
                    ) from None
                raise FileExistsError(
                    f"{path} exists with different content"
                ) from None
            except FileNotFoundError:
                continue  # dir rmdir'd between the tmp write and link
        finally:
            _remove_quiet(tmp)
        try:
            _fsync_dir(d)
        except FileNotFoundError:
            # the directory — and with it our freshly linked file — was
            # emptied and rmdir'd by a concurrent compactor after the
            # link: the write happened and was legitimately collected,
            # exactly the observable world of write-then-GC.
            pass
        return
    raise OSError(f"could not publish {path}: directory kept vanishing")


def _read_file(path: str) -> bytes | None:
    try:
        with open(path, "rb") as f:
            return f.read()
    except FileNotFoundError:
        return None


def _list_dir(path: str) -> list[str]:
    try:
        return [n for n in os.listdir(path) if not n.startswith(".tmp-")]
    except FileNotFoundError:
        return []


def _remove_quiet(path: str) -> None:
    try:
        os.remove(path)
    except FileNotFoundError:
        pass


def _native_step(step: str, *args, counted: str | None = "fs_steps") -> bool:
    """One file step as ONE call into ``native/io.cpp`` (interpreter lock
    released, paths relative to a directory opened once; the protocol is
    in that file's header).  True iff the step ran clean (status 0): the
    caller is done.  On any other status — the name exists, a directory
    vanished, any errno, or no library at all — the caller runs its Python
    helper from the start, so every rule about a surprise (identical
    replay, burned version, the retry of a vanishing directory) is
    written once, there.  The one exception is a NEGATIVE status: the
    name is in place and the directory's flush failed, which a replay
    would read back as an identical-content success; it is raised as
    ``_fsync_dir`` would have raised it.  ``counted`` names the counter
    pair: the seal tail's writes are ``fs_steps_*``, a poll's reads
    ``fs_reads_*``, the chunk iterator's windows ``fs_chunk_reads_*``;
    None where the caller's one read may be several steps and it counts
    the read itself (``load_ops_sync``)."""
    from .. import native

    try:
        lib = native.load()
    except Exception:
        FsStorage._warn_native_unavailable()
    else:
        status = getattr(lib, step)(*args)
        if status == 0:
            if counted:
                trace.add(counted + "_native", 1)
            return True
        if status < 0:
            raise OSError(-status, os.strerror(-status), os.fsdecode(args[0]))
    if counted:
        trace.add(counted + "_python", 1)
    return False


def _publish_native(step: str, path: str, data: bytes) -> bool:
    d, name = os.path.split(path)
    return _native_step(
        step, os.fsencode(d), os.fsencode(name), data, len(data)
    )


def _remove_prefixes_native(
    base: str, actor_last_versions: list[tuple[Actor, int]]
) -> bool:
    import ctypes

    n = len(actor_last_versions)
    # clamped into int64: the C loop judges names of at most 18 digits, so
    # a bound past either end compares as the bound itself would
    lasts = (max(-1, min(last, (1 << 63) - 1)) for _, last in actor_last_versions)
    return _native_step(
        "remove_log_prefixes", os.fsencode(base), n,
        b"".join(actor.hex().encode() + b"\0" for actor, _ in actor_last_versions),
        (ctypes.c_int64 * n)(*lasts),
    )


LIST_NAMES_BYTES = 1 << 16  # one listing's names; a longer one is Python's


def _list_names(path: str) -> list[str]:
    """A poll's listing of one directory: what ``_list_dir`` gives (an
    absent directory is empty, ``.tmp-`` files in flight are left out)
    from ONE native call, or from ``_list_dir`` itself on any other
    status (``fs_reads_*``)."""
    import ctypes

    buf = ctypes.create_string_buffer(LIST_NAMES_BYTES)
    n, used = ctypes.c_int64(), ctypes.c_int64()
    if not _native_step(
        "list_dir_names", os.fsencode(path), buf, len(buf),
        ctypes.byref(n), ctypes.byref(used), counted="fs_reads",
    ):
        return _list_dir(path)
    names = os.fsdecode(buf[: used.value]).split("\0")[: n.value]
    return [name for name in names if not name.startswith(".tmp-")]


def _op_actors(names: list[str]) -> list[Actor]:
    """The actors among the names of a log family's directory."""
    actors = []
    for n in names:
        try:
            actors.append(bytes.fromhex(n))
        except ValueError:
            continue  # foreign junk in the synced dir is not ours to judge
    return sorted(a for a in actors if len(a) == 16)


class FsStorage(Storage):
    def __init__(self, local_path: str, remote_path: str):
        self.local = os.fspath(local_path)
        self.remote = os.fspath(remote_path)
        self._sem = asyncio.Semaphore(FS_CONCURRENCY)

    async def _run(self, fn, *args):
        async with self._sem:
            return await asyncio.to_thread(fn, *args)

    # paths
    def _local_meta_path(self) -> str:
        return os.path.join(self.local, "meta-data.msgpack")

    def _local_checkpoint_path(self) -> str:
        return os.path.join(self.local, "checkpoint.msgpack")

    def _meta_dir(self) -> str:
        return os.path.join(self.remote, "meta")

    def _states_dir(self) -> str:
        return os.path.join(self.remote, "states")

    def _ops_dir(self, actor: Actor | None = None) -> str:
        base = os.path.join(self.remote, "ops")
        return os.path.join(base, actor.hex()) if actor is not None else base

    def _deltas_dir(self, actor: Actor | None = None) -> str:
        base = os.path.join(self.remote, "deltas")
        return os.path.join(base, actor.hex()) if actor is not None else base

    # -- local meta --------------------------------------------------------
    async def load_local_meta(self) -> bytes | None:
        return await self._run(_read_file, self._local_meta_path())

    # The seven calls of the seal tail exist as plain functions
    # (``*_sync``, the port's optional sync twins: core/storage.py); the
    # awaitables are the same functions handed to ``_run``.
    def store_local_meta_sync(self, data: bytes) -> None:
        self._store_local(self._local_meta_path(), bytes(data))

    @staticmethod
    def _store_local(path: str, data: bytes) -> None:
        if not _publish_native("write_file_atomic", path, data):
            _write_file_atomic(path, data)

    async def store_local_meta(self, data: bytes) -> None:
        await self._run(self.store_local_meta_sync, data)

    # -- local fold checkpoint ---------------------------------------------
    # Same durability discipline as the local meta: tmp + fsync + atomic
    # rename, so a crash mid-write leaves the previous checkpoint (or
    # none) — never a torn blob the dense warm-open path could trust.
    async def load_local_checkpoint(self) -> bytes | None:
        return await self._run(_read_file, self._local_checkpoint_path())

    def store_local_checkpoint_sync(self, data: bytes) -> None:
        self._store_local(self._local_checkpoint_path(), bytes(data))

    async def store_local_checkpoint(self, data: bytes) -> None:
        await self._run(self.store_local_checkpoint_sync, data)

    async def remove_local_checkpoint(self) -> None:
        await self._run(_remove_quiet, self._local_checkpoint_path())

    # -- content-addressed families ---------------------------------------
    async def _load_ca(self, d: str, names: list[str]) -> list[tuple[str, bytes]]:
        async def one(n):
            raw = await self._run(_read_file, os.path.join(d, n))
            return (n, raw) if raw is not None else None

        loaded = await asyncio.gather(*(one(n) for n in names))
        return [x for x in loaded if x is not None]

    @staticmethod
    def _store_ca(d: str, data: bytes) -> str:
        name = content_name(data)
        path, data = os.path.join(d, name), bytes(data)
        if not _publish_native("publish_file_new", path, data):
            _write_file_new(path, data)
        return name

    @staticmethod
    def _remove_ca(d: str, names: list[str]) -> None:
        for n in names:
            _remove_quiet(os.path.join(d, n))

    # The four reads of a poll exist as plain functions too (``*_sync``,
    # the port's optional sync twins: core/storage.py INGEST_TWINS), each
    # ONE native call; the awaitables are the same functions handed to
    # ``_run``.
    def list_remote_meta_names_sync(self) -> list[str]:
        return sorted(_list_names(self._meta_dir()))

    async def list_remote_meta_names(self) -> list[str]:
        return await self._run(self.list_remote_meta_names_sync)

    async def load_remote_metas(self, names: list[str]) -> list[tuple[str, bytes]]:
        return await self._load_ca(self._meta_dir(), names)

    async def store_remote_meta(self, data: bytes) -> str:
        return await self._run(self._store_ca, self._meta_dir(), data)

    async def remove_remote_metas(self, names: list[str]) -> None:
        await self._run(self._remove_ca, self._meta_dir(), names)

    def list_state_names_sync(self) -> list[str]:
        return sorted(_list_names(self._states_dir()))

    async def list_state_names(self) -> list[str]:
        return await self._run(self.list_state_names_sync)

    async def load_states(self, names: list[str]) -> list[tuple[str, bytes]]:
        return await self._load_ca(self._states_dir(), names)

    def store_state_sync(self, data: bytes) -> str:
        return self._store_ca(self._states_dir(), data)

    async def store_state(self, data: bytes) -> str:
        return await self._run(self.store_state_sync, data)

    def remove_states_sync(self, names: list[str]) -> None:
        d = self._states_dir()
        if names and not _native_step(
            "remove_names", os.fsencode(d), len(names),
            b"".join(os.fsencode(n) + b"\0" for n in names),
        ):
            self._remove_ca(d, names)

    async def remove_states(self, names: list[str]) -> None:
        await self._run(self.remove_states_sync, names)

    # -- op logs -----------------------------------------------------------
    def list_op_actors_sync(self) -> list[Actor]:
        return _op_actors(_list_names(self._ops_dir()))

    async def list_op_actors(self) -> list[Actor]:
        return await self._run(self.list_op_actors_sync)

    # one round of the size-only scan (``stat_ops``): capped in files so
    # that one gigantic log never demands an unbounded array; the loop
    # continues where the previous round stopped
    NATIVE_SCAN_BATCH = 65_536
    # Chunk budget for the pipelined ingest (iter_op_chunks): small enough
    # that a few in-flight chunks bound host memory AND the read/decrypt/
    # decode/reduce stages get real overlap, large enough that the batched
    # decrypt/decode amortize.
    CHUNK_BYTES = 24 << 20

    def _scan_sizes_native(self, lib, d: bytes, v: int):
        """One bounded native size-only pass (``scan_op_sizes``): the
        dense per-file sizes from version ``v``, as ``(sizes[:n],
        exhausted)`` — ``exhausted`` means the directory ran out inside
        this round.  The single encoding of the native scan calling
        convention, under ``stat_ops``."""
        import ctypes

        import numpy as np

        i64p = ctypes.POINTER(ctypes.c_int64)
        sizes = np.zeros(self.NATIVE_SCAN_BATCH, np.int64)
        n = int(lib.scan_op_sizes(
            d, v, self.NATIVE_SCAN_BATCH, sizes.ctypes.data_as(i64p)
        ))
        n = max(n, 0)
        return sizes[:n], n < self.NATIVE_SCAN_BATCH

    @staticmethod
    def _warn_native_unavailable() -> None:
        """Fall back to the per-file Python scan, but not silently — a
        failure here on every load would mask a real native-path bug.  The
        expected permanent case (no C toolchain: native.load() re-raises
        its cached build error per call) warns only once."""
        global _warned_native_scan
        if not _warned_native_scan:
            _warned_native_scan = True
            logger.warning(
                "native library unavailable; using the per-file Python "
                "paths (logged once)", exc_info=True,
            )
        else:
            logger.debug("native call failed", exc_info=True)

    def _probe_actors(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int]]:
        """Prefilter for the per-actor scans of ``stat_ops``: keep only
        actors whose NEXT wanted op file exists.  The dense scan reads
        nothing for the others (their log is fully consumed or GC'd),
        but a per-actor task, queue and thread hop costs ~1ms each — at
        10k replicas seconds spent discovering that 99% of actors had
        nothing new.  One stat per actor replaces all of it; the stats
        are dirfd-relative (resolve two path components, not the whole
        remote prefix) because on containerized kernels every path walk
        costs ~100µs+."""
        n = len(actor_first_versions)
        if n > 64:  # the C loop only pays off past its setup cost
            try:
                import numpy as np

                from .. import native

                lib = native.load()
                rel = b"\0".join(
                    f"{actor.hex()}/{first}".encode()
                    for actor, first in actor_first_versions
                ) + b"\0"
                mask = np.zeros(n, np.uint8)
                got = lib.probe_op_files(
                    self._ops_dir().encode(), n, rel,
                    mask.ctypes.data_as(native.u8p),
                )
                if got == n:
                    keep = np.flatnonzero(mask)
                    return [actor_first_versions[i] for i in keep.tolist()]
                if got == -1:
                    return []  # no ops directory at all
            except Exception:
                self._warn_native_unavailable()
        try:
            dfd = os.open(self._ops_dir(), os.O_RDONLY)
        except FileNotFoundError:
            return []
        out = []
        try:
            for pair in actor_first_versions:
                actor, first = pair
                try:
                    os.stat(f"{actor.hex()}/{first}", dir_fd=dfd)
                except OSError:
                    continue
                out.append(pair)
        finally:
            os.close(dfd)
        return out

    # The chunk iterator reads WINDOWS of wanted actors: one worker hop and
    # one native call (``load_op_window``) a window, CHUNK_WINDOWS of them
    # on their threads ahead of the emitter.  The windows in flight share
    # one chunk budget: a call's buffer is a CHUNK_WINDOWS-th of it, with a
    # size slot a KiB, and a window is as many actors as fill those slots
    # with 24 files each, four times what ``open()`` finds in a folder
    # nobody compacted for six rounds; a window whose runs are longer
    # stops where its buffers are full and is gone on with from there.
    # Eight by 128 is measured (PERF.md §6, PR 35): on the chip's host a
    # file is five system calls and 0.14 ms however it is asked for, so
    # what a read stage can win is threads side by side, 1,000 one-file
    # actors take 135 ms in one window, 67 in four at a time and 48 in
    # eight, and windows of 256 leave a thousand actors only four.
    CHUNK_WINDOWS = 8
    CHUNK_WINDOW_FILES = (CHUNK_BYTES // CHUNK_WINDOWS) >> 10
    CHUNK_WINDOW_ACTORS = CHUNK_WINDOW_FILES // 24

    async def iter_op_chunks(
        self,
        actor_first_versions: list[tuple[Actor, int]],
        max_bytes: int | None = None,
    ):
        """Bounded-memory op reading for the pipelined ingest: yields
        ``(actor, version, raw)`` lists of ~max_bytes, per-actor version
        order preserved across chunks (a chunk may end mid-actor), in the
        order of the request."""
        max_bytes = max_bytes if max_bytes is not None else self.CHUNK_BYTES
        chunk: list[tuple[Actor, int, bytes]] = []
        size = 0
        async with contextlib.aclosing(
            self._iter_windows(actor_first_versions, max_bytes)
        ) as reads:
            async for files in reads:
                for item in files:
                    chunk.append(item)
                    size += len(item[2])
                    if size >= max_bytes:
                        yield chunk
                        chunk, size = [], 0
        if chunk:
            yield chunk

    async def _iter_windows(
        self, wanted: list[tuple[Actor, int]], max_bytes: int
    ):
        """The files of ``wanted``, window by window, each window's as it
        was read: by ``_native_runs`` or, from the call that returned a
        status, by ``_file_runs`` under the same budget (a window that
        fell back is not read natively again).  Every window in flight
        was handed to its thread before the emitter awaits anything, so
        the read of the windows behind overlaps the emission of the one
        in front."""
        cap = max(1, min(max_bytes, self.CHUNK_BYTES) // self.CHUNK_WINDOWS)
        ahead = (
            wanted[i : i + self.CHUNK_WINDOW_ACTORS]
            for i in range(0, len(wanted), self.CHUNK_WINDOW_ACTORS)
        )
        loop = asyncio.get_running_loop()
        inflight: collections.deque = collections.deque()
        native_runs = functools.partial(
            self._native_runs, counted="fs_chunk_reads"
        )

        def start(window, read_runs):
            # ``asyncio.to_thread`` less the coroutine: the job is its
            # thread's before this returns, not a tick of the loop later
            # (the ingest's consumer holds the loop for its session's
            # vocabulary walk right after the producer's first step)
            job = loop.run_in_executor(
                None, contextvars.copy_context().run, read_runs,
                window, self.CHUNK_WINDOW_FILES, cap,
            )
            return read_runs, window, job

        def top_up():
            for window in itertools.islice(
                ahead, self.CHUNK_WINDOWS - len(inflight)
            ):
                inflight.append(start(window, native_runs))

        try:
            top_up()
            while inflight:
                read_runs, window, job = inflight.popleft()
                read = await job
                if read is None:  # a status: per file from here on
                    inflight.appendleft(start(window, self._file_runs))
                    continue
                files, rest = read
                if rest:  # its budget was full: go on from there, first
                    inflight.appendleft(start(rest, read_runs))
                top_up()
                yield files
        finally:
            for _, _, job in inflight:
                job.cancel()

    # The two readers of op files, under one contract: ``reader(wanted,
    # max_files, max_bytes) -> (files, rest)``.  ``files`` are in the order
    # asked, every run dense from its first version (an absent first file
    # is an empty run: no probe); ``rest`` is empty when every run ended,
    # otherwise the pair the reader stopped in, from its next version, and
    # the pairs behind it.  A reader stops before the file its budget does
    # not hold, and never before its first.  The buffers of ONE native
    # call of a poll's load (a larger load is drained in several):
    LOAD_RUNS_FILES = 1024
    LOAD_RUNS_BYTES = 1 << 20

    def _native_runs(
        self,
        wanted: list[tuple[Actor, int]],
        max_files: int,
        max_bytes: int,
        counted: str | None = None,
    ):
        """The native reader: the probe, the dense scan and the reads of
        every wanted actor as ONE call (``load_op_window``) under one
        ``ops/`` descriptor, or None on any status but 0 (a first file
        that alone overflows the buffer among them)."""
        import ctypes

        import numpy as np

        from .. import native

        n = len(wanted)
        i64 = ctypes.c_int64
        counts = (i64 * n)()
        sizes = (i64 * max_files)()
        buf = np.empty(max_bytes, np.uint8)
        n_files, n_bytes, stop = i64(), i64(), i64(n)
        if not _native_step(
            "load_op_window", os.fsencode(self._ops_dir()), n,
            b"".join(a.hex().encode() + b"\0" for a, _ in wanted),
            (i64 * n)(*(first for _, first in wanted)),
            max_files, max_bytes, counts, sizes,
            buf.ctypes.data_as(native.u8p), ctypes.byref(n_files),
            ctypes.byref(n_bytes), ctypes.byref(stop), counted=counted,
        ):
            return None
        raw = buf[: n_bytes.value].tobytes()
        each = iter(sizes[: n_files.value])
        out, end = [], 0
        for (actor, first), count in zip(wanted, counts):
            for v in range(first, first + count):
                start, end = end, end + next(each)
                out.append((actor, v, raw[start:end]))
        rest = list(wanted[stop.value :])
        if rest:
            actor, first = rest[0]
            rest[0] = (actor, first + counts[stop.value])
        return out, rest

    def _file_runs(
        self, wanted: list[tuple[Actor, int]], max_files: int, max_bytes: int
    ):
        """The per-file reader, what a status of the native one falls to:
        one ``_read_file`` a file, which tells a benign race (file gone:
        the run ends) from a defect (file present and unreadable: loud).
        A first file is taken whatever its size."""
        files: list[tuple[Actor, int, bytes]] = []
        size = 0
        for i, (actor, first) in enumerate(wanted):
            d = self._ops_dir(actor)
            for v in itertools.count(first):
                try:
                    raw = _read_file(os.path.join(d, str(v)))
                except NotADirectoryError:
                    raw = None  # a junk file of the actor's name: no run
                if raw is None:
                    break
                if files and (
                    len(files) >= max_files or size + len(raw) > max_bytes
                ):
                    return files, [(actor, v), *wanted[i + 1 :]]
                files.append((actor, v, raw))
                size += len(raw)
        return files, []

    def load_ops_sync(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, bytes]]:
        """A poll's load, drained: the native reader until a call returns
        a status, the per-file reader from where the drain stands then.
        One read of ``fs_reads_*``, however many calls it took."""
        out: list[tuple[Actor, int, bytes]] = []
        rest, read_runs = actor_first_versions, self._native_runs
        while rest:
            read = read_runs(rest, self.LOAD_RUNS_FILES, self.LOAD_RUNS_BYTES)
            if read is None:  # a status: per file from here on
                trace.add("fs_reads_python", 1)
                read_runs = self._file_runs
                continue
            files, rest = read
            out += files
        if actor_first_versions and read_runs == self._native_runs:
            trace.add("fs_reads_native", 1)
        return out

    async def load_ops(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, bytes]]:
        return await self._run(self.load_ops_sync, actor_first_versions)

    async def stat_ops(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, int]]:
        """Dense tail sizing for the replication-status backlog probe:
        the native ``scan_op_sizes`` pass (one C call per round), with a
        per-file ``os.stat`` continuation when the native path is
        unavailable.  Probe-prefiltered, so a fully consumed log costs
        one stat per actor, not a scan."""
        actor_first_versions = await self._run(
            self._probe_actors, actor_first_versions
        )

        def scan(actor: Actor, first: int) -> list[tuple[Actor, int, int]]:
            out: list[tuple[Actor, int, int]] = []
            v = first
            try:
                from .. import native

                lib = native.load()
                d = self._ops_dir(actor).encode()
                while True:
                    sizes, exhausted = self._scan_sizes_native(lib, d, v)
                    out.extend(
                        (actor, v + i, int(s)) for i, s in enumerate(sizes)
                    )
                    v += len(sizes)
                    if exhausted:
                        return out
            except Exception:
                self._warn_native_unavailable()
            # per-file stat continuation from wherever the native pass
            # stopped (or from ``first`` when it never started)
            dd = self._ops_dir(actor)
            while True:
                try:
                    st = os.stat(os.path.join(dd, str(v)))
                except OSError:
                    return out
                out.append((actor, v, int(st.st_size)))
                v += 1

        per_actor = await asyncio.gather(
            *(self._run(scan, a, f) for a, f in actor_first_versions)
        )
        return [item for chunk in per_actor for item in chunk]

    @staticmethod
    def _store_versioned(d: str, version: int, data: bytes) -> None:
        # version-addressed: a vanished collider BURNS the version (the
        # caller probes forward) — see _write_file_new's contract
        path, data = os.path.join(d, str(version)), bytes(data)
        if not _publish_native("publish_file_new", path, data):
            _write_file_new(path, data, relink_vanished_collider=False)

    @staticmethod
    def _remove_prefix(d: str, last: int) -> None:
        """Every version ≤ ``last`` of one actor's log directory."""
        for n in _list_dir(d):
            try:
                v = int(n)
            except ValueError:
                continue
            if v <= last:
                _remove_quiet(os.path.join(d, n))
        try:
            os.rmdir(d)  # tidy an emptied actor dir; fails if files remain
        except OSError:
            pass

    async def store_ops(self, actor: Actor, version: int, data: bytes) -> None:
        await self._run(
            self._store_versioned, self._ops_dir(actor), version, data
        )

    def _remove_logs(
        self, dir_of, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        """Prefix GC of one log family (``dir_of``: ``_ops_dir`` or
        ``_deltas_dir``), the whole list in one native step."""
        if actor_last_versions and not _remove_prefixes_native(
            dir_of(), actor_last_versions
        ):
            for actor, last in actor_last_versions:
                self._remove_prefix(dir_of(actor), last)

    def remove_ops_sync(
        self, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        self._remove_logs(self._ops_dir, actor_last_versions)

    async def remove_ops(self, actor_last_versions: list[tuple[Actor, int]]) -> None:
        await self._run(self.remove_ops_sync, actor_last_versions)

    # -- delta snapshots ---------------------------------------------------
    # Same layout idiom as the op logs (``remote/deltas/<actor-hex>/<N>``)
    # but a simpler read contract: logs are MAX_CHAIN-bounded and files
    # are deltas (small by construction), so a plain listdir+read per
    # actor is the whole fast path — no native scan, no probe prefilter.
    has_deltas = True

    async def list_delta_actors(self) -> list[Actor]:
        return _op_actors(await self._run(_list_dir, self._deltas_dir()))

    async def load_deltas(
        self, actor_first_versions: list[tuple[Actor, int]]
    ) -> list[tuple[Actor, int, bytes]]:
        def scan(actor: Actor, first: int) -> list[tuple[Actor, int, bytes]]:
            d = self._deltas_dir(actor)
            versions = sorted(
                v for v in (
                    int(n) for n in _list_dir(d) if n.isdigit()
                ) if v >= first
            )
            out = []
            for v in versions:
                raw = _read_file(os.path.join(d, str(v)))
                if raw is not None:  # racing GC may collect mid-walk
                    out.append((actor, v, raw))
            return out

        per_actor = await asyncio.gather(
            *(self._run(scan, a, f) for a, f in actor_first_versions)
        )
        return [item for chunk in per_actor for item in chunk]

    def store_delta_sync(self, actor: Actor, version: int, data: bytes) -> None:
        # version-addressed like op files (the producer probes forward)
        self._store_versioned(self._deltas_dir(actor), version, data)

    async def store_delta(self, actor: Actor, version: int, data: bytes) -> None:
        await self._run(self.store_delta_sync, actor, version, data)

    def remove_deltas_sync(
        self, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        self._remove_logs(self._deltas_dir, actor_last_versions)

    async def remove_deltas(
        self, actor_last_versions: list[tuple[Actor, int]]
    ) -> None:
        await self._run(self.remove_deltas_sync, actor_last_versions)
