"""ctypes loader for the native library (builds on demand via make)."""

from __future__ import annotations

import ctypes
import fcntl
import logging
import os
import subprocess
import threading

logger = logging.getLogger("crdt_enc_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "build", "libcrdtnative.so")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_error: Exception | None = None  # cached: never retry a failed build per call

u8p = ctypes.POINTER(ctypes.c_uint8)
u64p = ctypes.POINTER(ctypes.c_uint64)


def _build_and_load(target: str, so_path: str, dll_cls, bind_fn):
    """Build one make target under the shared file lock and dlopen it.

    Always invokes make: an incremental no-op when fresh, and source
    edits never silently run stale native code.  The file lock
    serializes concurrent processes (the in-process _lock can't) so one
    never dlopens a half-linked .so.  Building only the requested
    target keeps the libraries independent — e.g. a box without CPython
    dev headers still gets the header-free crypto/codec library even
    though the C-API state library cannot compile there.
    """
    os.makedirs(os.path.join(_HERE, "build"), exist_ok=True)  # lint: effect-ok=blocks (one-shot memoized build; warm() runs it off-loop)
    with open(os.path.join(_HERE, "build", ".lock"), "w") as lk:  # lint: effect-ok=blocks (one-shot memoized build; warm() runs it off-loop)
        fcntl.flock(lk, fcntl.LOCK_EX)
        try:
            subprocess.run(  # lint: effect-ok=blocks (one-shot memoized build; warm() runs it off-loop)
                ["make", "-C", _HERE, target],
                check=True,
                capture_output=True,
                text=True,
            )
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native build failed (exit {e.returncode}):\n"
                f"{e.stdout}\n{e.stderr}"
            ) from e
        lib = dll_cls(so_path)
    bind_fn(lib)
    return lib


def warm() -> None:
    """Build/load both native libraries now; a failure is logged, loudly,
    and does not raise.  First, once a process, the allocator's arena
    growth is set (:func:`_grow_thread_arenas_whole`).

    The loaders memoize success *and* failure, so after one ``warm()``
    every later ``load()``/``load_state()`` call is a cached dict hit —
    no ``make`` subprocess, no dlopen.  Event-loop code calls this once
    via ``asyncio.to_thread`` at open (see ``Core.open``) so the
    first-use build never runs on the loop; callers that need the
    library still probe the loaders themselves and fall back to the
    Python paths when the build failed — correct, but far slower, so the
    warning names what is missing.  A measurement must not run that way:
    ``chip_smoke.py`` rebuilds both libraries from source and fails
    unless both load.
    """
    _grow_thread_arenas_whole()
    for loader in (load, load_state):
        try:
            loader()
        except Exception as e:  # cached by the loader
            logger.warning(
                "native library unavailable (%s: %s); the pure-Python "
                "front end takes over, much slower", loader.__name__, e,
            )


_ARENA_HEAP = 64 << 20  # glibc's HEAP_MAX_SIZE on a 64-bit machine
_arenas_set = False


def _grow_thread_arenas_whole() -> None:
    """Have glibc map a worker thread's arena 64 MB at a time.

    glibc opens a thread's arena 132 KB long and lengthens it by the
    pages each request still lacks, one ``mprotect`` a step, so the
    first job on a thread that builds a large state out of small
    objects (``delta.verify`` rebuilds and packs the whole state) pays
    some 16,000 calls for every 64 MB; an arena keeps its length when
    its objects are freed, so only a thread's first such job pays, and
    which of the executor's ``cpu + 4`` threads a job lands on is
    chance.  Measured on the attached chip's host, 2026-10-01, on a
    10,000-device state (18 MB packed): 1,353-1,818 ms for a thread's
    first rebuild-and-pack against 243-256 ms for its later ones; with
    the pad below 333-379 ms and 221-280 ms.  ``M_TOP_PAD`` is what a new
    arena heap is opened with, and at the heap's full size one call
    opens all of it.  Setting any of these stops glibc from moving the
    other two on its own, so they are pinned where it would have moved
    them: the largest ``mmap`` threshold it allows, and a trim that
    leaves the pad.  Where ``mallopt`` is missing (no glibc) nothing
    is done."""
    global _arenas_set
    if _arenas_set:
        return
    _arenas_set = True
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return
    M_TRIM_THRESHOLD, M_TOP_PAD, M_MMAP_THRESHOLD = -1, -2, -3
    mallopt(M_MMAP_THRESHOLD, _ARENA_HEAP // 2)
    mallopt(M_TOP_PAD, _ARENA_HEAP)
    mallopt(M_TRIM_THRESHOLD, 2 * _ARENA_HEAP)


def load() -> ctypes.CDLL:
    global _lib, _load_error
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            # a failed build is permanent for this process — callers on hot
            # paths (e.g. the fs op scan) probe per call and must not spawn
            # a failing `make` subprocess every time
            raise _load_error
        try:
            lib = _build_and_load(
                "build/libcrdtnative.so", _SO, ctypes.CDLL, _bind
            )
        except Exception as e:
            # cache ANY load failure (build, dlopen, missing symbol): hot
            # paths probe per call and must never re-spawn make
            _load_error = e
            raise

        _lib = lib
        return lib


_STATE_SO = os.path.join(_HERE, "build", "libcrdtstate.so")
_state_lib: ctypes.PyDLL | None = None
_state_error: Exception | None = None


def load_state() -> ctypes.PyDLL:
    """The C-API state-assembly library (statebuild.cpp).

    Loaded with ``PyDLL`` — calls hold the GIL because the functions
    create Python objects (dicts of a folded state).  Separate from the
    CDLL crypto/codec library, whose calls release the GIL.  Same
    build-on-demand + cached-failure discipline as ``load()``.
    """
    global _state_lib, _state_error
    with _lock:
        if _state_lib is not None:
            return _state_lib
        if _state_error is not None:
            raise _state_error
        try:
            lib = _build_and_load(
                "build/libcrdtstate.so", _STATE_SO, ctypes.PyDLL, _bind_state
            )
        except Exception as e:
            _state_error = e
            raise
        _state_lib = lib
        return lib


def _bind_state(lib) -> None:
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.orset_fresh_fold.argtypes = [
        ctypes.POINTER(ctypes.c_int8), i32p, i32p, i32p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, i32p,
        ctypes.py_object, ctypes.py_object,
        ctypes.py_object, ctypes.py_object,
    ]
    lib.orset_fresh_fold.restype = ctypes.c_int
    # split fresh fold: rows handle out (counts[2] is the capacity
    # channel for the later take), then a sized copy-out + free
    lib.orset_fold_rows.argtypes = [
        ctypes.POINTER(ctypes.c_int8), i32p, i32p, i32p, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, i32p, i64p,
    ]
    lib.orset_fold_rows.restype = ctypes.c_void_p
    lib.orset_fold_rows_take.argtypes = [
        ctypes.c_void_p, i32p, i32p, i64p, ctypes.c_int64,
        i32p, i32p, i64p, ctypes.c_int64,
    ]
    lib.orset_fold_rows_take.restype = ctypes.c_int
    lib.orset_fold_rows_drop.argtypes = [ctypes.c_void_p]
    lib.orset_fold_rows_drop.restype = None
    lib.dense_clock_dict.argtypes = [i32p, ctypes.c_int64, ctypes.py_object]
    lib.dense_clock_dict.restype = ctypes.py_object
    lib.grouped_rows_dicts.argtypes = [
        i32p, i32p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.py_object, ctypes.py_object, ctypes.py_object,
    ]
    lib.grouped_rows_dicts.restype = ctypes.c_int
    lib.dicts_grouped_rows.argtypes = [ctypes.py_object] * 5
    lib.dicts_grouped_rows.restype = ctypes.py_object
    lib.bytes_lens_join.argtypes = [
        ctypes.py_object, u64p, u8p, ctypes.c_int64, ctypes.c_int64
    ]
    lib.bytes_lens_join.restype = ctypes.c_int64
    lib.canon_pack.argtypes = [ctypes.py_object]
    lib.canon_pack.restype = ctypes.py_object
    lib.canon_same.argtypes = [ctypes.py_object, ctypes.py_object]
    lib.canon_same.restype = ctypes.py_object
    lib.canon_counters.argtypes = []
    lib.canon_counters.restype = ctypes.py_object


def _bind(lib) -> None:
    # SIMD lanes the AEAD batch paths dispatched to at load (4, 8 or 16)
    lib.crdt_simd_lanes.argtypes = []
    lib.crdt_simd_lanes.restype = ctypes.c_int
    lib.hchacha20.argtypes = [u8p, u8p, u8p]
    lib.hchacha20.restype = None
    for name in ("chacha20poly1305_encrypt", "xchacha20poly1305_encrypt"):
        fn = getattr(lib, name)
        fn.argtypes = [
            u8p, u8p, u8p, ctypes.c_uint64, u8p, ctypes.c_uint64, u8p
        ]
        fn.restype = None
    for name in ("chacha20poly1305_decrypt", "xchacha20poly1305_decrypt"):
        fn = getattr(lib, name)
        fn.argtypes = [
            u8p, u8p, u8p, ctypes.c_uint64, u8p, ctypes.c_uint64, u8p
        ]
        fn.restype = ctypes.c_int
    lib.xchacha20poly1305_decrypt_batch.argtypes = [
        u8p, u8p, u8p, u64p, ctypes.c_uint64, u8p, u64p, u8p
    ]
    lib.xchacha20poly1305_decrypt_batch.restype = ctypes.c_int
    lib.xchacha20poly1305_decrypt_batch_mt.argtypes = [
        u8p, u8p, u8p, u64p, ctypes.c_uint64, u8p, u64p, u8p,
        ctypes.c_int,
    ]
    lib.xchacha20poly1305_decrypt_batch_mt.restype = ctypes.c_int
    lib.encbox_parse_batch.argtypes = [
        u8p, u64p, ctypes.c_uint64, u8p, u64p, u64p, u64p
    ]
    lib.encbox_parse_batch.restype = ctypes.c_int64
    lib.encbox_parse_batch_ptrs.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), u64p, ctypes.c_uint64, u8p,
        u64p, u64p, u64p,
    ]
    lib.encbox_parse_batch_ptrs.restype = ctypes.c_int64
    lib.encbox_decrypt_scatter_mt.argtypes = [
        u8p, u8p, u64p, u64p, u64p, ctypes.c_uint64, u8p, u64p, u8p,
        ctypes.c_int,
    ]
    lib.encbox_decrypt_scatter_mt.restype = ctypes.c_int
    # scalar one-shot MAC + the lane-parallel AEAD tag batch (zero AAD):
    # the differential tests pin the vectorized verify pass against both
    # the scalar core and the pure-Python oracle
    lib.poly1305_mac.argtypes = [u8p, u8p, ctypes.c_uint64, u8p]
    lib.poly1305_mac.restype = None
    lib.poly1305_aead_tags.argtypes = [u8p, u8p, u64p, ctypes.c_uint64, u8p]
    lib.poly1305_aead_tags.restype = None

    lib.orset_count_rows.argtypes = [u8p, ctypes.c_uint64]
    lib.orset_count_rows.restype = ctypes.c_int64
    lib.orset_decode.argtypes = [
        u8p, ctypes.c_uint64, u8p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int8), u64p, u64p,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.orset_decode.restype = ctypes.c_int64
    lib.counter_decode.argtypes = [
        u8p, ctypes.c_uint64, u8p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.counter_decode.restype = ctypes.c_int64

    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.scan_op_sizes.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, i64p
    ]
    lib.scan_op_sizes.restype = ctypes.c_int64
    lib.probe_op_files.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, u8p
    ]
    lib.probe_op_files.restype = ctypes.c_int64
    # the writers (io.cpp "file steps"): status 0, or the Python body runs
    for name in ("publish_file_new", "write_file_atomic"):
        fn = getattr(lib, name)
        fn.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64
        ]
        fn.restype = ctypes.c_int32
    lib.remove_log_prefixes.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, i64p
    ]
    lib.remove_log_prefixes.restype = ctypes.c_int32
    lib.remove_names.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p
    ]
    lib.remove_names.restype = ctypes.c_int32
    lib.file_step_flushes.argtypes = []
    lib.file_step_flushes.restype = ctypes.c_int64
    # the reads (io.cpp "the reads"): status 0, or fs.py reads on itself
    lib.list_dir_names.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, i64p, i64p
    ]
    lib.list_dir_names.restype = ctypes.c_int32
    lib.load_op_window.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, i64p,
        ctypes.c_int64, ctypes.c_int64, i64p, i64p, u8p, i64p, i64p, i64p,
    ]
    lib.load_op_window.restype = ctypes.c_int32
    # (the two-pass count+decode batch protocol still exists in C —
    # orset_count_rows_batch / orset_decode_batch[_h] — but the Python
    # span decoder moved to the single-pass grow/take protocol below, so
    # only the live entry points are bound)
    lib.actor_hash_build.argtypes = [
        u8p, ctypes.c_uint64, ctypes.POINTER(ctypes.c_int32),
        ctypes.c_uint64,
    ]
    lib.actor_hash_build.restype = None
    lib.orset_decode_batch_grow.argtypes = [
        u8p, u64p, u64p, ctypes.c_uint64, u8p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_uint64, i64p,
    ]
    lib.orset_decode_batch_grow.restype = ctypes.c_void_p
    lib.orset_decode_take.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int8), u64p, u64p,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.orset_decode_take.restype = None
    lib.orset_decode_drop.argtypes = [ctypes.c_void_p]
    lib.orset_decode_drop.restype = None
    lib.counter_decode_batch.argtypes = [
        u8p, u64p, u64p, ctypes.c_uint64, u8p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.counter_decode_batch.restype = ctypes.c_int64
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.orset_host_reduce.argtypes = [
        ctypes.POINTER(ctypes.c_int8), i32p, i32p, i32p, ctypes.c_int64,
        i32p, ctypes.c_int32, ctypes.c_int64, i32p, i32p,
    ]
    lib.orset_host_reduce.restype = ctypes.c_int64
    lib.intern_spans_native.argtypes = [
        u8p, u64p, u64p, ctypes.c_int64, i64p, ctypes.c_int64,
        i32p, u64p, u64p, ctypes.c_int64,
    ]
    lib.intern_spans_native.restype = ctypes.c_int64
    lib.map_count_rows_batch.argtypes = [
        u8p, u64p, u64p, ctypes.c_uint64, i64p
    ]
    lib.map_count_rows_batch.restype = ctypes.c_int64
    lib.map_decode_batch.argtypes = (
        [u8p, u64p, u64p, ctypes.c_uint64, u8p, ctypes.c_uint64]
        + [u64p, u64p, i32p, i32p]
        + [u64p, u64p, u64p, u64p, i32p, i32p]
        + [u64p, u64p, u64p, u64p, i32p, i32p, i32p, i32p]
        + [u64p, u64p, i32p, i32p, i32p]
    )
    lib.map_decode_batch.restype = ctypes.c_int64



def in_ptr(b):
    """Zero-copy input pointer for bytes/bytearray/ndarray.  The caller must
    keep the object alive across the native call (numpy view held by the
    returned tuple)."""
    import numpy as np

    arr = np.frombuffer(b, dtype=np.uint8) if not isinstance(b, np.ndarray) else b
    if arr.size == 0:
        return None, arr
    return arr.ctypes.data_as(u8p), arr


def out_buf(n: int):
    """Writable output buffer of n bytes (numpy-backed)."""
    import numpy as np

    arr = np.empty(n, dtype=np.uint8)
    return (arr.ctypes.data_as(u8p) if n else None), arr
