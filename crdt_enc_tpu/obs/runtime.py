"""The runtime's signals: recompiles, H2D/D2H transfers, device memory,
and the interpreter's collector passes.

The regressions ADVICE r5 caught by hand — an unbounded-recompile fold
loop, a silent fallback off the device path — are exactly the ones this
module makes mechanical:

* **Recompile counter** (:func:`track_recompiles`): every XLA backend
  compile bumps the ``jax_compiles`` counter and records its duration
  under the ``jax.compile`` span, via the public ``jax.monitoring``
  duration-event stream.  A steady-state fold loop whose ``jax_compiles``
  grows per iteration is recompiling — the bucket-padding contract is
  broken (tests/test_obs.py pins the counter constant across a
  varying-batch fold loop).
* **H2D accounting**: the streaming paths count ``h2d_bytes`` at each
  ``jax.device_put`` issue (ops/stream.py, parallel/session.py); transfer
  issue latency is the ``stream.h2d`` span histogram.
* **D2H accounting** (:func:`pull`): the pulls of device arrays back to
  the host on the paths whose uploads ``h2d_bytes`` counts (the OR-Set
  folds of accel.py and session.py, the service's buckets and device
  cut, the delta verify) go through it and count ``d2h_bytes`` and
  ``d2h_pulls`` (one per device array) where the pull is issued.
* **Device memory** (:func:`sample_device_memory`): ``bytes_in_use`` /
  ``peak_bytes_in_use`` gauges sampled at fold boundaries — the
  bounded-device-memory claim of the donated-plane streaming fold,
  observable.  A no-op on backends without allocator stats (CPU), probed
  once and cached.

* **Collector passes** (:func:`track_gc`): a pass of the interpreter's
  cyclic collector holds the interpreter lock, so it stops every worker
  job and every pull, inside whichever span happens to be open.  One
  function on ``gc.callbacks`` counts ``gc_passes`` / ``gc_pause_us``
  (every generation), ``gc_full_passes`` / ``gc_full_pause_us``
  (generation 2) and ``gc_collected``; a pass of generation 1 or 2 is
  also a ``runtime.gc`` ``pause`` entry of the event log (parent: the
  span open in the collecting thread) and, while
  ``record.jax_annotations`` is set, a ``jax.profiler`` annotation of
  that name, so the profiler's trace shows it on the device's clock.
  Never a span aggregate: a pause is no phase of the program.  A pass
  can start inside ``with record._lock:``, so the callback never waits
  for that lock: it keeps plain totals (passes never nest) and the
  registry folds them in wherever it is read (``record.on_read``).

Nothing here imports jax at module load: the registry stays importable in
jax-less tooling contexts, and the listeners attach only when asked.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from collections import deque

from . import record

_lock = threading.Lock()
_listener_installed = False
_recompiles_enabled = False
_recompiles_explicit = False  # an operator choice must stick

# The one duration event XLA emits exactly once per backend compilation
# (jaxpr tracing and MLIR lowering emit siblings; counting those would
# double-book a single cache miss).  NOTE: with a persistent compilation
# cache configured (crdt_enc_tpu.enable_compilation_cache), jax
# emits this event around the compile-or-retrieve step, so a disk-cache
# RETRIEVAL also counts as a "compile" here — the cache_hits/cache_misses
# events below split the two: ``jax_cache_misses`` is the count of real
# XLA compiles, ``jax_cache_hits`` the count served from disk.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_mem_supported: bool | None = None  # probed once; None = not yet probed


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    if _recompiles_enabled and event == _COMPILE_EVENT:
        record.add("jax_compiles", 1)
        record.observe("jax.compile", duration)


def _on_event(event: str, **kwargs) -> None:
    if not _recompiles_enabled:
        return
    if event == _CACHE_HIT_EVENT:
        record.add("jax_cache_hits", 1)
    elif event == _CACHE_MISS_EVENT:
        record.add("jax_cache_misses", 1)


def track_recompiles(on: bool = True) -> None:
    """Start (or stop) counting XLA backend compiles into the
    ``jax_compiles`` counter / ``jax.compile`` span.  Idempotent; the
    monitoring listener registers once per process and toggles via a
    flag (jax.monitoring offers no unregister).  Counts are process-wide
    and cleared by ``trace.reset()`` like every other counter.  An
    explicit call here is an OPERATOR choice — the accelerator's
    default-on wiring (:func:`ensure_recompile_tracking`) never
    overrides it."""
    global _recompiles_explicit
    with _lock:
        _recompiles_explicit = True
    _set_recompiles(on)


def ensure_recompile_tracking() -> None:
    """Default-on wiring (TpuAccelerator.__init__): enable tracking
    unless the operator already made an explicit track_recompiles()
    choice — constructing a second accelerator must not silently undo a
    deliberate opt-out."""
    with _lock:
        if _recompiles_explicit:
            return
    _set_recompiles(True)


def _set_recompiles(on: bool) -> None:
    global _listener_installed, _recompiles_enabled
    with _lock:
        _recompiles_enabled = on
        if on and not _listener_installed:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event
            )
            jax.monitoring.register_event_listener(_on_event)
            _listener_installed = True


# ------------------------------------------------------- collector passes
_gc_installed = False
_gc_enabled = False
_gc_explicit = False  # an operator choice must stick
# The pass in flight: perf_counter_ns at its start (None: no pass that is
# being accounted) and its annotation.  Passes never nest, and a pass
# ends on the thread it started on.
_gc_t0: int | None = None
_gc_ann = None
# Totals since the process started, bumped by the callback alone, under
# the counters they are published as.  Whole microseconds a pass, the same
# for both pause totals, so the full passes never read ahead of all passes.
_GC_COUNTERS = ("gc_passes", "gc_pause_us", "gc_full_passes",
                "gc_full_pause_us", "gc_collected", "events_dropped")
_gc_totals = [0] * len(_GC_COUNTERS)
_gc_folded = [0] * len(_GC_COUNTERS)  # what the registry was handed of them
_gc_fold_lock = threading.Lock()  # between readers; never the callback's
# pause entries waiting for a reader, bounded like the event log itself
# (a drop counts into ``events_dropped``)
_gc_pauses: deque = deque(maxlen=4096)


def _on_gc(phase: str, info: dict) -> None:
    """The one ``gc.callbacks`` entry.  Two clock reads and a few adds a
    pass; takes no lock (it may run inside ``record._lock``)."""
    global _gc_t0, _gc_ann
    if phase == "start":
        if not _gc_enabled:
            return
        if info["generation"] and record.jax_annotations:
            profiler = sys.modules.get("jax.profiler")
            if profiler is not None:
                _gc_ann = profiler.TraceAnnotation("runtime.gc")
                _gc_ann.__enter__()
        _gc_t0 = time.perf_counter_ns()
        return
    t1 = time.perf_counter_ns()
    t0 = _gc_t0
    if t0 is None:  # tracking began inside this pass
        return
    _gc_t0 = None
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    generation, totals = info["generation"], _gc_totals
    us = (t1 - t0 + 500) // 1000
    # the wider total first: a reader between two adds never sees the
    # full passes ahead of all passes
    totals[1] += us
    totals[0] += 1
    totals[4] += info["collected"]
    if not generation:
        return
    if generation == 2:
        totals[3] += us
        totals[2] += 1
    if record.events_enabled():
        if len(_gc_pauses) == _gc_pauses.maxlen:
            totals[5] += 1
        # perf_counter_ns is perf_counter's clock, the spans' own
        _gc_pauses.append(record.pause_entry(
            "runtime.gc", t0 / 1e9, t1 / 1e9,
            {"generation": generation, "collected": info["collected"]},
        ))


def _fold_gc() -> None:
    """Bring what the callback has counted since the last fold into the
    registry (``record.on_read``): the counters' growth and the pause
    entries, through ``record.fold`` and not ``record.add``: a pause is
    nobody's increment, so no counter tap sees it."""
    with _gc_fold_lock:
        now = tuple(_gc_totals)  # one copy: no pass can fall inside it
        grown = {}
        for i, name in enumerate(_GC_COUNTERS):
            if now[i] != _gc_folded[i]:
                grown[name] = now[i] - _gc_folded[i]
                _gc_folded[i] = now[i]
        pauses = []
        while _gc_pauses:  # each pop is whole, whatever the callback appends
            pauses.append(_gc_pauses.popleft())
        if grown or pauses:
            record.fold(grown, pauses)


def track_gc(on: bool = True) -> None:
    """Start (or stop) accounting the collector's passes (module docs).
    Idempotent; the callback is appended to ``gc.callbacks`` once per
    process and toggles via a flag.  The counts are cleared by
    ``trace.reset()`` like every other counter.  Changes nothing about
    when or what the collector collects.  An explicit call here is an
    OPERATOR choice — the accelerator's default-on wiring
    (:func:`ensure_gc_tracking`) never overrides it."""
    global _gc_explicit
    with _lock:
        _gc_explicit = True
    _set_gc(on)


def ensure_gc_tracking() -> None:
    """Default-on wiring (TpuAccelerator.__init__), as
    :func:`ensure_recompile_tracking`."""
    with _lock:
        if _gc_explicit:
            return
    _set_gc(True)


def _set_gc(on: bool) -> None:
    global _gc_installed, _gc_enabled
    with _lock:
        _gc_enabled = on
        if on and not _gc_installed:
            record.on_read(_fold_gc)
            gc.callbacks.append(_on_gc)
            _gc_installed = True


def recompile_count() -> int:
    """The current ``jax_compiles`` counter (0 when tracking is off)."""
    return record.snapshot()["counters"].get("jax_compiles", 0)


def pull(*arrays) -> tuple:
    """``arrays`` as host numpy arrays, the device ones counted into
    ``d2h_bytes`` (and one ``d2h_pulls`` each: every device array is a
    blocking sync of its own, whatever its size) where the pull is
    issued (the twin of the ``h2d_bytes`` accounting).  An array that is
    already numpy passes through uncounted."""
    import numpy as np

    host = tuple(np.asarray(x) for x in arrays)
    pulled = [
        h.nbytes for h, x in zip(host, arrays)
        if not isinstance(x, np.ndarray)
    ]
    if pulled:
        record.add("d2h_bytes", sum(pulled))
        record.add("d2h_pulls", len(pulled))
    return host


def sample_device_memory(device=None) -> dict | None:
    """Record ``device_bytes_in_use`` / ``device_peak_bytes`` gauges from
    the backend allocator, returning the raw stats dict.  Returns None —
    and stays cheap, a cached boolean check — on backends without
    allocator stats (the CPU backend) or before jax is imported.

    The capability cache applies only to the DEFAULT device: an
    explicitly passed ``device`` is always probed (a stats-less default
    backend must not disable sampling of a capable one), and a transient
    exception never latches the cache — only a successful probe that
    reports no stats does."""
    global _mem_supported
    default_dev = device is None
    if default_dev and _mem_supported is False:
        return None
    import sys

    if "jax" not in sys.modules:
        return None
    import jax

    try:
        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None  # transient failure: do not latch the capability
    if not stats:
        if default_dev:
            _mem_supported = False
        return None
    if default_dev:
        _mem_supported = True
    if "bytes_in_use" in stats:
        record.gauge("device_bytes_in_use", int(stats["bytes_in_use"]))
    if "peak_bytes_in_use" in stats:
        record.gauge("device_peak_bytes", int(stats["peak_bytes_in_use"]))
    return stats
