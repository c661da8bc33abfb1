"""JAX runtime signals: recompiles, H2D transfers, device memory.

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

Nothing here imports jax at module load: the registry stays importable in
jax-less tooling contexts, and the listeners attach only when asked.
"""

from __future__ import annotations

import threading

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
