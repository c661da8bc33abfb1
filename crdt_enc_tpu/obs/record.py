"""Structured per-phase tracing and metrics: the process-wide registry.

The reference ships no observability at all (SURVEY.md §5: no tracing/log
crates anywhere; anyhow context strings are the only diagnostics).  The
rebuild's contract is per-phase timers around the compaction pipeline —
list/load/decrypt/decode/fold/write — plus counters for the BASELINE
metric (ops merged/sec), with optional ``jax.profiler`` trace annotations
so device-side kernel time lines up with host phases in a profile.

Design: one process-wide registry, monotonic wall-clock spans, plain
dicts under a lock (spans fire at file/batch granularity — hundreds per
compaction — so overhead is irrelevant next to I/O and crypto).  Spans
nest; a span records under its own flat name, so concurrent asyncio tasks
timing the same phase simply accumulate.  Each span also records the span
that CAUSED it: one ``ContextVar`` holds the open span, and asyncio tasks
and ``asyncio.to_thread`` hops copy the context, so a tenant's seal under
``serve.phase.seal`` or ``delta.verify`` on a worker thread keeps its
parent.  The aggregate keeps the set of parent names seen per name
(``snapshot()["spans"][name]["parents"]``, ``None`` for a root) and
``tree()`` turns those into name -> direct children.

Aggregates are count + total seconds + a **bounded log-scale histogram**
(quarter-octave buckets, so every estimate is within ~±9% of the true
value): ``report()`` and ``snapshot()`` publish p50/p95/p99/max per span.
A phase whose *mean* looks healthy can still hide a 100× tail (one
recompile, one cold dispatch) — the quantiles make that visible.

Usage::

    from crdt_enc_tpu.utils import trace   # compat shim onto this module

    with trace.span("stream.decrypt"):
        ...
    trace.add("ops_folded", len(batch))
    trace.gauge("device_bytes_in_use", stats["bytes_in_use"])
    print(trace.report())     # phase table with quantiles
    trace.snapshot()          # {"spans": ..., "counters": ..., "gauges": ...}

Logging: spans emit DEBUG records on the ``crdt_enc_tpu.trace`` logger;
enable with ``logging.getLogger("crdt_enc_tpu").setLevel(logging.DEBUG)``.

Event log: aggregated slots cannot show *when* phases ran relative to
each other, which is exactly what auditing an overlapped pipeline needs
(did chunk k+1's ingest start before chunk k's fold finished?).
``enable_events()`` turns on a per-occurrence log — every span exit
appends ``{"name", "t0", "t1", "meta", "tid", "thread", "kind", "id",
"parent"}`` (``parent`` is the ``id`` of the span open around it, or
None) with monotonic ``perf_counter`` timestamps comparable across
threads — read it back with ``events()`` or export a Chrome-trace
timeline with ``obs.timeline``.  The log is a RING BUFFER (``DEFAULT_EVENT_CAPACITY``
occurrences; configure with ``set_events_capacity``): when full, the
oldest event is dropped and the ``events_dropped`` counter bumps, so an
instrumented long-running service can leave events on without unbounded
growth.  Off by default, and ``reset()`` restores the default off state
(seam tests cannot leak event recording into later tests).  Counter and
gauge updates also append (``kind: "counter"/"gauge"``) while events are
on, which is what the timeline's counter tracks are built from.  A
``kind: "pause"`` entry is a stretch in which the interpreter ran none of
the program (``runtime.gc``, a collector pass: obs.runtime): it has the
shape of a span's entry, its ``parent`` the span it landed in, and no
aggregate under ``snapshot()["spans"]``: a pause is no phase.

Whoever keeps totals of its own because it may not wait for this
registry's lock (the collector's callback can fire inside it) registers
one function with :func:`on_read`; every read or clearing of the registry
calls it first, outside the lock, and it folds what it has in
(:func:`fold`, :func:`pause_entry`).

Span and metric names are REGISTERED in ``docs/observability.md``;
``tools/check_span_names.py`` lints the tree against the registry.
"""

from __future__ import annotations

import contextvars
import itertools
import logging
import math
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager

logger = logging.getLogger("crdt_enc_tpu.trace")

# When True and jax is already imported, spans also open a
# jax.profiler.TraceAnnotation so they show up in device traces.
jax_annotations = False

DEFAULT_EVENT_CAPACITY = 65536

_lock = threading.Lock()
# name -> [count, total_seconds, max_seconds, {bucket_index: count},
#          {parent names seen; None for a root}]
_spans: dict[str, list] = {}
_counters: dict[str, int] = {}
_gauges: dict[str, float] = {}
_events_enabled = False
_events_capacity = DEFAULT_EVENT_CAPACITY
_events: deque = deque(maxlen=DEFAULT_EVENT_CAPACITY)


# Called, outside ``_lock``, before the registry is read or cleared
# (``snapshot``, ``events``, ``drain_events``, ``reset``): see on_read.
_read_hooks: list = []


def on_read(fold) -> None:
    """Register ``fold()``, which brings totals kept outside the registry
    into it (module docs).  It runs on the reading thread with ``_lock``
    free, may take it, and is registered once a process."""
    if fold not in _read_hooks:
        _read_hooks.append(fold)


def _fold_in() -> None:
    for hook in _read_hooks:
        hook()


def fold(counts: dict, entries=()) -> None:
    """What an :func:`on_read` hook brings in, under one acquisition of
    the lock: ``counts`` added to the counters and ``entries`` (built by
    :func:`pause_entry`) appended to the event log while it is on.  These
    are nobody's increments: no counter tap sees them and no counter
    entry is logged for them."""
    with _lock:
        for name, n in counts.items():
            _counters[name] = _counters.get(name, 0) + n
        if _events_enabled:
            for e in entries:
                _append_event_locked(e)


# --------------------------------------------------------------- histogram
# Quarter-octave log2 buckets: index = floor(4·log2(dt)).  Bucket width is
# 2^0.25 ≈ 19%, so a quantile read back as the bucket's geometric midpoint
# is within ±9% — plenty for phase timing, at a bounded ~4 bytes/bucket.
# Indices clamp to [≈1ns, ≈5d], so the table size is bounded (~200 slots)
# no matter what durations arrive.
_HIST_SCALE = 4
_HIST_MIN_IDX = _HIST_SCALE * -30  # 2^-30 s ≈ 1 ns
_HIST_MAX_IDX = _HIST_SCALE * 19  # 2^19 s ≈ 6 days


def _hist_index(dt: float) -> int:
    if dt <= 0:
        return _HIST_MIN_IDX
    i = math.floor(_HIST_SCALE * math.log2(dt))
    return max(_HIST_MIN_IDX, min(_HIST_MAX_IDX, i))


def _hist_value(idx: int) -> float:
    return 2.0 ** ((idx + 0.5) / _HIST_SCALE)


def _hist_quantile(hist: dict, count: int, q: float) -> float:
    """Value at quantile ``q`` (geometric bucket midpoint)."""
    rank = max(1, math.ceil(q * count))
    seen = 0
    for idx in sorted(hist):
        seen += hist[idx]
        if seen >= rank:
            return _hist_value(idx)
    return 0.0


def quantiles_ms(hist: dict, count: int) -> dict:
    """p50/p95/p99 in milliseconds from one span's bucket table."""
    if not count:
        return {}
    return {
        f"p{int(q * 100)}_ms": round(_hist_quantile(hist, count, q) * 1e3, 4)
        for q in (0.50, 0.95, 0.99)
    }


# ------------------------------------------------------------ event buffer
def enable_events(on: bool = True) -> None:
    """Toggle the per-occurrence event log (see module docs)."""
    global _events_enabled
    with _lock:
        _events_enabled = on


def set_events_capacity(capacity: int) -> None:
    """Resize the event ring buffer, keeping the newest events; any
    events a shrink discards count into ``events_dropped`` exactly like
    ring overflow (the drop counter is the completeness signal timeline
    consumers rely on)."""
    if capacity < 1:
        raise ValueError(f"event capacity must be >= 1, got {capacity}")
    global _events, _events_capacity
    with _lock:
        overflow = len(_events) - capacity
        if overflow > 0:
            _counters["events_dropped"] = (
                _counters.get("events_dropped", 0) + overflow
            )
        _events_capacity = capacity
        _events = deque(_events, maxlen=capacity)


def events_capacity() -> int:
    return _events_capacity


def events_enabled() -> bool:
    return _events_enabled


def drain_events() -> list[dict]:
    """Like :func:`events`, but CONSUMES the ring buffer: the returned
    occurrences are removed, so successive drains never hand out the
    same event twice (the metrics sink drains, keeping one timeline per
    record instead of a cumulative re-copy)."""
    _fold_in()
    with _lock:
        out = [dict(e) for e in _events]
        _events.clear()
        return out


def events() -> list[dict]:
    """A consistent copy of the recorded occurrences, in completion order.
    Span entries: name, t0, t1 (``time.perf_counter`` seconds — monotonic,
    cross-thread comparable), meta (the span's ``meta`` arg), tid/thread
    (recording thread), kind ("span").  Counter/gauge entries carry
    ``kind: "counter"/"gauge"`` and the post-update ``value`` at ``t0``;
    a ``kind: "pause"`` entry has a span entry's keys (module docs)."""
    _fold_in()
    with _lock:
        return [dict(e) for e in _events]


def _append_event_locked(entry: dict) -> None:
    if len(_events) == _events.maxlen:
        _counters["events_dropped"] = _counters.get("events_dropped", 0) + 1
    _events.append(entry)


def _event_base(name: str, kind: str) -> dict:
    t = threading.current_thread()
    return {"name": name, "kind": kind, "tid": t.ident, "thread": t.name}


def _interval_entry(name: str, kind: str, t0: float, t1: float, meta,
                    sid: int, parent: tuple | None) -> dict:
    """The entry of something that lasted (a span, a pause), recorded by
    the calling thread; ``parent`` is an ``_open`` value."""
    e = _event_base(name, kind)
    e["t0"], e["t1"], e["meta"] = t0, t1, meta
    e["id"] = sid
    e["parent"] = parent[0] if parent is not None else None
    return e


def pause_entry(name: str, t0: float, t1: float, meta) -> dict:
    """A ``kind: "pause"`` entry (module docs) for ``[t0, t1]`` on the
    spans' clock, its parent the span open in the calling context.  Takes
    no lock: the caller hands it to :func:`fold` when it may."""
    return _interval_entry(name, "pause", t0, t1, meta, next(_ids), _open.get())


# ------------------------------------------------------------------- spans
# The span open in the calling context, as ``(id, name)``.  Tasks and
# to_thread hops copy the context at creation, so whatever they open is
# parented on the span that was open where they were spawned.
_open: contextvars.ContextVar[tuple | None] = contextvars.ContextVar(
    "crdt_trace_open_span", default=None
)
_ids = itertools.count(1)


class span:
    """Time a phase: ``with span("stream.decrypt", meta=k): ...``.
    Re-entrant and concurrency-tolerant: every exit accumulates (count,
    seconds, histogram, parent name) under ``name``.  ``meta`` (e.g. a
    chunk index) is recorded only in the event log, never in the
    aggregate.  A plain class, not a ``contextmanager`` generator: the
    service opens some ten spans for each of a thousand tenants a cycle,
    and this form costs about half as much."""

    __slots__ = ("name", "meta", "_id", "_parent", "_token", "_ann", "_t0")

    def __init__(self, name: str, meta=None):
        self.name = name
        self.meta = meta

    def __enter__(self) -> None:
        self._parent = _open.get()
        self._id = next(_ids)
        self._ann = None
        if jax_annotations and "jax" in sys.modules:
            import jax.profiler

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        # set last: nothing after it can raise and leave the marker open
        self._token = _open.set((self._id, self.name))
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        try:
            _open.reset(self._token)
        except ValueError:
            # exited in another Context than it was entered in: the
            # marker there is not ours to restore.  The measurement is
            # kept and the body's exception is not masked
            pass
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _record_span(
            self.name, self._t0, t1, self.meta, self._id, self._parent
        )
        return False


def _record_span(name: str, t0: float, t1: float, meta, sid: int,
                 parent: tuple | None) -> None:
    dt = t1 - t0
    with _lock:
        slot = _spans.get(name)
        if slot is None:
            slot = _spans[name] = [0, 0.0, 0.0, {}, set()]
        slot[0] += 1
        slot[1] += dt
        if dt > slot[2]:
            slot[2] = dt
        idx = _hist_index(dt)
        slot[3][idx] = slot[3].get(idx, 0) + 1
        slot[4].add(parent[1] if parent is not None else None)
        if _events_enabled:
            _append_event_locked(
                _interval_entry(name, "span", t0, t1, meta, sid, parent)
            )
    logger.debug("span %s: %.6fs", name, dt)


def observe(name: str, seconds: float, meta=None) -> None:
    """Record one occurrence of ``seconds`` under span ``name`` without a
    context manager — for durations reported by a callback (e.g. the XLA
    compile-time listener in obs.runtime).  Its parent is the span open
    in the calling context."""
    t1 = time.perf_counter()
    _record_span(name, t1 - seconds, t1, meta, next(_ids), _open.get())


# ---------------------------------------------------------------- counters
# Context-local counter taps: the registry's counters are process-wide,
# which is exactly wrong for a caller that needs "increments caused by MY
# work" while other tasks share the process (the population runner's
# lanes each need their own quarantine tally).  A tap is a plain dict
# registered in the calling context; every add() mirrors its increment
# into each tap visible from the caller's context.  asyncio tasks and
# to_thread hops copy the context at creation, so a tap covers the whole
# task tree under the ``with`` — and nothing outside it.
_taps: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "crdt_trace_counter_taps", default=()
)


@contextmanager
def counter_tap():
    """Yield a dict accumulating every counter increment made from this
    context (and tasks/threads spawned within it) until exit.  Taps
    nest — an inner tap does not steal from an outer one, both see the
    increment.  The global registry is untouched; read the tap."""
    local: dict[str, int] = {}
    token = _taps.set(_taps.get() + (local,))
    try:
        yield local
    finally:
        _taps.reset(token)


def _add_locked(name: str, n: int, taps: tuple) -> None:
    value = _counters.get(name, 0) + n
    _counters[name] = value
    for tap in taps:
        tap[name] = tap.get(name, 0) + n
    if _events_enabled:
        e = _event_base(name, "counter")
        e["t0"] = e["t1"] = time.perf_counter()
        e["meta"], e["value"] = None, value
        _append_event_locked(e)


def add(name: str, n: int = 1) -> None:
    """Bump a counter (e.g. ops folded, states merged, bytes decrypted)."""
    with _lock:
        _add_locked(name, n, _taps.get())


def add_many(counts: dict) -> None:
    """``add(name, n)`` for every item of ``counts``, under one
    acquisition of the registry's lock."""
    with _lock:
        taps = _taps.get()
        for name, n in counts.items():
            _add_locked(name, n, taps)


def gauge(name: str, value: float) -> None:
    """Set a point-in-time gauge (e.g. device bytes in use)."""
    with _lock:
        _gauges[name] = value
        if _events_enabled:
            e = _event_base(name, "gauge")
            e["t0"] = e["t1"] = time.perf_counter()
            e["meta"], e["value"] = None, value
            _append_event_locked(e)


# ---------------------------------------------------------------- registry
def snapshot() -> dict:
    """A consistent copy: {"spans": {name: {"count", "seconds", "max_ms",
    "p50_ms", "p95_ms", "p99_ms", "parents"}}, "counters": {...},
    "gauges": {...}}.  ``parents`` lists the names of the spans this one
    was seen under, sorted, ``None`` (first) where it was a root."""
    _fold_in()
    with _lock:
        return {
            "spans": {
                k: {
                    "count": c,
                    "seconds": s,
                    "max_ms": round(mx * 1e3, 4),
                    **quantiles_ms(h, c),
                    "parents": sorted(
                        p, key=lambda n: (n is not None, n or "")
                    ),
                }
                for k, (c, s, mx, h, p) in _spans.items()
            },
            "counters": dict(_counters),
            "gauges": dict(_gauges),
        }


def tree() -> dict:
    """The span tree the aggregates have seen: name -> sorted names of
    its direct children, and ``None`` -> the roots.  A name seen under
    two parents is a child of both."""
    out: dict = {}
    with _lock:
        for name, slot in _spans.items():
            for parent in slot[4]:
                out.setdefault(parent, []).append(name)
    return {k: sorted(v) for k, v in out.items()}


def reset() -> None:
    """Clear every aggregate (the parents seen included) and the event
    log, and restore the event defaults (recording OFF, default
    capacity) — a test or run that enabled events cannot leak recording
    state into the next one.  Totals kept outside the registry
    (:func:`on_read`) are folded in first and cleared with the rest."""
    global _events_enabled, _events_capacity, _events
    _fold_in()
    with _lock:
        _spans.clear()
        _counters.clear()
        _gauges.clear()
        _events_enabled = False
        _events_capacity = DEFAULT_EVENT_CAPACITY
        _events = deque(maxlen=DEFAULT_EVENT_CAPACITY)


def format_snapshot(snap: dict) -> str:
    """Human-readable phase table for one snapshot dict (shared by
    ``report()`` and the obs_report CLI)."""
    lines = []
    spans = sorted(
        snap.get("spans", {}).items(),
        key=lambda kv: kv[1]["seconds"],
        reverse=True,
    )
    if spans:
        w = max(len(k) for k, _ in spans)
        for k, v in spans:
            q = ""
            if "p50_ms" in v:
                q = (
                    f"  p50 {v['p50_ms']:>9.3f}ms  p95 {v['p95_ms']:>9.3f}ms"
                    f"  p99 {v['p99_ms']:>9.3f}ms  max {v['max_ms']:>9.3f}ms"
                )
            lines.append(
                f"{k:<{w}}  {v['seconds']:>9.4f}s  x{v['count']}{q}"
            )
    for k in sorted(snap.get("counters", ())):
        lines.append(f"{k} = {snap['counters'][k]}")
    for k in sorted(snap.get("gauges", ())):
        lines.append(f"{k} = {snap['gauges'][k]} (gauge)")
    return "\n".join(lines) if lines else "(no spans recorded)"


def report() -> str:
    """Human-readable phase table, longest total first, with quantiles."""
    return format_snapshot(snapshot())


def throughput(span_name: str, counter_name: str) -> float | None:
    """counter / span-seconds, or None if either is missing/zero."""
    snap = snapshot()
    s = snap["spans"].get(span_name)
    c = snap["counters"].get(counter_name)
    if not s or not c or s["seconds"] <= 0:
        return None
    return c / s["seconds"]
