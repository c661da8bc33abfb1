"""The collector's passes on the program's clock (obs.runtime ``track_gc``,
ISSUE 34): counters for every pass, a ``pause`` entry of the event log for
a pass of generation 1 or 2, never a span aggregate; and the two hazards the
callback is built around: a pass that starts inside the registry's own lock,
and a pass that ends just before a snapshot is read.
"""

from __future__ import annotations

import gc
import threading

import pytest

from crdt_enc_tpu.obs import record, runtime, timeline

GC_COUNTERS = ("gc_passes", "gc_pause_us", "gc_full_passes",
               "gc_full_pause_us", "gc_collected")


@pytest.fixture(autouse=True)
def _tracking_on():
    """Tracking on for the test, the module's choice flags put back after
    it (an explicit choice made here must not stick for the worker's later
    tests)."""
    saved = runtime._gc_enabled, runtime._gc_explicit
    record.reset()
    runtime._set_gc(True)
    yield
    runtime._gc_enabled, runtime._gc_explicit = saved
    record.reset()


def pauses() -> list:
    return [e for e in record.events() if e["kind"] == "pause"]


def test_forced_full_pass_is_counted_and_logged_under_the_open_span():
    record.enable_events()
    with record.span("phase.holding_the_pass"):
        gc.collect()
    counters = record.snapshot()["counters"]
    assert counters["gc_full_passes"] == 1
    assert counters["gc_passes"] >= 1
    assert counters["gc_pause_us"] >= counters["gc_full_pause_us"] > 0
    (pause,) = [p for p in pauses() if p["meta"]["generation"] == 2]
    (holder,) = [e for e in record.events()
                 if e["name"] == "phase.holding_the_pass"]
    assert pause["name"] == "runtime.gc"
    assert pause["parent"] == holder["id"]
    assert holder["t0"] <= pause["t0"] <= pause["t1"] <= holder["t1"]
    assert pause["tid"] == threading.get_ident()
    assert set(pause["meta"]) == {"generation", "collected"}


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_only_generations_one_and_two_are_logged(generation):
    record.enable_events()
    gc.collect(generation)
    counters = record.snapshot()["counters"]
    assert counters["gc_passes"] >= 1
    assert counters.get("gc_full_passes", 0) == (generation == 2)
    logged = [p["meta"]["generation"] for p in pauses()]
    assert (generation in logged) == (generation > 0)
    assert 0 not in logged


def test_a_pass_that_frees_cycles_counts_what_it_collected():
    class Node:
        pass

    gc.collect()
    record.reset()
    for _ in range(50):
        a, b = Node(), Node()
        a.other, b.other = b, a
    del a, b
    gc.collect()
    assert record.snapshot()["counters"]["gc_collected"] >= 100


def test_a_pass_inside_the_registrys_lock_returns_and_is_counted_later():
    """``_record_span`` builds a dict under ``record._lock`` and a pass can
    start at any allocation: the callback must not wait for that lock."""
    done = threading.Event()

    def collect_holding_the_lock():
        with record._lock:
            gc.collect()
        done.set()

    worker = threading.Thread(target=collect_holding_the_lock, daemon=True)
    worker.start()
    worker.join(timeout=30)
    assert done.is_set() and not worker.is_alive(), "the callback blocked on record._lock"
    counters = record.snapshot()["counters"]
    assert counters["gc_full_passes"] == 1 and counters["gc_pause_us"] > 0


def test_a_pass_that_ended_before_the_second_snapshot_is_in_the_delta():
    """What ``cellbench/run.py`` does around a timed call."""
    before = record.snapshot()["counters"]
    gc.collect()
    after = record.snapshot()["counters"]
    assert after["gc_full_passes"] - before.get("gc_full_passes", 0) == 1
    assert after["gc_pause_us"] > before.get("gc_pause_us", 0)
    # and nothing is counted twice by a third read
    again = record.snapshot()["counters"]
    assert again["gc_full_passes"] == after["gc_full_passes"]


def test_explicit_off_sticks_against_the_default_on_wiring():
    runtime._gc_explicit = False
    runtime.track_gc(False)
    runtime.ensure_gc_tracking()  # what every TpuAccelerator() calls
    record.reset()
    gc.collect()
    assert not any(k in record.snapshot()["counters"] for k in GC_COUNTERS)
    runtime.track_gc(True)
    gc.collect()
    assert record.snapshot()["counters"]["gc_full_passes"] == 1


def test_default_on_wiring_installs_one_callback_once():
    runtime._gc_explicit = False
    for _ in range(3):
        runtime.ensure_gc_tracking()
    assert gc.callbacks.count(runtime._on_gc) == 1
    assert record._read_hooks.count(runtime._fold_gc) == 1


def test_reset_clears_the_counts_and_keeps_the_callback():
    record.enable_events()
    gc.collect()
    assert record.snapshot()["counters"]["gc_full_passes"] == 1
    record.reset()
    assert runtime._on_gc in gc.callbacks
    assert "gc_full_passes" not in record.snapshot()["counters"]
    assert pauses() == []
    gc.collect()
    assert record.snapshot()["counters"]["gc_full_passes"] == 1


def test_a_pause_is_never_a_span_aggregate():
    record.enable_events()
    with record.span("phase.holding_the_pass"):
        gc.collect()
    snap = record.snapshot()
    assert "runtime.gc" not in snap["spans"]
    assert all("runtime.gc" not in kids for kids in record.tree().values())
    assert list(snap["spans"]) == ["phase.holding_the_pass"]


def test_counter_taps_do_not_see_the_collector():
    with record.counter_tap() as tap:
        gc.collect()
        record.snapshot()
    assert not any(k in tap for k in GC_COUNTERS)


def test_timeline_draws_a_pause_on_the_collecting_threads_lane():
    record.enable_events()
    with record.span("phase.holding_the_pass"):
        gc.collect()
    trace_obj = timeline.to_chrome_trace()
    drawn = [e for e in trace_obj["traceEvents"] if e.get("cat") == "pause"]
    (full,) = [e for e in drawn if e["args"]["generation"] == 2]
    (holder,) = [e for e in trace_obj["traceEvents"]
                 if e.get("name") == "phase.holding_the_pass"]
    assert full["ph"] == "X" and full["name"] == "runtime.gc"
    assert full["tid"] == holder["tid"]
    assert full["args"]["parent"] == holder["args"]["id"]
    assert "chunk" not in full["args"]


def test_metrics_exposition_carries_the_collector_counters():
    from crdt_enc_tpu.obs import sink

    gc.collect()
    text = sink.to_prometheus()
    assert "gc_full_passes_total 1" in text
    assert "gc_pause_us_total" in text


def test_pause_entries_wait_bounded_for_a_reader():
    record.enable_events()
    room = runtime._gc_pauses.maxlen
    # a real pass that falls between a pair below takes that pair's place
    for _ in range(room + 64):
        runtime._on_gc("start", {"generation": 1})
        runtime._on_gc("stop", {"generation": 1, "collected": 0})
    assert len(runtime._gc_pauses) == room
    assert record.snapshot()["counters"]["events_dropped"] >= 1
    assert len(pauses()) == room


def test_passes_from_many_threads_lose_no_count_under_concurrent_reads():
    """More collecting threads than cores, a shortened switch interval, and
    a reader folding all the while: the registry ends with exactly the passes
    an independent callback saw stop, and no read ever shows the full passes
    ahead of all passes."""
    import sys
    import time

    seen = [0]

    def witness(phase, info):
        if phase == "stop":
            seen[0] += 1

    stop_reading = threading.Event()
    torn = []

    def reader():
        while not stop_reading.is_set():
            c = record.snapshot()["counters"]
            if c.get("gc_full_passes", 0) > c.get("gc_passes", 0) or (
                    c.get("gc_full_pause_us", 0) > c.get("gc_pause_us", 0)):
                torn.append(c)

    def collector(k):
        deadline = time.monotonic() + 20
        for i in range(300):
            gc.collect(i % 3 if k % 2 else 0)
            if time.monotonic() > deadline:
                break

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    gc.callbacks.append(witness)
    try:
        record.reset()  # folds what is pending, so both counts start level
        seen[0] = 0
        threads = [threading.Thread(target=collector, args=(k,), daemon=True)
                   for k in range(24)]
        watcher = threading.Thread(target=reader, daemon=True)
        watcher.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        stop_reading.set()
        watcher.join(timeout=30)
        assert not watcher.is_alive() and not any(t.is_alive() for t in threads)
        counted = record.snapshot()["counters"]["gc_passes"]
        witnessed = seen[0]
    finally:
        gc.callbacks.remove(witness)
        sys.setswitchinterval(interval)
    assert not torn, torn[:1]
    # a pass in flight at either edge is seen by one side alone
    assert abs(counted - witnessed) <= 2, (counted, witnessed)
    # a ``gc.collect()`` that finds a pass in flight on another thread returns
    # at once (passes never nest), so far fewer than 24 x 300 ran
    assert counted >= 300
