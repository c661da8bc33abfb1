"""The observability subsystem (crdt_enc_tpu/obs/, ISSUE 2).

Pinned here:

* **histogram quantiles**: log-scale aggregates report p50/p95/p99 within
  the documented quarter-octave bucket error;
* **event ring buffer**: bounded capacity, drop counting, and
  ``reset()`` restoring the events-off default (no state leaks between
  tests);
* **thread safety**: concurrent spans/counters lose no updates;
* **disabled-path overhead**: spans stay cheap with events off;
* **timeline export**: Chrome-trace JSON schema (lanes, chunk args,
  counter tracks) and the chunk-overlap proof on a recorded streaming
  run, via the obs_report CLI — the ISSUE 2 acceptance;
* **recompile counter**: constant across a varying-batch fold loop
  (the ADVICE-r5 unbounded-recompile bug class, mechanized);
* **sink**: JSONL round-trip, Prometheus exposition, Core.compact
  wiring;
The span-name registry lint lives in the static-analysis engine now
(rule SPN001, gated by tests/test_static_analysis.py).
"""

from __future__ import annotations

import json
import secrets
import threading
import time

import numpy as np
import pytest

from crdt_enc_tpu.obs import record, runtime, sink, timeline
from crdt_enc_tpu.utils import codec, trace


@pytest.fixture(autouse=True)
def _clean_registry():
    trace.reset()
    yield
    trace.reset()


def test_trace_shim_is_the_registry():
    # the utils.trace compat shim and obs.record must be ONE module, or
    # flags set through the old name would fork
    assert trace is record


# ---------------------------------------------------------------- histogram


def test_histogram_quantiles_within_bucket_error():
    durations = [0.001] * 50 + [0.010] * 45 + [0.100] * 5
    for d in durations:
        record.observe("phase.x", d)
    s = trace.snapshot()["spans"]["phase.x"]
    assert s["count"] == 100
    # quarter-octave buckets: estimates within ~±19% of the true value
    assert 0.8 <= s["p50_ms"] <= 1.25
    assert 8.0 <= s["p95_ms"] <= 12.5
    assert 80.0 <= s["p99_ms"] <= 125.0
    assert s["max_ms"] >= 99.0
    rep = trace.report()
    assert "p95" in rep and "phase.x" in rep


def test_observe_feeds_throughput_and_report():
    record.observe("phase.y", 0.5)
    trace.add("items", 100)
    assert 150 < trace.throughput("phase.y", "items") < 250


# ------------------------------------------------------------- event buffer


def test_event_ring_buffer_bounds_and_drop_counter():
    trace.enable_events()
    trace.set_events_capacity(4)
    for i in range(10):
        with trace.span("phase.x", meta=i):
            pass
    evs = trace.events()
    assert len(evs) == 4
    # newest survive, oldest dropped
    assert [e["meta"] for e in evs] == [6, 7, 8, 9]
    assert trace.snapshot()["counters"]["events_dropped"] == 6
    # aggregates are NOT affected by event drops
    assert trace.snapshot()["spans"]["phase.x"]["count"] == 10
    # a capacity SHRINK counts its discards too — the drop counter is the
    # timeline-completeness signal, whatever caused the loss
    trace.set_events_capacity(1)
    assert len(trace.events()) == 1
    assert trace.snapshot()["counters"]["events_dropped"] == 9


def test_reset_restores_events_defaults():
    trace.enable_events()
    trace.set_events_capacity(8)
    with trace.span("phase.x"):
        pass
    assert trace.events()
    trace.reset()
    # flag AND capacity restored: a seam test cannot leak event
    # recording (or a tiny ring) into later tests
    assert trace.events_capacity() == record.DEFAULT_EVENT_CAPACITY
    with trace.span("phase.x"):
        pass
    assert trace.events() == []


def test_events_carry_thread_identity():
    trace.enable_events()
    with trace.span("phase.x"):
        pass
    t = threading.Thread(
        target=lambda: record.observe("phase.x", 0.001), name="obs-worker"
    )
    t.start()
    t.join()
    threads = {e["thread"] for e in trace.events()}
    assert "obs-worker" in threads and len(threads) == 2
    assert all(isinstance(e["tid"], int) for e in trace.events())


# ------------------------------------------------------------ thread safety


def test_multithreaded_spans_and_counters_lose_no_updates(request):
    N_THREADS, N_ITERS = 8, 400
    # a collector pass of generation 1 or 2 is an entry of the event log
    # too, where an earlier test of this process turned the tracker on
    # (``runtime.track_gc``): the count below is of this test's events
    import gc

    gc.disable()
    request.addfinalizer(gc.enable)
    trace.enable_events()
    trace.set_events_capacity(N_THREADS * N_ITERS // 2)  # force drops too
    barrier = threading.Barrier(N_THREADS)

    def work(k):
        barrier.wait()
        for _ in range(N_ITERS):
            with trace.span("stress.span"):
                pass
            trace.add("stress_counter", 1)
            trace.gauge("stress_gauge", k)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = trace.snapshot()
    total = N_THREADS * N_ITERS
    assert snap["spans"]["stress.span"]["count"] == total
    assert snap["counters"]["stress_counter"] == total
    assert snap["gauges"]["stress_gauge"] in range(N_THREADS)
    # histogram buckets account for every occurrence
    hist_total = sum(
        record._spans["stress.span"][3].values()  # noqa: SLF001 — white-box
    )
    assert hist_total == total
    # ring buffer stayed bounded and drops were counted exactly
    kept = len(trace.events())
    dropped = snap["counters"]["events_dropped"]
    assert kept == trace.events_capacity()
    # span + counter + gauge events each fired `total` times
    assert kept + dropped == 3 * total


def test_disabled_path_overhead_and_no_events():
    N = 20_000
    t0 = time.perf_counter()
    for _ in range(N):
        with trace.span("phase.x"):
            pass
    per_span = (time.perf_counter() - t0) / N
    assert trace.events() == []
    assert trace.snapshot()["spans"]["phase.x"]["count"] == N
    # generous bound (~30x measured) so machine weather can't flake it;
    # catches accidental O(events) or allocation regressions on the
    # disabled path
    assert per_span < 200e-6, f"span overhead {per_span * 1e6:.1f}µs"


# ----------------------------------------------------------------- timeline


def _synthetic_pipeline_events():
    """A recorded 4-chunk run of the real ingest pipeline with stage
    durations pinned by sleeps — deterministic overlap on any box."""
    from crdt_enc_tpu import ops as K

    trace.enable_events()

    def ingest(span, k):
        time.sleep(0.02)
        return span

    def reduce(item, k):
        time.sleep(0.05)

    K.run_ingest_pipeline(list(range(4)), ingest, reduce, depth=2)
    trace.add("h2d_bytes", 4096)
    return trace.events()


def test_chrome_trace_schema_golden():
    events = _synthetic_pipeline_events()
    obj = timeline.to_chrome_trace(events)
    # round-trips as JSON (Perfetto/chrome://tracing load this directly)
    obj = json.loads(json.dumps(obj))
    assert obj["displayTimeUnit"] == "ms"
    evs = obj["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert phases == {"M", "X", "C"}
    # one thread_name metadata event per lane; producer + consumer lanes
    lanes = [e for e in evs if e["ph"] == "M"]
    assert {e["name"] for e in lanes} == {"thread_name"}
    lane_names = {e["args"]["name"] for e in lanes}
    # producer workers are numbered lanes (crdt-ingest-producer-<i>);
    # a single-producer run exports exactly producer + consumer
    assert any(n.startswith("crdt-ingest-producer") for n in lane_names)
    assert len(lanes) == 2
    # timestamps rebase to 0 at the earliest event (the run's
    # stream_producers gauge fires first, ahead of any X span); X events
    # carry positive durations and the chunk index in args
    xs = [e for e in evs if e["ph"] == "X"]
    assert min(e["ts"] for e in evs if e["ph"] in ("X", "C")) == 0.0
    assert min(e["ts"] for e in xs) >= 0.0
    assert all(e["dur"] > 0 for e in xs)
    ingests = [e for e in xs if e["name"] == "stream.ingest"]
    assert sorted(e["args"]["chunk"] for e in ingests) == [0, 1, 2, 3]
    # ingest and reduce run on DIFFERENT lanes
    tid_by_stage = {
        name: {e["tid"] for e in xs if e["name"] == name}
        for name in ("stream.ingest", "stream.reduce")
    }
    assert tid_by_stage["stream.ingest"].isdisjoint(tid_by_stage["stream.reduce"])
    # counter track present
    cs = [e for e in evs if e["ph"] == "C"]
    assert any(e["name"] == "h2d_bytes" and e["args"]["value"] == 4096
               for e in cs)
    # and the overlap is provable from the exported JSON alone
    assert timeline.chunk_overlaps(obj, "stream.ingest", "stream.reduce")


def _native_crypto_or_skip():
    from crdt_enc_tpu import native

    try:
        native.load()
    except RuntimeError as e:
        pytest.skip(f"native crypto library unavailable: {e}")


def test_export_trace_cli_proves_overlap_on_streaming_run(
    tmp_path, capsys, monkeypatch
):
    """ISSUE 2 acceptance: obs_report export-trace on a recorded
    streaming run (encrypted op files → the core's pipelined ingest)
    emits valid Chrome-trace JSON whose events prove chunk k+1's decrypt
    overlaps chunk k's fold."""
    _native_crypto_or_skip()
    import asyncio
    import time as _time

    from _ingest_doors import orset_workload, read_pipelined, seed_remote
    from crdt_enc_tpu.parallel import TpuAccelerator
    from crdt_enc_tpu.parallel import session as psession
    from crdt_enc_tpu.tools import obs_report

    files, _, host = orset_workload(n_files=60, ops_per_file=8)
    # a slowed consumer widens the overlap window so the proof is
    # deterministic on one core: a PIPELINED run shows chunk k+1's
    # decrypt starting inside the slow fold k; a serial run would not,
    # however slow the fold — same discipline as the seam tests'
    # injected delays
    real_reduce = psession.OrsetFoldSession.reduce_chunk

    def slow_reduce(self, decoded):
        _time.sleep(0.005)
        return real_reduce(self, decoded)

    monkeypatch.setattr(
        psession.OrsetFoldSession, "reduce_chunk", slow_reduce
    )

    async def go():
        remote, _ = await seed_remote(files)
        trace.enable_events()
        return await read_pipelined(remote, 6, accel=TpuAccelerator())

    reader = asyncio.run(go())
    assert reader.with_state(
        lambda s: codec.pack(s.to_obj())
    ) == codec.pack(host.to_obj())
    # record the run through the sink (events attach automatically)
    run_path = tmp_path / "run.jsonl"
    rec = sink.MetricsSink(str(run_path)).write("e2e-streaming-smoke")
    assert rec["events"]
    out_path = tmp_path / "trace.json"
    rc = obs_report.main([
        "export-trace", str(run_path), "-o", str(out_path),
        "--check-overlap", "ops.chunk_decrypt:ops.chunk_fold",
    ])
    assert rc == 0, capsys.readouterr()
    with open(out_path) as f:
        obj = json.load(f)
    assert obj["traceEvents"]
    ks = timeline.chunk_overlaps(obj, "ops.chunk_decrypt", "ops.chunk_fold")
    assert ks, "recorded streaming run shows no decrypt/fold overlap"
    out = capsys.readouterr().out
    assert "overlap proof" in out


# ------------------------------------------------------------ JAX runtime


def test_recompile_counter_constant_across_varying_batches():
    """ISSUE 2 acceptance: the jax_compiles counter stays CONSTANT
    across a fold loop whose raw batch sizes vary inside one padding
    bucket — the regression test for the ADVICE-r5 recompile bug class
    (every growth step recompiling the donated fold)."""
    import jax

    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.parallel.accel import _bucket

    runtime.track_recompiles()
    R, E = 4, 8
    rng = np.random.default_rng(5)

    def fold(n_rows):
        bucket = _bucket(n_rows, floor=64)
        kind = np.zeros(bucket, np.int8)
        member = np.zeros(bucket, np.int32)
        actor = np.full(bucket, R, np.int32)  # sentinel-pad the tail
        counter = np.zeros(bucket, np.int32)
        kind[:n_rows] = rng.integers(0, 2, n_rows)
        member[:n_rows] = rng.integers(0, E, n_rows)
        actor[:n_rows] = rng.integers(0, R, n_rows)
        counter[:n_rows] = rng.integers(1, 100, n_rows)
        out = K.orset_fold(
            np.zeros(R, np.int32), np.zeros((E, R), np.int32),
            np.zeros((E, R), np.int32), kind, member, actor, counter,
            num_members=E, num_replicas=R,
        )
        jax.block_until_ready(out)

    fold(40)  # warmup: compiles once for the 64-row bucket
    baseline = runtime.recompile_count()
    for n in (33, 47, 56, 64, 41):
        fold(n)
    assert runtime.recompile_count() == baseline, (
        "varying raw batch sizes inside one padding bucket recompiled "
        "the fold"
    )
    # ...and a bucket CHANGE is visible as exactly what it is
    fold(100)
    assert runtime.recompile_count() > baseline


def test_jax_compile_span_records_durations():
    import jax
    import jax.numpy as jnp

    runtime.track_recompiles()

    @jax.jit
    def f(x):
        return x * 2 + 1

    jax.block_until_ready(f(jnp.arange(7)))
    snap = trace.snapshot()
    assert snap["counters"].get("jax_compiles", 0) >= 1
    assert snap["spans"]["jax.compile"]["seconds"] > 0


def test_sample_device_memory_cpu_degrades_to_noop():
    # CPU backend has no allocator stats: returns None, records nothing,
    # and caches the capability probe
    assert runtime.sample_device_memory() is None
    assert "device_bytes_in_use" not in trace.snapshot()["gauges"]


# ------------------------------------------------------------------- sink


def test_sink_jsonl_roundtrip_and_prometheus(tmp_path):
    with trace.span("stream.fold"):
        pass
    trace.add("ops_folded", 7)
    trace.gauge("device_bytes_in_use", 123)
    path = tmp_path / "metrics.jsonl"
    s = sink.MetricsSink(str(path))
    s.write("first")
    s.write("second", meta={"note": "hi"})
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["label"] for r in lines] == ["first", "second"]
    rec = lines[-1]
    assert rec["counters"]["ops_folded"] == 7
    assert rec["spans"]["stream.fold"]["count"] == 1
    assert rec["meta"] == {"note": "hi"}
    assert "events" not in rec  # events off → no timeline payload
    prom = sink.to_prometheus(rec)
    assert "crdt_ops_folded_total 7" in prom
    assert 'crdt_span_count_total{span="stream.fold"} 1' in prom
    assert "crdt_device_bytes_in_use 123" in prom
    assert 'quantile="0.95"' in prom
    # registry-derived exposition metadata (ISSUE 6 satellite)
    assert "# TYPE crdt_ops_folded_total counter" in prom
    assert "# TYPE crdt_device_bytes_in_use gauge" in prom
    assert "# HELP crdt_ops_folded_total" in prom
    # sink records are schema-stamped so fleet/trend can reject
    # mixed-version inputs loudly
    assert rec["schema"] == sink.SCHEMA_VERSION


def test_sink_drains_events_per_write(tmp_path):
    trace.enable_events()
    with trace.span("phase.x", meta=0):
        pass
    s = sink.MetricsSink(str(tmp_path / "m.jsonl"))
    first = s.write("first")
    assert [e["name"] for e in first["events"]] == ["phase.x"]
    # drained: a second write without new activity carries no timeline,
    # and the live log is empty
    assert "events" not in s.write("second")
    assert trace.events() == []
    # disabling recording (without reset) also stops attachment, even if
    # stale events remained
    with trace.span("phase.x", meta=1):
        pass
    trace.enable_events(False)
    assert "events" not in s.write("third")


def test_chunk_overlaps_ignores_earlier_runs():
    """An event log holding TWO pipeline runs (e.g. warmup then
    measured) must not pair run-1 reduces with run-2 ingests — a fully
    serialized second run yields NO overlap proof."""
    def x(name, chunk, ts, dur):
        return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                "args": {"chunk": chunk}, "pid": 1, "tid": 0}

    # reduce k spans [100k+40, 100k+110): ingest k+1 (starts 100k+100)
    # opens inside it — every interior chunk overlaps
    run1 = [x("stream.ingest", k, 100 * k, 50) for k in range(4)] + [
        x("stream.reduce", k, 100 * k + 40, 70) for k in range(4)
    ]
    # second run, strictly serialized: ingest k+1 starts after reduce k
    base = 10_000
    run2 = []
    for k in range(3):
        run2.append(x("stream.ingest", k, base + 200 * k, 50))
        run2.append(x("stream.reduce", k, base + 200 * k + 60, 50))
    serial = {"traceEvents": run1 + run2, "displayTimeUnit": "ms"}
    assert timeline.chunk_overlaps(serial) == []
    # run 1 alone DID overlap — the split keeps real proofs working
    assert timeline.chunk_overlaps(
        {"traceEvents": run1, "displayTimeUnit": "ms"}
    )


def test_sample_device_memory_explicit_device_bypasses_cache():
    class FakeDev:
        def memory_stats(self):
            return {"bytes_in_use": 5, "peak_bytes_in_use": 9}

    # the default-device probe on CPU latched unsupported...
    assert runtime.sample_device_memory() is None
    assert runtime._mem_supported is False  # noqa: SLF001 — white-box
    # ...but an explicitly passed stats-capable device still samples
    stats = runtime.sample_device_memory(FakeDev())
    assert stats == {"bytes_in_use": 5, "peak_bytes_in_use": 9}
    g = trace.snapshot()["gauges"]
    assert g["device_bytes_in_use"] == 5 and g["device_peak_bytes"] == 9
    # and the default-device cache was not flipped by the explicit probe
    assert runtime._mem_supported is False  # noqa: SLF001


def test_sink_default_from_env_and_configure(tmp_path, monkeypatch):
    env_path = tmp_path / "env.jsonl"
    monkeypatch.setenv(sink.ENV_VAR, str(env_path))
    monkeypatch.setattr(sink, "_configured", False)
    assert sink.maybe_write("via-env") is not None
    assert json.loads(env_path.read_text())["label"] == "via-env"
    # explicit configure overrides the env var
    conf_path = tmp_path / "conf.jsonl"
    sink.configure(str(conf_path))
    try:
        sink.maybe_write("via-configure")
        assert json.loads(conf_path.read_text())["label"] == "via-configure"
        assert len(env_path.read_text().splitlines()) == 1
    finally:
        monkeypatch.setattr(sink, "_configured", False)


def test_compact_appends_sink_snapshot(tmp_path, monkeypatch):
    """Core.compact is wired into the run-scoped sink: one labelled
    snapshot per compaction, with the compact.* spans populated."""
    import asyncio

    from tests.test_trace import make_opts
    from crdt_enc_tpu.backends import MemoryRemote
    from crdt_enc_tpu.core import Core

    path = tmp_path / "compact.jsonl"
    # before the configure: monkeypatch restores what it found, and a
    # setattr made after it would restore the configured sink
    monkeypatch.setattr(sink, "_configured", False)
    sink.configure(str(path))
    try:
        async def go():
            remote = MemoryRemote()
            w = await Core.open(make_opts(remote))
            for _ in range(3):
                await w.apply_ops([w.with_state(lambda s: s.inc(w.actor_id))])
            await w.compact()

        asyncio.run(go())
    finally:
        monkeypatch.setattr(sink, "_configured", False)
    rec = json.loads(path.read_text().splitlines()[-1])
    assert rec["label"] == "compact"
    for name in ("compact.ingest", "compact.seal", "compact.write",
                 "compact.gc"):
        assert name in rec["spans"], name
    assert rec["meta"]["gc_op_actors"] >= 1


# -------------------------------------------------------------- CLI + lint


def _write_run(tmp_path, label, seconds):
    record.observe("stream.fold", seconds)
    trace.add("ops_folded", 10)
    path = tmp_path / f"{label}.jsonl"
    sink.MetricsSink(str(path)).write(label)
    trace.reset()
    return path


def test_obs_report_report_and_diff(tmp_path, capsys):
    from crdt_enc_tpu.tools import obs_report

    a = _write_run(tmp_path, "old", 0.010)
    b = _write_run(tmp_path, "new", 0.030)
    assert obs_report.main(["report", str(a)]) == 0
    out = capsys.readouterr().out
    assert "stream.fold" in out and "p95" in out
    assert obs_report.main(["diff", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "stream.fold" in out and "+" in out
    # prometheus subcommand
    assert obs_report.main(["prom", str(b)]) == 0
    assert "crdt_span_seconds_total" in capsys.readouterr().out


def test_obs_report_export_trace_requires_events(tmp_path, capsys):
    from crdt_enc_tpu.tools import obs_report

    a = _write_run(tmp_path, "noevents", 0.010)
    rc = obs_report.main(
        ["export-trace", str(a), "-o", str(tmp_path / "t.json")]
    )
    assert rc == 2
    assert "no event log" in capsys.readouterr().err


# The span-name registry and thread-discipline lints moved into the
# static-analysis engine (rules SPN001/THR001); the tier-1 gate is now
# tests/test_static_analysis.py::test_live_repo_analysis_clean_within_budget
# (plus the shim exit-code tests there).


# ---- prometheus text-format escaping (ISSUE 11 satellite) -----------------


def test_prometheus_label_value_escaping_roundtrip():
    """Label values escape backslash, double-quote and newline per the
    text-format spec; a spec-compliant unescape recovers the original
    span name exactly."""
    weird = 'sp"an\\x\nend'
    snap = {
        "spans": {weird: {"count": 1, "seconds": 0.5}},
        "counters": {},
        "gauges": {},
    }
    prom = sink.to_prometheus(snap)
    line = next(
        ln for ln in prom.splitlines()
        if ln.startswith("crdt_span_count_total{")
    )
    # the rendered line is ONE physical line (the newline was escaped)
    assert "\n" not in line
    rendered = line[len('crdt_span_count_total{span="'):line.rindex('"')]
    assert rendered == 'sp\\"an\\\\x\\nend'
    unescaped = (
        rendered.replace("\\\\", "\x00").replace('\\"', '"')
        .replace("\\n", "\n").replace("\x00", "\\")
    )
    assert unescaped == weird


def test_prometheus_help_escaping(monkeypatch):
    """HELP text escapes backslash and newline (only those two, per the
    spec) — both for registry-derived and fallback help strings."""
    monkeypatch.setattr(
        sink, "registry_help", lambda: {"ops_folded": "a\\b\nc"}
    )
    snap = {"spans": {}, "counters": {"ops_folded": 1}, "gauges": {}}
    prom = sink.to_prometheus(snap)
    assert "# HELP crdt_ops_folded_total a\\\\b\\nc" in prom
    # fallback help for an unregistered name is escaped the same way
    snap = {"spans": {}, "counters": {}, "gauges": {"we\\ird": 1}}
    prom = sink.to_prometheus(snap)
    help_line = next(
        ln for ln in prom.splitlines() if ln.startswith("# HELP")
    )
    assert "we\\\\ird" in help_line


def test_prometheus_registry_help_single_escape():
    """The registry parse keeps raw text; escaping happens once at
    render time (a doc description containing a backslash must not
    double-escape)."""
    sink._help_cache = None
    try:
        help_ = sink.registry_help()
        # live-repo registry descriptions never pre-escape
        assert all("\\\\" not in v for v in help_.values())
    finally:
        sink._help_cache = None


def test_sink_rotation_concurrent_writers(tmp_path, monkeypatch):
    """N threads writing through CRDT_OBS_SINK_MAX_MB rotation: the
    size bound holds, every record lands in EXACTLY one generation
    (the limit allows at most one rotation for this workload — nothing
    is lost, nothing duplicated), and every surviving record parses
    under check_schema."""
    import threading

    path = tmp_path / "rot.jsonl"
    s = sink.MetricsSink(str(path))
    probe = len(json.dumps(s.write("probe-00"))) + 1
    n_threads, per_thread = 8, 6
    total = n_threads * per_thread + 1  # +1 for the probe record
    limit = probe * total  # > half the volume → at most ONE rotation
    monkeypatch.setenv("CRDT_OBS_SINK_MAX_MB", str(limit / 1e6))

    barrier = threading.Barrier(n_threads)

    def writer(i):
        barrier.wait()
        for k in range(per_thread):
            s.write(f"w-{i:03d}-{k:02d}")

    threads = [
        threading.Thread(target=writer, args=(i,))
        for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    labels = []
    for p in (path, tmp_path / "rot.jsonl.1"):
        if not p.exists():
            continue
        assert p.stat().st_size <= limit  # the bound held per generation
        records = sink.read_records(str(p))
        sink.check_schema(records, source=str(p))
        labels.extend(r["label"] for r in records)
    assert len(labels) == total  # nothing lost
    assert len(set(labels)) == total  # nothing written twice
