"""Run one cell: ``python -m cellbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout, on a machine that
holds the chip.

One process, one event loop.  Set-up builds the natives, takes the chip (or
exits non-zero: there is no CPU fallback), makes the whole run's ops from
``--seed``, lets the cell's driver open the program and take in the head, and
runs the mix's warm-up rounds so that every shape of the cell is compiled.
The window then repeats: publish the next batch (untimed: the other devices'
writes), time one call of the program, until ``--seconds`` of wall have
passed.  After the window the driver holds what the program produced against
the plain reference; ``correct`` is the conjunction of its checks, each printed
beside its limit: as the last lines of standard error and under the result
line's last key, ``compared``.

Standard output ends with the result line the benchmark's contract names; what
a reader may want besides goes to lines before it.  With ``--trace 1`` the
window runs under ``jax.profiler`` and the line carries the cell's per-layer
metrics, each read by its own reader from the program's spans and counters or
from the trace; with ``--trace 0`` it carries the end-to-end metrics.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time

from cellbench import gen, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = "cellbench"


def log(*a) -> None:
    print("cellbench:", *a, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def process_age() -> float:
    """Seconds since this process started, from the kernel's record of it;
    set-up is timed from there, so interpreter start and imports count."""
    with open("/proc/self/stat") as fh:
        start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
    boot = time.clock_gettime(time.CLOCK_BOOTTIME)
    return boot - start_ticks / os.sysconf("SC_CLK_TCK")


def load_cell(root: str, workload: str) -> dict:
    """Everything the manifest and the data files say about one cell."""
    manifest = load_json(root, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"cellbench: no workload {workload!r} in BENCHMARK.json")
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])

    def listed(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    layer = []
    for m in manifest["per_layer"]:
        if listed(m):
            spec = load_json(root, BENCH, "layer_metrics", m["name"] + ".json")
            layer.append({**spec, "name": m["name"], "unit": m["unit"]})
    return {
        "name": workload,
        "chips": entry["chips"],
        "cell": load_json(root, BENCH, "cells", workload + ".json"),
        "config": load_json(root, config_entry["file"]),
        "traffic": load_json(root, BENCH, "traffic", entry["traffic"] + ".json"),
        "end_to_end": [m for m in manifest["end_to_end"] if listed(m)],
        "per_layer": layer,
        "peaks": load_json(root, BENCH, "peaks.json"),
    }


def take_chip(chips: int, require_tpu: bool):
    """The devices of this run, or ``None`` where the cell cannot run here."""
    pinned = os.environ.get("JAX_PLATFORMS", "").lower().split(",")[0].strip()
    if require_tpu and pinned not in ("", "tpu"):
        log(f"JAX_PLATFORMS={pinned!r} pins another platform; a cell runs on a TPU")
        return None
    # both native libraries, from the committed sources, before JAX is
    # touched: the loaders run ``make`` (the only child of this process), and
    # a run on the Python fallbacks would not be a measurement
    from crdt_enc_tpu import native

    native.load()
    native.load_state()
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        log(f"needs {chips} TPU chip(s); found {len(devices)} x {devices[0].platform}")
        return None
    return devices


def compile_cache() -> str:
    """JAX's persistent cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with every
    program kept, however short its compile: a second run finds them all."""
    import jax

    import crdt_enc_tpu

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return crdt_enc_tpu.enable_compilation_cache()


def snapshot_delta(before: dict, after: dict) -> tuple:
    """What one call added to the program's spans and counters."""
    spans = {}
    for name, s in after["spans"].items():
        b = before["spans"].get(name, {"count": 0, "seconds": 0.0})
        if s["count"] > b["count"]:
            spans[name] = {"count": s["count"] - b["count"],
                           "seconds": s["seconds"] - b["seconds"]}
    counters = {
        k: v - before["counters"].get(k, 0)
        for k, v in after["counters"].items()
        if v != before["counters"].get(k, 0)
    }
    return spans, counters


def add_into(total: dict, part: dict) -> None:
    for k, v in part.items():
        if isinstance(v, dict):
            slot = total.setdefault(k, {"count": 0, "seconds": 0.0})
            slot["count"] += v["count"]
            slot["seconds"] += v["seconds"]
        else:
            total[k] = total.get(k, 0) + v


async def run_window(driver, seconds: float, first_round: int, n_rounds: int,
                     traced: bool, fault: str | None) -> dict:
    """The measured window.  Returns the timed calls and, when ``traced``,
    what they added to the program's spans and counters.  The ``fault``
    ``withhold_file`` keeps one op file of the first batch from the program."""
    import jax

    from crdt_enc_tpu.utils import trace

    calls, spans, counters = [], {}, {}
    t_end = time.perf_counter() + seconds
    r = first_round
    while time.perf_counter() < t_end and r < n_rounds:
        await driver.publish(
            r, withhold=fault == "withhold_file" and r == first_round
        )
        before = trace.snapshot() if traced else None
        with jax.profiler.TraceAnnotation(trace_reduce.CALL):
            t0 = time.perf_counter()
            outcome = await driver.call(r)
            wall = time.perf_counter() - t0
        if traced:
            s, c = snapshot_delta(before, trace.snapshot())
            add_into(spans, s)
            add_into(counters, c)
            top = sorted(s.items(), key=lambda kv: -kv[1]["seconds"])[:12]
            print(f"cellbench: call {len(calls)} took {1e3 * wall:.0f} ms; spans, ms:",
                  " ".join(f"{k}={1e3 * v['seconds']:.0f}" for k, v in top))
        calls.append({"round": r, "wall": wall, **outcome})
        r += 1
    if r >= n_rounds:
        log(f"the prepared batches ran out after {len(calls)} calls; the "
            "metrics are per call and stand (raise the mix's max_ops_per_s)")
    return {"calls": calls, "spans": spans, "counters": counters}


async def warm_worker_threads(obj) -> None:
    """Every worker thread of the loop's default executor packs ``obj`` once
    with the program's canonical packer, all ``cpu + 4`` of them held at a
    barrier so that each thread takes one.  What that warms is the packer's
    one output buffer on that thread (PR 23: a thread's first large pack was
    some ten times slower than its later ones).  What it does not warm is
    what the seal tail's ``delta.verify`` does off the loop, which builds a
    state's worth of small objects before it packs them: a thread's first
    such build was 1.4-1.8 s against 0.25 s later, under glibc's page-by-page
    growth of a new arena, and since PR 41 the program itself opens a
    thread's arena whole (``native.warm()``, ``M_TOP_PAD``).  The call stays
    because it is part of every cell's ``setup_s`` as measured."""
    from crdt_enc_tpu.utils import codec

    n = min(32, (os.cpu_count() or 1) + 4)  # ThreadPoolExecutor's own default
    barrier = threading.Barrier(n)

    def work():
        try:
            barrier.wait(timeout=60)  # holds each thread, so that all n start
        except threading.BrokenBarrierError:
            pass
        codec.pack(obj)

    await asyncio.gather(*(asyncio.to_thread(work) for _ in range(n)))


def layer_metrics(cell: dict, window: dict) -> dict:
    out = {}
    for spec in cell["per_layer"]:
        reader = importlib.import_module(f"cellbench.readers.{spec['reader']}")
        value = reader.read(window, spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


async def run_async(cell: dict, seed: int, seconds: float, traced: bool,
                    devices, workdir: str, started: float, fault: str | None,
                    keep_trace: str | None) -> dict:
    import jax

    from crdt_enc_tpu.obs import runtime as obs_runtime
    from crdt_enc_tpu.utils import trace

    obs_runtime.track_recompiles()
    config, traffic = cell["config"], cell["traffic"]
    warmup = traffic["warmup_rounds"]
    n_rounds = gen.rounds_for(traffic, config, seconds)
    plan = gen.plan_run(config, traffic, seed, n_rounds)
    log(f"set-up: {time.perf_counter() - started:.1f} s to the plan "
        f"({n_rounds} rounds, {len(plan.kind)} ops)")
    module = importlib.import_module(f"cellbench.drivers.{config['driver']}")
    driver = module.Driver(config, plan, workdir)
    profile_dir = os.path.join(workdir, "profile")
    try:
        await driver.open()
        log(f"set-up: {time.perf_counter() - started:.1f} s to the program open "
            "and the initial ops taken in")
        for r in range(warmup):
            await driver.publish(r)
            await driver.call(r)
        await warm_worker_threads(driver.warm_object())
        compiles = trace.snapshot()["counters"].get("jax_compiles", 0)
        if traced:
            # the program's spans reach the profiler's trace as annotations
            trace.jax_annotations = True
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(profile_dir, profiler_options=options)
        setup_s = time.perf_counter() - started
        try:
            window = await run_window(driver, seconds, warmup, n_rounds, traced, fault)
        finally:
            if traced:
                jax.profiler.stop_trace()
                trace.jax_annotations = False
        compiles = trace.snapshot()["counters"].get("jax_compiles", 0) - compiles
        calls = window["calls"]
        print(f"cellbench: calls completed in the window: {len(calls)}; "
              f"jax compiles inside it: {compiles}")
        print("cellbench: wall of each call, ms:",
              " ".join(f"{1e3 * c['wall']:.0f}" for c in calls))
        checks = await driver.check()
    finally:
        await driver.close()

    correct = bool(calls) and all(value <= limit for _, value, limit in checks)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
        ),
    }
    result = {
        "correct": correct,
        "attempted": sum(c["attempted"] for c in calls),
        "failed": sum(c["failed"] for c in calls),
    }
    if traced:
        flat = trace_reduce.load_xplane(trace_reduce.find_xplane(profile_dir))
        reduced = trace_reduce.reduce(flat)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            with open(os.path.join(keep_trace, cell["name"] + ".txt"), "w") as fh:
                fh.write(trace_reduce.describe(flat, top=40))
            with open(os.path.join(keep_trace, cell["name"] + ".json"), "w") as fh:
                json.dump(trace_reduce.sample(flat), fh)
        result["metrics"] = layer_metrics(cell, {
            "calls": len(calls),
            "ops": sum(c["ops"] for c in calls),
            "spans": window["spans"],
            "counters": window["counters"],
            "trace": flat,
            "shapes": [plan.round_shape(c["round"]) for c in calls],
            "peaks": cell["peaks"].get(device["kind"], {}),
        })
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {**(driver.end_to_end(calls) if calls else {}), "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"] if m["name"] in values
        }
    result["device"] = device
    # every number compared, beside its limit: the line's last key
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in checks}
    return result


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: str = ROOT, require_tpu: bool = True,
             shrink: dict | None = None, fault: str | None = None,
             keep_trace: str | None = None) -> int:
    """Run one cell and print its lines.  The keyword arguments exist for the
    tests and for ``cellbench.control``; the command passes none of them.
    ``shrink`` lays sizes over the configuration and the mix, ``fault`` breaks
    a guarantee on purpose, ``keep_trace`` names a directory for a readable
    summary of the profiler's trace."""
    started = time.perf_counter() - process_age()
    cell = load_cell(root, workload)
    if shrink:
        cell["config"] = {**cell["config"], **shrink.get("config", {})}
        cell["traffic"] = {**cell["traffic"], **shrink.get("traffic", {})}
    if not os.path.isdir(os.path.join(ROOT, "crdt_enc_tpu")):
        log("no program here: crdt_enc_tpu/ is missing beside cellbench/")
        return 2
    devices = take_chip(cell["chips"], require_tpu)
    if devices is None:
        return 2
    if require_tpu and devices[0].device_kind not in cell["peaks"]:
        log(f"no peaks recorded for device kind {devices[0].device_kind!r}; "
            "add it to cellbench/peaks.json with its source")
        return 2
    if require_tpu:
        log(f"compile cache: {compile_cache()}")
    workdir = tempfile.mkdtemp(prefix="cellbench-")
    try:
        result = asyncio.run(run_async(
            cell, seed, seconds, traced, devices[:cell["chips"]], workdir,
            started, fault, keep_trace,
        ))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    for name, c in result["compared"].items():
        log(f"check {name}: value {c['value']} limit {c['limit']} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    raise SystemExit(main())
