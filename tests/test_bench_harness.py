"""The bench harness itself is infrastructure worth pinning: one JSON
line on success, a diagnostic JSON + exit 3 when a TPU was expected and
the process finds none (a measurement path never falls back to the
CPU).  Runs bench.py as a real subprocess on tiny CPU shapes."""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_REPO, "bench.py")


def _env(**extra):
    env = os.environ.copy()
    env.update(extra)
    return env


def test_smoke_emits_one_json_line():
    r = subprocess.run(
        [sys.executable, _BENCH, "--smoke"],
        env=_env(
            JAX_PLATFORMS="cpu",
            BENCH_OPS="4000", BENCH_REPLICAS="64", BENCH_MEMBERS="32",
            BENCH_HOST_OPS="2000", BENCH_CHAIN="50", BENCH_ITERS="1",
        ),
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "orset_compaction_fold_ops_per_sec"
    assert rec["value"] > 0
    assert rec["unit"] == "ops/s"
    assert rec["backend"] == "cpu"
    assert rec["full_batch_equal"] is True
    assert rec["method"] in ("marginal_chain", "single_dispatch_upper_bound")


def test_multitenant_smoke_emits_one_json_line():
    """The ISSUE-7 bench end-to-end on a tiny CPU fleet: one JSON line,
    byte-identity asserted inside the run (a divergence exits 1)."""
    r = subprocess.run(
        [sys.executable, _BENCH, "--e2e-multitenant", "--smoke",
         "--tenants", "4"],
        env=_env(
            JAX_PLATFORMS="cpu", BENCH_LOCAL_DISABLE="1",
            BENCH_MT_OPS="48", BENCH_MT_OPF="12", BENCH_MT_MEMBERS="16",
        ),
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "orset_multitenant_agg_ops_per_sec"
    assert rec["value"] > 0
    assert rec["byte_identical"] is True
    assert rec["unit"] == "ops/s" and rec["vs_baseline"] > 0
    assert rec["fold_paths"].get("batched") == 4
    assert rec["warm_cycle"]["warm_hits"] == 4


def test_strong_read_smoke_emits_one_json_line():
    """The ISSUE-15 bench end-to-end on a tiny fleet: one JSON line,
    the final strong read oracle-compared inside the run (divergence
    exits 1)."""
    r = subprocess.run(
        [sys.executable, _BENCH, "--e2e-strong-read", "--smoke"],
        env=_env(JAX_PLATFORMS="cpu", BENCH_LOCAL_DISABLE="1"),
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "strong_read_e2e_reads_per_sec"
    assert rec["value"] > 0 and rec["unit"] == "reads/s"
    assert rec["byte_identical"] is True
    assert rec["reads_strong"] > 0
    assert rec["final_covered_versions"] == rec["total_ops"]
    assert "p99_ms" in rec["strong_ms"] and "p99_ms" in rec["eventual_ms"]
    assert rec["watermark_lag_versions"]["max"] >= 0


def test_delta_smoke_emits_one_json_line():
    """The ISSUE-10 bench end-to-end on a tiny CPU remote: one JSON
    line, byte-identity + chains-applied asserted inside the run (a
    divergence or an unused chain exits 1)."""
    r = subprocess.run(
        [sys.executable, _BENCH, "--e2e-delta", "--smoke"],
        env=_env(
            JAX_PLATFORMS="cpu", BENCH_LOCAL_DISABLE="1",
            BENCH_DELTA_OPS="3000", BENCH_DELTA_REPLICAS="40",
            BENCH_DELTA_MEMBERS="48", BENCH_DELTA_ROUNDS="2",
        ),
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    assert rec["metric"] == "orset_e2e_delta_bytes_reduction"
    assert rec["unit"] == "x"
    assert rec["byte_identical"] is True
    assert rec["deltas_applied"] == 2
    # the whole point: the incremental consumer reads far fewer bytes
    assert rec["value"] >= 5
    assert rec["bytes_read_delta_path"] < rec["bytes_read_snapshot_path"]


def test_unavailable_backend_emits_diagnostic_and_exit_3():
    # non-smoke + no TPU: bench.py initializes JAX in its own process,
    # finds a non-TPU default device, and must emit ONE diagnostic JSON
    # line and exit 3 before measuring anything.  JAX_PLATFORMS must be
    # emptied explicitly: the test conftest pins it to "cpu" in THIS
    # process, which would otherwise flow into the child and
    # legitimately select the CPU harness path.
    r = subprocess.run(
        [sys.executable, _BENCH],
        env=_env(
            JAX_PLATFORMS="",
            # a host with a directly attached TPU runs the real
            # benchmark: pin tiny shapes so that case stays bounded, and
            # never touch the committed evidence file
            BENCH_OPS="4000", BENCH_REPLICAS="64", BENCH_MEMBERS="32",
            BENCH_HOST_OPS="2000", BENCH_CHAIN="50", BENCH_ITERS="1",
            BENCH_LOCAL_DISABLE="1",
        ),
        capture_output=True, text=True, timeout=300,
    )
    if r.returncode == 0:
        import pytest

        pytest.skip("a real TPU is attached to this host — the "
                    "unavailable-backend path cannot be exercised here")
    assert r.returncode == 3, (r.returncode, r.stderr[-2000:])
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    assert rec["value"] is None
    assert rec["error"] == "tpu_backend_unavailable"
    assert rec["platform"] != "tpu" and rec["device_kind"]
    # no stand-in for a measurement is offered, and no probe history:
    # the check is one in-process look at the default device
    assert "last_good_local" not in rec and "attempts" not in rec


def test_bench_starts_no_process():
    """One process per chip: bench.py imports nothing that could start
    one (its JAX init is in-process, no child probe, no watchdog)."""
    import ast

    tree = ast.parse(open(_BENCH).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert not imported & {"subprocess", "multiprocessing", "threading"}
