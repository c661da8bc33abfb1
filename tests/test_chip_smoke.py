"""``chip_smoke.py``'s contract, as far as a CPU can show it, and the
compile-cache placement contract it relies on.

The smoke itself only proves anything on a TPU (the driver runs it
there); what tier-1 pins is that the script refuses without one, that
``--tiny`` pre-flights every phase's code on the CPU and says so, and
that ``enable_compilation_cache`` takes its directory from outside the
program."""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _env(**extra):
    env = os.environ.copy()
    # one device: the mesh phase then reports "skipped" (it has its own
    # differential tests on the virtual mesh) and the run stays short
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_tiny_preflight_runs_every_phase_on_cpu():
    r = subprocess.run(
        [sys.executable, _SMOKE, "--tiny"], env=_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    report, verdict = r.stdout.splitlines()
    # the last line is the verdict, with exactly the keys the driver reads
    verdict = json.loads(verdict)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert verdict["device"]["count"] == 1
    rec = json.loads(report)
    assert rec["ok"] is True and rec["chip"] is False and rec["tiny"] is True
    assert rec["device"] == verdict["device"]
    assert rec["claim"] is None
    assert rec["native"]["rebuilt_from_source"] is False  # tiny only
    assert rec["native"]["simd_lanes"] in (4, 8, 16)
    phases = rec["phases"]
    assert list(phases) == [
        "bulk_northstar", "bulk_device", "merge_northstar", "serve",
        "reads", "kernels", "mesh",
    ]
    assert all(p["ok"] for p in phases.values()), {
        k: v.get("error") for k, v in phases.items() if not v["ok"]
    }
    assert phases["mesh"]["skipped"] == "1 device"
    assert not rec["trimmed"] and not rec["phases_not_selected"]
    # the routing evidence the on-chip report is built from
    dev = phases["bulk_device"]
    assert dev["byte_identical"] is True
    assert [rd["route"]["session_mode"] for rd in dev["rounds"]] == [
        "buffer"] * 3
    assert all(rd["route"]["rows_device"] > 0 and
               rd["route"]["rows_host"] == 0 for rd in dev["rounds"])
    assert dev["rounds"][0]["h2d_bytes"] > 0
    assert dev["rounds"][-1]["jax_compiles"] == 0
    assert phases["serve"]["cycles"][-1]["jax_compiles"] == 0
    assert phases["reads"]["strong_no_policy"]["refused"] == "lag_exceeded"
    checks = phases["kernels"]["checks"]
    assert all(c["ok"] for c in checks.values())
    # off the chip the Pallas kernels can only run interpreted, and the
    # record says so by name — on the chip the same marker is an error
    assert any("interpreted" in k
               for c in checks.values() for k in c["pallas_traced"])


def _native_build_stamp():
    so = os.path.join(
        _REPO, "crdt_enc_tpu", "native", "build", "libcrdtnative.so")
    return os.stat(so).st_mtime_ns if os.path.exists(so) else None


def test_refuses_without_a_tpu_before_building_anything(tmp_path):
    """The default invocation under a pinned CPU platform exits non-zero
    with no result, before the native build or the JAX import."""
    before = _native_build_stamp()
    r = subprocess.run(
        [sys.executable, _SMOKE], env=_env(JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
    )
    assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
    assert r.stdout.strip() == ""
    assert "refusing" in r.stderr
    assert _native_build_stamp() == before  # nothing was rebuilt


def test_partial_run_cannot_report_ok(tmp_path):
    """``--phases`` is a builder's aid: an unselected or dependency-less
    phase is not ok, so a partial run can never pass for a whole one."""
    import argparse
    import asyncio

    import chip_smoke

    args = argparse.Namespace(seed=0, deadline=1e9)
    ctx = chip_smoke.Ctx(
        args, chip_smoke.TINY, False, None, None, str(tmp_path))
    phases = asyncio.run(chip_smoke.run_phases(ctx, ["reads"]))
    assert phases["reads"] == {
        "ok": False, "skipped": "needs bulk_northstar, bulk_device"}
    assert phases["bulk_northstar"] == {"ok": False,
                                        "skipped": "not selected"}
    assert not any(p["ok"] for p in phases.values())


_CACHE_PROBE = (
    "import jax, crdt_enc_tpu;"
    "p = crdt_enc_tpu.enable_compilation_cache();"
    "print(p); print(jax.config.jax_compilation_cache_dir)"
)


def _cache_probe(**env):
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE],
        env=_env(JAX_PLATFORMS="cpu", PYTHONPATH=_REPO, **env),
        capture_output=True, text=True, timeout=120, cwd="/",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_compile_cache_dir_contract(tmp_path, monkeypatch):
    """Set → JAX's own setting stands: the function returns it and never
    calls ``jax.config.update("jax_compilation_cache_dir", …)``.
    Unset → the fixed ``<checkout>/.jax_cache``, identical across two
    calls and two processes (no pid, time, home directory or XDG)."""
    d = str(tmp_path / "cache")
    want = os.path.join(_REPO, ".jax_cache")
    assert _cache_probe(JAX_COMPILATION_CACHE_DIR=d) == [d, d]
    assert _cache_probe(
        HOME="/nonexistent", XDG_CACHE_HOME="/nonexistent/xdg"
    ) == [want, want]

    import jax

    import crdt_enc_tpu

    updates = []
    real_update = jax.config.update
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val)),
    )
    assert crdt_enc_tpu.enable_compilation_cache() == d
    assert "jax_compilation_cache_dir" not in updates
    # unset: two calls here agree with each other and with the child
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        a = crdt_enc_tpu.enable_compilation_cache()
        b = crdt_enc_tpu.enable_compilation_cache()
        assert a == b == want
        assert updates.count("jax_compilation_cache_dir") == 2
    finally:
        real_update("jax_compilation_cache_dir", None)
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
