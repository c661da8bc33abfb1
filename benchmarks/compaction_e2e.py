"""True end-to-end compaction wall-clock over real encrypted files.

The BASELINE metric is "ops merged/sec + compaction wall-clock": this
harness measures the REAL thing — a populated remote directory of sealed
op files, then a fresh replica's ``open → read_remote → compact`` timed
wall-to-wall (listing, reading, decrypting, decoding, folding, sealing the
snapshot, GC), once with the host accelerator and once with the TPU
accelerator against byte-identical copies of the same remote.

Run:  python benchmarks/compaction_e2e.py [--files N] [--ops-per-file K]
Prints one JSON line: end-to-end ops/sec for both accelerators and the
speedup, plus a byte-equality check of the two compacted snapshots.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import time
import uuid
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


async def build_remote(root: Path, n_writers: int, files_per_writer: int,
                       ops_per_file: int, n_members: int) -> int:
    """Writers populate the shared remote through the real product path."""
    from crdt_enc_tpu.backends import FsStorage, PlainKeyCryptor, XChaChaCryptor
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    total = 0
    for w in range(n_writers):
        core = await Core.open(OpenOptions(
            storage=FsStorage(str(root / f"w{w}"), str(root / "remote")),
            cryptor=XChaChaCryptor(),
            key_cryptor=PlainKeyCryptor(),
            adapter=orset_adapter(),
            supported_data_versions=(DEFAULT_DATA_VERSION_1,),
            current_data_version=DEFAULT_DATA_VERSION_1,
            create=True,
        ))
        for _ in range(files_per_writer):
            def build(s, w=w):
                ops = []
                for j in range(ops_per_file):
                    m = (total + j * 7 + w) % n_members
                    if j % 9 == 8 and s.contains(m):
                        ops.append(s.rm_ctx(m))
                    else:
                        op = s.add_ctx(core.actor_id, m)
                        ops.append(op)
                    s.apply(ops[-1])
                return ops
            ops = await core.update(build)
            total += len(ops)
    return total


async def timed_compact(root: Path, remote: Path, accel) -> tuple[float, bytes]:
    from crdt_enc_tpu.backends import FsStorage, PlainKeyCryptor, XChaChaCryptor
    from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
    from crdt_enc_tpu.models import canonical_bytes
    from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1

    kw = {"accelerator": accel} if accel is not None else {}
    t0 = time.perf_counter()
    core = await Core.open(OpenOptions(
        storage=FsStorage(str(root), str(remote)),
        cryptor=XChaChaCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        **kw,
    ))
    await core.compact()
    wall = time.perf_counter() - t0
    return wall, core.with_state(canonical_bytes)


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--writers", type=int, default=32)
    ap.add_argument("--files", type=int, default=64, help="files per writer")
    ap.add_argument("--ops-per-file", type=int, default=48)
    ap.add_argument("--members", type=int, default=512)
    ap.add_argument("--build-into", help="(internal) build the remote under this dir and exit")
    ap.add_argument(
        "--skip-host", action="store_true",
        help="profiling mode: skip the (minutes-long at full scale) host "
        "compaction; byte equality is then cold==warm only",
    )
    ap.add_argument(
        "--compact-one", nargs=3, metavar=("LOCAL", "REMOTE", "ACCEL"),
        help="(internal) run one timed compaction (ACCEL: host|tpu) and print JSON",
    )
    args = ap.parse_args()

    if args.build_into:
        total = await build_remote(
            Path(args.build_into), args.writers, args.files,
            args.ops_per_file, args.members,
        )
        print(total)
        return

    if args.compact_one:
        import hashlib
        import os
        import resource

        import crdt_enc_tpu
        from crdt_enc_tpu.parallel import TpuAccelerator
        from crdt_enc_tpu.utils import trace

        crdt_enc_tpu.enable_compilation_cache()
        local, remote, kind = args.compact_one
        accel = TpuAccelerator() if kind == "tpu" else None
        profile = os.environ.get("COMPACT_PROFILE") == "1"
        if profile:
            trace.reset()
        wall, state_bytes = await timed_compact(Path(local), Path(remote), accel)
        rec = {
            "wall": wall,
            "rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024,
            "digest": hashlib.sha256(state_bytes).hexdigest(),
        }
        if profile:
            snap = trace.snapshot()
            rec["spans"] = {
                k: round(v["seconds"], 3)
                for k, v in sorted(snap["spans"].items())
            }
            rec["counters"] = snap["counters"]
            log(trace.report())
        print(json.dumps(rec))
        return

    base = Path(tempfile.mkdtemp(prefix="compact-e2e-"))
    log(f"building remote: {args.writers} writers x {args.files} files "
        f"x {args.ops_per_file} ops …")
    # the builder holds millions of live op objects — run it in a child so
    # this process's peak RSS measures the COMPACTIONS, not the synthesis
    import subprocess

    build = subprocess.run(
        [sys.executable, __file__, "--build-into", str(base),
         "--writers", str(args.writers), "--files", str(args.files),
         "--ops-per-file", str(args.ops_per_file),
         "--members", str(args.members)],
        capture_output=True, text=True,
    )
    if build.returncode != 0:
        log(build.stderr)
        raise RuntimeError("remote build failed")
    total = int(build.stdout.strip().splitlines()[-1])
    n_files = args.writers * args.files
    log(f"remote ready: {n_files} op files, {total} ops")

    # byte-identical remote copies: each compaction consumes (GCs) its
    # remote, so every measurement needs a fresh copy.  Each measurement
    # runs in its OWN child process so its peak RSS is its own — the TPU
    # pipelined ingest's bounded-memory claim is only checkable that way.
    # One process per chip holds: this parent never imports JAX, and the
    # children run one after another (subprocess.run blocks), so exactly
    # one process touches the chip at a time.
    # The TPU path runs twice — the first pays per-process jit tracing
    # (compiles come from the persistent cache) and warms it; the second
    # is the steady state a long-lived compactor sees.  Both are reported.
    remote_host = base / "remote"
    remote_tpu_cold = base / "remote-tpu-cold"
    remote_tpu_warm = base / "remote-tpu-warm"
    shutil.copytree(remote_host, remote_tpu_cold)
    shutil.copytree(remote_host, remote_tpu_warm)

    def compact_child(local: Path, remote: Path, kind: str) -> dict:
        r = subprocess.run(
            [sys.executable, __file__, "--compact-one", str(local),
             str(remote), kind],
            capture_output=True, text=True,
        )
        if r.returncode != 0:
            log(r.stderr)
            raise RuntimeError(f"{kind} compaction child failed")
        if os.environ.get("COMPACT_PROFILE") == "1":
            for ln in r.stderr.splitlines():  # the span table
                log(f"  [{kind}] {ln}")
        return json.loads(r.stdout.strip().splitlines()[-1])

    if args.skip_host:
        host = None
    else:
        host = compact_child(base / "reader-host", remote_host, "host")
        log(f"host compact: {host['wall']:.2f}s -> "
            f"{total / host['wall']:,.0f} ops/s e2e ({host['rss_mb']:.0f}MB)")
    cold = compact_child(base / "reader-tpu-cold", remote_tpu_cold, "tpu")
    log(f"tpu  compact (cold process): {cold['wall']:.2f}s")
    warm = compact_child(base / "reader-tpu", remote_tpu_warm, "tpu")
    log(f"tpu  compact (warm): {warm['wall']:.2f}s -> "
        f"{total / warm['wall']:,.0f} ops/s e2e ({warm['rss_mb']:.0f}MB)")

    equal = cold["digest"] == warm["digest"] and (
        host is None or host["digest"] == cold["digest"]
    )
    shutil.rmtree(base, ignore_errors=True)
    rec = {
        "metric": "compaction_e2e_ops_per_sec",
        "n_files": n_files,
        "n_ops": total,
        "tpu_wall_s": round(warm["wall"], 3),
        "tpu_cold_wall_s": round(cold["wall"], 3),
        "value": round(total / warm["wall"], 1),
        "unit": "ops/s",
        "byte_equal": bool(equal),
        "tpu_rss_mb": round(warm["rss_mb"], 1),
    }
    if host is not None:
        rec.update(
            host_wall_s=round(host["wall"], 3),
            vs_baseline=round(host["wall"] / warm["wall"], 2),
            host_rss_mb=round(host["rss_mb"], 1),
        )
    if "spans" in warm:
        rec["tpu_spans"] = warm["spans"]
    print(json.dumps(rec))


if __name__ == "__main__":
    asyncio.run(main())
