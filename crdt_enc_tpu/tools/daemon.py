"""Fleet daemon CLI (docs/GUIDE.md "Running the daemon").

    python -m crdt_enc_tpu.tools.daemon run \\
        --tenant /var/crdt/localA=/mnt/remoteA \\
        --tenant /var/crdt/localB=/mnt/remoteB \\
        [--port 9464] [--interval 1.0] [--cycles 0] [--deltas]

    python -m crdt_enc_tpu.tools.daemon selftest \\
        [--tenants 6] [--cycles 6] [--faulty 2] [--seed 0] \\
        [--mesh dp=8[,mp=M]]

``run`` opens one fs-backed :class:`~crdt_enc_tpu.core.Core` per
``--tenant LOCAL=REMOTE`` pair (XChaCha data cryptor, plain key wrap —
the bench stack), admits them into a
:class:`~crdt_enc_tpu.serve.FleetDaemon`, and runs the supervised loop
until SIGTERM/SIGINT, which drains gracefully: the in-flight cycle
finishes, every tenant seals a warm-open checkpoint, the live endpoint
stops.  ``--cycles N`` bounds the loop (smoke runs).  ``--port`` serves
``/metrics`` + ``/healthz`` (with the ``daemon`` control-plane section)
from the daemon's own live telemetry server.

``selftest`` is the CI smoke (tools/run_checks.sh): an in-memory fleet
with the PR-9 fault injector armed on some tenants runs N supervised
cycles — tenant errors must be isolated into backoff/quarantine while
healthy tenants keep sealing — then the faults heal, the fleet
recovers, the daemon drains, and every remote must fsck clean AND
refold (cold) byte-identical to the daemon's live tenant state.  Exit 0
on a clean pass, 1 on any failed expectation.  ``--mesh dp=N[,mp=M]``
runs the whole smoke through a MESH-backed service (the sharded
mega-folds of docs/multitenant.md) — on a CPU box export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first, the
virtual mesh the tier-1 differential tests use.

Exit codes: 0 clean, 1 failed expectation / fatal error, 2 usage.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
import sys

logger = logging.getLogger("crdt_enc_tpu.tools.daemon")


def _parse_mesh(spec: str | None):
    """``dp=N[,mp=M]`` → a (dp, mp) Mesh, or None when no spec.
    Exits 2 on malformed specs, degenerate (size < 2) meshes, or too
    few devices (usage errors) — the shared ``parse_mesh_spec``
    validation, so ``--mesh dp=1`` can never silently smoke the
    UNsharded path while claiming mesh coverage."""
    if not spec:
        return None
    from ..parallel.mesh import parse_mesh_spec

    try:
        dp, mp = parse_mesh_spec(spec)
    except ValueError as e:
        print(f"--mesh: {e} (got {spec!r})", file=sys.stderr)
        raise SystemExit(2)
    import jax

    from ..parallel.mesh import make_mesh

    if len(jax.devices()) < dp * mp:
        print(
            f"--mesh dp={dp},mp={mp} needs {dp * mp} devices, found "
            f"{len(jax.devices())}; on a CPU box set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return make_mesh((dp, mp))


def _open_opts(storage, *, create: bool, deltas: bool, identity: bool = False):
    from ..backends import PlainKeyCryptor, XChaChaCryptor
    from ..backends.identity_crypto import IdentityCryptor
    from ..core import OpenOptions, orset_adapter
    from ..parallel import TpuAccelerator
    from ..utils.versions import DEFAULT_DATA_VERSION_1

    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor() if identity else XChaChaCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=create,
        accelerator=TpuAccelerator(min_device_batch=1),
        delta=deltas,
    )


# ---------------------------------------------------------------- run
async def _run(args) -> int:
    from ..backends import FsStorage
    from ..core import Core
    from ..serve import DaemonConfig, FleetDaemon

    pairs = []
    for spec in args.tenant:
        local, sep, remote = spec.partition("=")
        if not sep or not local or not remote:
            print(f"--tenant wants LOCAL=REMOTE, got {spec!r}",
                  file=sys.stderr)
            return 2
        pairs.append((local, remote))
    if not pairs:
        print("run: at least one --tenant LOCAL=REMOTE required",
              file=sys.stderr)
        return 2

    cores = [
        await Core.open(_open_opts(
            FsStorage(local, remote), create=True, deltas=args.deltas,
        ))
        for local, remote in pairs
    ]
    cfg = DaemonConfig(interval_s=args.interval)
    daemon = FleetDaemon(cores, cfg, live_port=args.port)
    if daemon.service.live is not None:
        print(f"live telemetry on :{daemon.service.live.port} "
              "(/metrics /healthz /snapshot)", file=sys.stderr)

    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, daemon.request_drain)
        except NotImplementedError:  # non-unix
            pass
    await daemon.run_forever(max_cycles=args.cycles)
    h = daemon.health()
    print(
        f"drained after {h['cycles']} cycle(s): {h['tenants']} tenant(s), "
        f"{h['quarantined']} quarantined, degraded={h['degraded']}",
        file=sys.stderr,
    )
    return 0


def _cmd_run(args) -> int:
    return asyncio.run(_run(args))


# ----------------------------------------------------------- selftest
async def _selftest(args) -> int:
    from ..backends import MemoryRemote, MemoryStorage, PlainKeyCryptor
    from ..core import Core
    from ..models import canonical_bytes
    from ..serve import DaemonConfig, FleetDaemon, ServeConfig
    from ..sim import DeterministicCryptor, FaultConfig, FaultyStorage
    from ..tools.fsck import fsck_remote

    class _FlakyStorage:
        """Deterministic outage: tenant 0's remote refuses listings
        while ``broken`` — the guaranteed-error half of the smoke (the
        seeded FaultyStorage half exercises the survivable damage
        classes, whose tenant-level escalation is probabilistic)."""

        def __init__(self, inner):
            self._inner = inner
            self.broken = False

        def __getattr__(self, name):
            return getattr(self._inner, name)

        async def list_op_actors(self):
            if self.broken:
                raise OSError("selftest: remote unreachable")
            return await self._inner.list_op_actors()

    T, faulty = args.tenants, min(1 + args.faulty, args.tenants)
    remotes = [MemoryRemote() for _ in range(T)]
    cores = []
    wrappers = []
    flaky = None
    for t, remote in enumerate(remotes):
        writer = await Core.open(_open_opts(
            MemoryStorage(remote), create=True, deltas=True, identity=True,
        ))
        for i in range(24):
            m = b"t%d-%d" % (t, i % 11)
            await writer.update(
                lambda s, m=m: s.add_ctx(writer.actor_id, m)
            )
        storage = MemoryStorage(remote)
        if t == 0:
            storage = flaky = _FlakyStorage(storage)
        elif t < faulty:
            storage = FaultyStorage(
                storage, FaultConfig.all_faults(),
                seed=args.seed, name=f"t{t}",
            )
            storage.heal()  # open clean; arm once admitted
            wrappers.append(storage)
        cores.append(await Core.open(_open_opts(
            storage, create=True, deltas=True, identity=True,
        )))

    cfg = DaemonConfig(
        interval_s=0.0, max_idle_cycles=1, quarantine_after=2,
        quarantine_probe_every=3, backoff_base=1.0, backoff_cap=2.0,
        breaker_after=T + 1, serve=ServeConfig(seal_empty=False),
    )
    daemon = FleetDaemon(
        cores, cfg, seed=args.seed, mesh=_parse_mesh(args.mesh)
    )
    for w in wrappers:
        w.arm()
    flaky.broken = True

    failures: list[str] = []
    for _ in range(args.cycles):
        report = await daemon.run_cycle()
        h = daemon.health()
        print(
            f"cycle {report['cycle']}: selected={len(report['selected'])} "
            f"errors={h['last_cycle']['errors']} backoff={h['backoff']} "
            f"quarantined={h['quarantined']}", file=sys.stderr,
        )
    # isolation checks: the flaky tenant must have failed into the
    # backoff/quarantine machine, and every HEALTHY tenant must have
    # kept sealing through the fault phase — tenant failures never
    # poison the cycle
    t0 = daemon.entry("t0")
    if t0.failures == 0 and t0.state == "active":
        failures.append("flaky tenant t0 never entered backoff/quarantine")
    for t in range(faulty, T):
        entry = daemon.entry(f"t{t}")
        if entry is None or entry.last_sealed < 0:
            failures.append(
                f"healthy tenant t{t} never sealed while peers faulted"
            )

    # heal: the transient faults clear, the backoff re-probe path must
    # bring every tenant back to sealing
    flaky.broken = False
    for w in wrappers:
        w.heal()
    for _ in range(max(6, 2 * cfg.quarantine_probe_every)):
        await daemon.run_cycle()
        if all(
            daemon.entry(tid).state == "active"
            and daemon.entry(tid).last_sealed >= 0
            for tid in daemon.tenant_ids
        ):
            break
    else:
        failures.append("fleet did not recover to all-active after heal")

    await daemon.drain()
    if daemon.state != "drained":
        failures.append(f"drain left state {daemon.state!r}")

    # post-drain audit: every remote fscks clean and refolds cold to the
    # daemon tenant's live state (the no-divergence oracle)
    for t, (core, remote) in enumerate(zip(cores, remotes)):
        report = await fsck_remote(
            MemoryStorage(remote), DeterministicCryptor(f"selftest{t}"),
            PlainKeyCryptor(), deep=True,
        )
        if not report.ok:
            failures.append(f"tenant {t}: fsck errors: {report.issues[:3]}")
        cold = await Core.open(_open_opts(
            MemoryStorage(remote), create=True, deltas=False, identity=True,
        ))
        await cold.read_remote()
        if cold.with_state(canonical_bytes) != core.with_state(
            canonical_bytes
        ):
            failures.append(f"tenant {t}: cold refold diverges from daemon")

    for line in failures:
        print(f"SELFTEST FAIL: {line}", file=sys.stderr)
    if not failures:
        print(
            f"selftest OK: {T} tenants ({faulty} faulted), "
            f"{daemon.cycle} cycles, drained, fsck clean",
            file=sys.stderr,
        )
    return 1 if failures else 0


def _cmd_selftest(args) -> int:
    return asyncio.run(_selftest(args))


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    ap = argparse.ArgumentParser(
        prog="python -m crdt_enc_tpu.tools.daemon", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_run = sub.add_parser("run", help="run a fleet daemon over fs remotes")
    p_run.add_argument(
        "--tenant", action="append", default=[], metavar="LOCAL=REMOTE",
        help="one tenant's local dir + remote dir (repeatable)",
    )
    p_run.add_argument("--port", type=int, default=None,
                       help="live telemetry port (0 = ephemeral)")
    p_run.add_argument("--interval", type=float, default=1.0,
                       help="seconds between supervised cycles")
    p_run.add_argument("--cycles", type=int, default=0,
                       help="stop after N cycles (0 = run until SIGTERM)")
    p_run.add_argument("--deltas", action="store_true",
                       help="delta-state replication on every tenant")
    p_run.set_defaults(fn=_cmd_run)

    p_st = sub.add_parser(
        "selftest", help="bounded in-memory smoke with injected faults"
    )
    p_st.add_argument("--tenants", type=int, default=6)
    p_st.add_argument("--cycles", type=int, default=6)
    p_st.add_argument("--faulty", type=int, default=2,
                      help="tenants wrapped in the all-fault injector")
    p_st.add_argument("--seed", type=int, default=0)
    p_st.add_argument("--mesh", default=None, metavar="dp=N[,mp=M]",
                      help="run the smoke through a mesh-backed service")
    p_st.set_defaults(fn=_cmd_selftest)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
