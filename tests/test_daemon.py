"""Always-on fleet daemon (ISSUE 12): scheduler, backoff/quarantine,
breaker, admission, drain, crash/reopen.

The control-plane contract under test: compaction cadence is driven by
STALENESS (backlog/watermark), failing tenants isolate into capped
backoff and a quarantine ring instead of poisoning the cycle, a
whole-cycle outage trips the circuit breaker into honest degraded mode,
the fleet mutates (admit/evict) while running, and nothing the daemon
does — including being SIGKILL'd mid-flight — can diverge a tenant from
what a solo ``Core.compact()`` of the same remote produces.
"""

import asyncio
import copy
import json
import urllib.request

import pytest

from crdt_enc_tpu.backends import (
    FsStorage,
    IdentityCryptor,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.core import Core, OpenOptions, StaleWriterError, orset_adapter
from crdt_enc_tpu.models import canonical_bytes
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.serve import (
    AdmissionError,
    DaemonConfig,
    FleetDaemon,
    ServeConfig,
)
from crdt_enc_tpu.serve.daemon import ACTIVE, BACKOFF, QUARANTINED
from crdt_enc_tpu.utils import trace
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


def run(coro):
    return asyncio.run(coro)


def make_opts(storage, create=True, **kw):
    kw.setdefault("accelerator", TpuAccelerator(min_device_batch=1))
    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=create,
        **kw,
    )


async def seed_tenant(storage, n_ops, tag):
    """Populate a tenant remote with adds through a writer core."""
    core = await Core.open(make_opts(storage))
    for i in range(n_ops):
        m = b"%s-%d" % (tag, i % 13)
        await core.update(lambda s, m=m: s.add_ctx(core.actor_id, m))
    return core


class FlakyStorage(MemoryStorage):
    """Remote that refuses listings while ``broken`` — the transient
    storage-outage class the backoff machine exists for."""

    broken = False

    async def list_op_actors(self):
        if self.broken:
            raise OSError("injected outage")
        return await super().list_op_actors()


def quick_cfg(**kw):
    kw.setdefault("max_idle_cycles", 1)
    kw.setdefault("backoff_base", 1.0)
    kw.setdefault("backoff_cap", 2.0)
    kw.setdefault("backoff_jitter", 0.0)
    kw.setdefault("serve", ServeConfig(seal_empty=False))
    return DaemonConfig(**kw)


# ---------------------------------------------------------- scheduling


def test_scheduler_compacts_backlog_polls_quiet():
    """Staleness-driven cadence: a tenant with sealed-but-unfolded ops
    is selected and sealed; an in-sync tenant is only stat-polled (no
    seal attempt, no decrypt) until its idle cadence comes due."""

    async def scenario():
        busy_r, quiet_r = MemoryRemote(), MemoryRemote()
        await seed_tenant(MemoryStorage(busy_r), 20, b"busy")
        busy = await Core.open(make_opts(MemoryStorage(busy_r)))
        quiet = await Core.open(make_opts(MemoryStorage(quiet_r)))
        await quiet.compact()  # in sync: no backlog, no staleness
        daemon = FleetDaemon(
            [busy, quiet], quick_cfg(max_idle_cycles=100)
        )
        report = await daemon.run_cycle()
        assert "t0" in report["selected"]
        assert report["results"]["t0"]["outcome"] == "sealed"
        # never-sealed tenants are due once (unknown staleness); from
        # the second cycle the quiet tenant is poll-only
        report2 = await daemon.run_cycle()
        assert report2["selected"] == []
        assert report2["results"]["t0"]["outcome"] == "polled"
        assert report2["results"]["t1"]["outcome"] == "polled"
        # laggards jump the queue: new ops land on the busy tenant and
        # the next cycle selects exactly it
        w = await Core.open(make_opts(MemoryStorage(busy_r)))
        await w.update(lambda s: s.add_ctx(w.actor_id, b"late"))
        await daemon.run_cycle()  # poll refreshes the staleness inputs
        report3 = await daemon.run_cycle()
        assert report3["selected"] == ["t0"]
        await daemon.drain()

    run(scenario())


# ------------------------------------------- backoff/quarantine machine


def test_backoff_quarantine_and_recovery():
    """Consecutive failures walk active → backoff → quarantined; the
    ring re-probes on its cadence and a healed tenant returns to
    sealing.  Healthy tenants keep sealing throughout."""

    async def scenario():
        bad_r, ok_r = MemoryRemote(), MemoryRemote()
        await seed_tenant(FlakyStorage(bad_r), 15, b"bad")
        await seed_tenant(MemoryStorage(ok_r), 15, b"ok")
        bad_storage = FlakyStorage(bad_r)
        bad = await Core.open(make_opts(bad_storage))
        ok = await Core.open(make_opts(MemoryStorage(ok_r)))
        daemon = FleetDaemon(
            [bad, ok],
            quick_cfg(
                quarantine_after=2, quarantine_probe_every=2,
                backoff_base=2.0, backoff_cap=4.0,
            ),
        )
        bad_storage.broken = True
        trace.reset()
        await daemon.run_cycle()  # failure 1 → backoff
        t0 = daemon.entry("t0")
        assert t0.state == BACKOFF and t0.failures == 1
        assert t0.eligible_at > daemon.cycle
        await daemon.run_cycle()  # still backing off: not attempted
        assert t0.state == BACKOFF
        await daemon.run_cycle()  # re-probe → failure 2 → quarantine
        assert t0.state == QUARANTINED
        snap = trace.snapshot()
        assert snap["counters"]["daemon_backoffs"] >= 1
        assert snap["counters"]["daemon_quarantines"] == 1
        assert snap["gauges"]["daemon_quarantined"] == 1
        # the healthy tenant sealed in cycle 1 and stayed active
        assert daemon.entry("t1").state == ACTIVE
        assert daemon.entry("t1").last_sealed >= 1
        # heal → the ring's slow re-probe path recovers the tenant
        bad_storage.broken = False
        for _ in range(6):
            await daemon.run_cycle()
            if daemon.entry("t0").state == ACTIVE:
                break
        assert daemon.entry("t0").state == ACTIVE
        assert trace.snapshot()["gauges"]["daemon_quarantined"] == 0
        await daemon.drain()

    run(scenario())


def test_quarantine_probe_runs_even_when_not_due():
    """The ring's re-probe cadence is a guarantee: a quarantined tenant
    whose last status looks healthy (not _due, huge idle cadence) must
    still be attempted every ``quarantine_probe_every`` cycles — and
    recover once its storage heals."""

    async def scenario():
        remote = MemoryRemote()
        await seed_tenant(FlakyStorage(remote), 12, b"q")
        st = FlakyStorage(remote)
        core = await Core.open(make_opts(st))
        daemon = FleetDaemon(
            [core],
            quick_cfg(
                max_idle_cycles=1000, quarantine_after=2,
                quarantine_probe_every=2, backoff_cap=1.0,
            ),
        )
        await daemon.run_cycle()  # seals; status now healthy
        assert daemon.entry("t0").last_sealed == 1
        st.broken = True
        while daemon.entry("t0").state != QUARANTINED:
            await daemon.run_cycle()
            assert daemon.cycle < 10
        st.broken = False
        trace.reset()
        while daemon.entry("t0").state != ACTIVE:
            await daemon.run_cycle()
            assert daemon.cycle < 16, "quarantine probe never ran"
        assert trace.snapshot()["counters"]["daemon_probes"] >= 1
        await daemon.drain()

    run(scenario())


def test_circuit_breaker_degraded_and_half_open_recovery():
    """Whole-cycle failures trip the breaker: degraded mode seals
    nothing (no decrypt/decode attempts beyond the half-open probe),
    reports honestly, and closes again when the probe succeeds."""

    async def scenario():
        remotes = [MemoryRemote() for _ in range(2)]
        storages = []
        cores = []
        for r in remotes:
            await seed_tenant(FlakyStorage(r), 12, b"x")
            st = FlakyStorage(r)
            storages.append(st)
            cores.append(await Core.open(make_opts(st)))
        daemon = FleetDaemon(
            cores,
            quick_cfg(
                quarantine_after=2,  # the whole fleet parks while open
                breaker_after=2, breaker_probe_every=2,
                backoff_cap=1.0,
            ),
        )
        for st in storages:
            st.broken = True
        trace.reset()
        while not daemon.degraded:
            report = await daemon.run_cycle()
            assert daemon.cycle < 20, "breaker never tripped"
        assert trace.snapshot()["counters"]["daemon_breaker_trips"] == 1
        assert daemon.health()["degraded"] is True
        # drive the fleet fully into quarantine while degraded: the
        # half-open probe must still find a tenant to try
        while any(
            daemon.entry(t).state != QUARANTINED for t in daemon.tenant_ids
        ):
            await daemon.run_cycle()
            assert daemon.cycle < 30, "fleet never fully parked"
        # degraded: polls only (errors recorded, nothing sealed) until
        # the half-open probe; heal and let the probe close the breaker
        for st in storages:
            st.broken = False
        while daemon.degraded:
            report = await daemon.run_cycle()
            assert daemon.cycle < 30, "breaker never closed"
        assert any(
            r["outcome"] == "sealed" for r in report["results"].values()
        )
        h = daemon.health()
        assert h["degraded"] is False
        await daemon.drain()

    run(scenario())


# --------------------------------------------------- admission/eviction


def test_admission_budget_and_eviction_checkpoint():
    async def scenario():
        remote = MemoryRemote()
        await seed_tenant(MemoryStorage(remote), 25, b"adm")
        storage = MemoryStorage(remote)
        core = await Core.open(make_opts(storage))
        daemon = FleetDaemon([core], quick_cfg())
        # fleet-size gate
        daemon.config.max_tenants = 1
        extra = await Core.open(make_opts(MemoryStorage(MemoryRemote())))
        with pytest.raises(AdmissionError):
            await daemon.admit(extra)
        # byte-budget gate: per-tenant estimate past the warm budget
        daemon.config.max_tenants = 100
        daemon.config.admission_bytes = 1024
        daemon.config.tenant_cost_bytes = 4096
        with pytest.raises(AdmissionError):
            await daemon.admit(extra)
        daemon.config.admission_bytes = 0  # back to the warm budget
        tid = await daemon.admit(extra)
        assert daemon.entry(tid) is not None
        # duplicate tid is refused loudly
        with pytest.raises(AdmissionError):
            await daemon.admit(extra, tid=tid)
        await daemon.run_cycle()
        # eviction checkpoints and hands the core back; the next open
        # of that tenant is WARM
        got = await daemon.evict("t0")
        assert got is core
        assert daemon.entry("t0") is None
        reopened = await Core.open(make_opts(storage, create=False))
        assert reopened.opened_from_checkpoint, (
            reopened.checkpoint_fallback_reason
        )
        assert reopened.with_state(canonical_bytes) == core.with_state(
            canonical_bytes
        )
        with pytest.raises(KeyError):
            await daemon.evict("t0")
        await daemon.discard("t0")  # unknown tid: cleanup path, safe
        await daemon.drain()

    run(scenario())


def test_drain_is_terminal_and_idempotent():
    async def scenario():
        remote = MemoryRemote()
        await seed_tenant(MemoryStorage(remote), 10, b"dr")
        storage = MemoryStorage(remote)
        core = await Core.open(make_opts(storage))
        daemon = FleetDaemon([core], quick_cfg())
        await daemon.run_cycle()
        assert (await daemon.drain()) == {}
        assert daemon.state == "drained"
        assert daemon.service.closed
        # drained daemon: cycles and admissions refuse loudly, a second
        # drain is a no-op
        with pytest.raises(RuntimeError):
            await daemon.run_cycle()
        with pytest.raises(AdmissionError):
            await daemon.admit(core, tid="again")
        assert (await daemon.drain()) == {}
        # the drain checkpoint makes the tenant's next open warm
        reopened = await Core.open(make_opts(storage, create=False))
        assert reopened.opened_from_checkpoint

    run(scenario())


# ------------------------------------------------------------- healthz


def test_healthz_daemon_section():
    async def scenario():
        remote = MemoryRemote()
        await seed_tenant(MemoryStorage(remote), 10, b"hz")
        core = await Core.open(make_opts(MemoryStorage(remote)))
        daemon = FleetDaemon([core], quick_cfg(), live_port=0)
        try:
            await daemon.run_cycle()
            port = daemon.service.live.port
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5
            ) as resp:
                health = json.loads(resp.read())
            d = health["daemon"]
            assert d["state"] == "running"
            assert d["cycles"] == 1 and d["tenants"] == 1
            assert d["quarantined"] == 0 and d["degraded"] is False
            assert d["uptime_s"] >= 0
            assert d["last_cycle"]["selected"] == 1
        finally:
            await daemon.drain()
        assert daemon.health()["state"] == "drained"

    run(scenario())


# ------------------------------------------------- crash/reopen (kill)


@pytest.mark.parametrize("backend", ["memory", "fs"])
def test_sigkill_reopen_converges_warm_and_fsck_clean(backend, tmp_path):
    """Satellite 3: a daemon SIGKILL'd mid-flight (abandoned with no
    drain) loses nothing durable — every tenant reopens WARM from the
    cycle-sealed checkpoint, a post-reopen write mints fresh dots (the
    ``_ensure_own_history`` recovery contract), the fleet converges
    byte-identically with a cold oracle, and both backends' remotes
    fsck clean."""

    async def scenario():
        from crdt_enc_tpu.sim import DeterministicCryptor
        from crdt_enc_tpu.tools.fsck import fsck_remote

        def storage(i, tag):
            if backend == "memory":
                return MemoryStorage(remotes[i])
            return FsStorage(
                str(tmp_path / f"{tag}-{i}"), str(tmp_path / f"remote-{i}")
            )

        if backend == "memory":
            remotes = [MemoryRemote() for _ in range(3)]
        else:
            remotes = list(range(3))
        writers = [
            await seed_tenant(storage(i, "w"), 18, b"k%d" % i)
            for i in range(3)
        ]
        tenant_storages = [storage(i, "t") for i in range(3)]
        cores = [await Core.open(make_opts(st)) for st in tenant_storages]
        daemon = FleetDaemon(cores, quick_cfg())
        await daemon.run_cycle()  # seals snapshots + checkpoints
        # SIGKILL: no drain, no close — everything in memory abandoned
        del daemon, cores

        reopened = []
        for st in tenant_storages:
            c = await Core.open(make_opts(st, create=False))
            assert c.opened_from_checkpoint, c.checkpoint_fallback_reason
            reopened.append(c)
        # post-reopen writes go through the own-history guard and mint
        # fresh dots; a StaleWriterError here would be the documented
        # loud-transient (it must NOT corrupt) — with a healthy remote
        # it must simply succeed
        for i, c in enumerate(reopened):
            await c.update(
                lambda s, i=i: s.add_ctx(c.actor_id, b"post-kill-%d" % i)
            )
            await c.compact()
        for i, c in enumerate(reopened):
            cold = await Core.open(make_opts(storage(i, "cold")))
            await cold.read_remote()
            assert cold.with_state(canonical_bytes) == c.with_state(
                canonical_bytes
            ), f"tenant {i} diverged after kill/reopen"
            report = await fsck_remote(
                storage(i, "fsck"), DeterministicCryptor(f"k{i}"),
                PlainKeyCryptor(), deep=True,
            )
            assert report.ok, report.issues[:3]

    run(scenario())


def test_gc_orphan_dot_reuse_guard():
    """Regression for the simulator-discovered peer-GC blind spot
    (tests/data/sim/dot_reuse_gc_orphan.json): an op file a crashed
    incarnation stored but never recorded is folded AND GC'd by a peer
    before the author's first post-reopen write.  The author's own-tail
    probe finds nothing — the unread covering snapshot must force a
    re-read, so the next write mints a FRESH dot instead of reusing the
    spent one."""

    async def scenario():
        remote = MemoryRemote()
        storage = MemoryStorage(remote)
        w = await Core.open(make_opts(storage))
        for i in range(3):
            await w.update(
                lambda s, i=i: s.add_ctx(w.actor_id, b"m%d" % i)
            )
        await w.compact()  # snapshot + checkpoint; cursor v3
        # crash orphan: the op file lands, local meta/memory never learn
        blob = await w._seal([[0, b"orphan", [w.actor_id, 4]]])
        await w.storage.store_ops(w.actor_id, 4, blob)
        actor = w.actor_id
        # a peer folds the orphan and GCs it
        peer = await Core.open(make_opts(MemoryStorage(remote)))
        await peer.compact()
        assert await peer.storage.list_op_actors() == []  # orphan GC'd
        # the author reopens warm (cursor v3) and writes
        del w
        w2 = await Core.open(make_opts(storage, create=False))
        assert w2.opened_from_checkpoint
        await w2.update(lambda s: s.add_ctx(actor, b"fresh"))
        state = w2._data.state
        # dot 4 belongs to the orphan (folded via the peer's snapshot);
        # the new write must have minted dot 5
        assert state.clock.counters[actor] == 5
        assert state.entries[b"orphan"] == {actor: 4}
        assert state.entries[b"fresh"] == {actor: 5}
        cold = await Core.open(make_opts(MemoryStorage(remote)))
        await cold.read_remote()
        await w2.compact()
        await cold.read_remote()
        assert cold.with_state(canonical_bytes) == w2.with_state(
            canonical_bytes
        )

    run(scenario())


def test_vanished_history_refuses_write():
    """The fail-closed half of the guard: a replica with durable
    history facing a view where its merged snapshots vanished and no
    replacement is visible must refuse the write loudly
    (StaleWriterError), not mint possibly-spent dots."""

    class CensoredStorage(MemoryStorage):
        censor = False

        async def list_state_names(self):
            names = await super().list_state_names()
            return [] if self.censor else names

    async def scenario():
        remote = MemoryRemote()
        storage = CensoredStorage(remote)
        w = await Core.open(make_opts(storage))
        await w.update(lambda s: s.add_ctx(w.actor_id, b"a"))
        await w.compact()
        # a peer compacts: w's merged snapshot is GC'd, replaced by the
        # peer's — which the censored listing then hides
        peer = await Core.open(make_opts(MemoryStorage(remote)))
        await peer.update(lambda s: s.add_ctx(peer.actor_id, b"b"))
        await peer.compact()
        del w
        w2 = await Core.open(make_opts(storage, create=False))
        storage.censor = True
        with pytest.raises(StaleWriterError):
            await w2.update(lambda s: s.add_ctx(w2.actor_id, b"c"))
        # the refusal is transient: a repaired view writes normally
        storage.censor = False
        await w2.update(lambda s: s.add_ctx(w2.actor_id, b"c"))
        assert b"c" in w2._data.state.entries

    run(scenario())


# --------------------------------------------------- sim vocabulary


def test_sim_daemon_vocabulary_schedule_roundtrip():
    from crdt_enc_tpu.sim import Schedule, generate
    from crdt_enc_tpu.sim.faults import FaultConfig

    sched = generate(3, 4, 200, FaultConfig.none(), daemon=True)
    kinds = {s.kind for s in sched.steps}
    assert "daemon" in kinds
    assert sched.daemon
    again = Schedule.from_obj(sched.to_obj())
    assert again.daemon and [s.to_obj() for s in again.steps] == [
        s.to_obj() for s in sched.steps
    ]
    # the flag OFF preserves the pre-daemon RNG stream bit-for-bit
    plain = generate(3, 4, 200, FaultConfig.none())
    plain_flagged = generate(3, 4, 200, FaultConfig.none(), daemon=False)
    assert [s.to_obj() for s in plain.steps] == [
        s.to_obj() for s in plain_flagged.steps
    ]
    assert not any(
        s.kind in ("daemon", "ddrain") for s in plain.steps
    )


def test_sim_daemon_schedule_runs_clean():
    """A small no-fault daemon-vocabulary schedule runs a real
    FleetDaemon inside the simulator with zero violations and counted
    daemon cycles."""
    from crdt_enc_tpu.sim import Schedule, Step, run_schedule
    from crdt_enc_tpu.sim.faults import FaultConfig

    sched = Schedule(
        seed=11, n_replicas=3, daemon=True,
        steps=[
            Step("add", 0, 1), Step("add", 1, 2), Step("daemon"),
            Step("add", 2, 3), Step("daemon"), Step("crash", 1),
            Step("daemon"), Step("reopen", 1), Step("daemon"),
            Step("ddrain"), Step("add", 0, 4), Step("daemon"),
        ],
        faults=FaultConfig.none(),
    )
    result = run_schedule(sched)
    assert result.ok, result.violation
    assert result.daemon_cycles == 5


# ------------------------------------- step(), the pass run_forever makes
# (ISSUE 45: the benchmark's timed call is the code the process runs)


async def two_tenants(**cfg):
    """A daemon over one tenant with a backlog and one in sync."""
    busy_r = MemoryRemote()
    await seed_tenant(MemoryStorage(busy_r), 12, b"st")
    busy = await Core.open(make_opts(MemoryStorage(busy_r)))
    quiet = await Core.open(make_opts(MemoryStorage(MemoryRemote())))
    await quiet.compact()
    return FleetDaemon([busy, quiet], quick_cfg(**cfg))


@pytest.mark.parametrize("max_cycles", [1, 3])
def test_run_forever_is_the_loop_over_step(max_cycles):
    """Same reports, ``max_cycles`` kept, the last pass of a bounded run
    not paced, drain in ``finally``."""

    async def scenario():
        daemon = await two_tenants(interval_s=0.01, max_idle_cycles=100)
        passes, reports = [], []
        step = daemon.step

        async def watched(*, pace=True):
            passes.append(pace)
            reports.append(await step(pace=pace))
            assert daemon.last_cycle_report is reports[-1]
            return reports[-1]

        daemon.step = watched
        await daemon.run_forever(max_cycles=max_cycles)
        assert passes == [True] * (max_cycles - 1) + [False]
        assert [r["cycle"] for r in reports] == list(range(1, max_cycles + 1))
        assert reports[0]["results"]["t0"]["outcome"] == "sealed"
        assert daemon.cycle == max_cycles and daemon.state == "drained"

    run(scenario())


def test_step_survives_a_raising_cycle_and_run_forever_keeps_going():
    async def scenario():
        daemon = await two_tenants(interval_s=0.01, max_idle_cycles=100)
        cycle_locked, calls = daemon._cycle_locked, []

        async def flaky():
            calls.append(daemon.cycle)
            if len(calls) == 2:
                raise ValueError("injected")
            return await cycle_locked()

        daemon._cycle_locked = flaky
        assert (await daemon.step())["cycle"] == 1
        assert await daemon.step() is None  # logged and survived, paced
        await daemon.run_forever(max_cycles=4)  # cycles 3 and 4
        assert calls == [1, 2, 3, 4] and daemon.state == "drained"
        # a drained daemon's RuntimeError is raised, not survived
        with pytest.raises(RuntimeError):
            await daemon.step()

    run(scenario())


def test_step_paces_by_next_interval_and_a_drain_request_cuts_it_short():
    async def scenario():
        trace.reset()
        daemon = await two_tenants(interval_s=0.2, max_idle_cycles=100)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await daemon.step()
        assert loop.time() - t0 >= 0.2
        daemon.config.interval_s = 30.0
        loop.call_later(0.05, daemon.request_drain)
        t0 = loop.time()
        await daemon.step()
        assert loop.time() - t0 < 5.0
        pace = trace.snapshot()["spans"]["daemon.pace"]
        assert pace["count"] == 2 and pace["parents"] == [None]
        assert 0.2 <= pace["seconds"] < 5.2
        await daemon.drain()

    run(scenario())


@pytest.mark.parametrize("batch, due, selected, deferred, polled", [
    (256, 2, 2, 0, 0),  # first cycle: never-sealed tenants are all due
    (1, 2, 1, 1, 1),  # the cap leaves one due tenant out, and polls it
])
def test_select_span_and_the_four_counters(batch, due, selected, deferred,
                                           polled):
    """All four counters every cycle, 0 where that is the count; the
    scheduler's span first under ``daemon.cycle``."""

    async def scenario():
        trace.reset()
        daemon = await two_tenants(batch=batch, max_idle_cycles=100)
        await daemon.run_cycle()
        snap = trace.snapshot()
        got = {k: snap["counters"][k] for k in (
            "daemon_due", "daemon_selected", "daemon_deferred",
            "daemon_polled")}  # a KeyError here is "not counted"
        assert got == {"daemon_due": due, "daemon_selected": selected,
                       "daemon_deferred": deferred, "daemon_polled": polled}
        assert snap["spans"]["daemon.select"]["parents"] == ["daemon.cycle"]
        children = set(trace.tree()["daemon.cycle"])
        assert {"daemon.select", "serve.run_cycle"} <= children
        assert children <= {"daemon.select", "serve.run_cycle", "daemon.poll"}
        # a second cycle at the default cap: nothing due, both polled
        if batch == 256:
            await daemon.run_cycle()
            after = trace.snapshot()["counters"]
            assert after["daemon_due"] == 2 and after["daemon_deferred"] == 0
            assert after["daemon_polled"] == 2
            assert "daemon.poll" in trace.tree()["daemon.cycle"]
        await daemon.drain()

    run(scenario())


# ----------------------------------------------------- admission by shape


def stand_in(members: int, replicas: int):
    """A core as far as admission looks at it: a state of that shape."""
    import types

    from crdt_enc_tpu.models import ORSet

    state = ORSet()
    actors = [b"%016d" % r for r in range(replicas)]
    state.entries = {m: {a: 1 for a in actors[:1]} for m in range(members)}
    state.clock.counters.update({a: 1 for a in actors})
    return types.SimpleNamespace(_data=types.SimpleNamespace(state=state))


def admitted(cores, **cfg) -> int:
    """How many of ``cores`` a daemon with the CLI's configuration (and
    ``cfg`` over it) admits before it refuses one."""
    daemon = FleetDaemon([], DaemonConfig(interval_s=1.0, **cfg))
    try:
        for n, core in enumerate(cores):
            try:
                daemon._admit_locked(core, f"t{n}")
            except AdmissionError as e:
                assert "byte budget" in str(e)
                return n
        return len(cores)
    finally:
        daemon.service.close()


@pytest.mark.parametrize("cores, cfg, want", [
    # the benchmark's fleet: 64 x 4 cells in classes 64 x 8, 4,128 B each
    ([stand_in(64, 4)] * 1024, {}, 1024),
    # tenants that really are 1 MiB each (512 x 256 cells, 4 B, two planes
    # and the clock) are refused where the estimate refused them
    ([stand_in(512, 256)] * 300, {}, 255),
    # a mixed fleet is the sum of its tenants, not the newcomer's times n
    ([stand_in(512, 256)] * 200 + [stand_in(64, 4)] * 800, {}, 1000),
    # an empty state sits at the classes' floor
    ([stand_in(0, 0)] * 2000, {}, 2000),
    # a state that cannot say: the configured estimate, as before
    ([object()] * 300, {}, 256),
    ([object()] * 300, {"tenant_cost_bytes": 1 << 19}, 300),
    # an operator's own budget is in the estimate's unit, whatever the shape
    ([stand_in(64, 4)] * 300, {"admission_bytes": 100 << 20}, 100),
    ([stand_in(64, 4)] * 300,
     {"admission_bytes": 1 << 20, "tenant_cost_bytes": 4096}, 256),
])
def test_admission_by_shape(cores, cfg, want):
    assert admitted(cores, **cfg) == want


def test_admission_by_the_observed_mean_once_the_warm_tier_has_data():
    async def scenario():
        daemon = await two_tenants(max_idle_cycles=100)
        await daemon.run_cycle()
        warm = daemon.service.warm
        assert len(warm) == 1 and warm.bytes_held > 0
        mean = warm.bytes_held
        daemon.config.serve.warm_bytes = 3 * mean  # room for one more
        await daemon.admit(stand_in(512, 256))  # whatever its own shape
        with pytest.raises(AdmissionError, match=f"{mean}B this tenant"):
            await daemon.admit(stand_in(0, 0))
        await daemon.drain()

    run(scenario())


def test_eviction_gives_its_share_of_the_budget_back():
    async def scenario():
        big = stand_in(512, 256)  # 1 MiB and 1 KiB of planes
        daemon = FleetDaemon([big] * 255, DaemonConfig(interval_s=1.0))
        with pytest.raises(AdmissionError):
            await daemon.admit(big)
        await daemon.evict("t0", checkpoint=False)
        await daemon.discard("t1")
        await daemon.admit(big, tid="a")
        await daemon.admit(big, tid="b")
        with pytest.raises(AdmissionError):
            await daemon.admit(big, tid="c")
        daemon.service.close()

    run(scenario())
