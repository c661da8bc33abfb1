"""Fault-injection tests for the crash-safety-by-ordering design.

The reference has no fault tests (SURVEY.md §5); its safety story is
structural — immutable ``create_new`` + fsync writes, content-addressed
names, store-new-before-delete-old (crdt-enc-tokio lib.rs:326-432, core
lib.rs:362-369, 653-661).  These tests *prove* the structure: a simulated
process death at every dangerous point between a durable write and its
follow-up must leave the remote in a state every replica still converges
from, and a re-run must clean up rather than corrupt.

``CrashStorage`` wraps a real backend and raises ``SimulatedCrash`` when a
named method is hit — before the call (the write never happened) or after
it (the write is durable but the caller's bookkeeping is lost), which is
exactly the fault model of a kill -9 between two syscalls.
"""

import asyncio

import pytest

from crdt_enc_tpu.backends import FsStorage, IdentityCryptor, PlainKeyCryptor
from crdt_enc_tpu.core import Core, OpenOptions, gcounter_adapter, orset_adapter
from crdt_enc_tpu.models import canonical_bytes
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


class SimulatedCrash(Exception):
    pass


class CrashStorage:
    """Delegate to ``inner``, but die at an injection point.

    ``crash_on``: method name; ``when``: "before" (call never runs) or
    "after" (call completes — its effects are durable — then we die);
    ``skip``: let that many calls through first.  The trap disarms after
    firing once, modelling a process that restarts and does not crash
    again at the same point.
    """

    def __init__(self, inner, crash_on: str, when: str = "before", skip: int = 0):
        assert when in ("before", "after")
        self._inner = inner
        self._crash_on = crash_on
        self._when = when
        self._remaining = skip
        self.armed = True

    def __getattr__(self, name):
        attr = getattr(self._inner, name)
        if name != self._crash_on or not callable(attr):
            return attr

        async def trapped(*args, **kwargs):
            if not self.armed:
                return await attr(*args, **kwargs)
            if self._remaining > 0:
                self._remaining -= 1
                return await attr(*args, **kwargs)
            self.armed = False
            if self._when == "before":
                raise SimulatedCrash(f"crash before {name}")
            result = await attr(*args, **kwargs)
            raise SimulatedCrash(f"crash after {name}")

        return trapped


def run(coro):
    return asyncio.run(coro)


def make_opts(storage, adapter, create=True):
    return OpenOptions(
        storage=storage,
        cryptor=IdentityCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter,
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=create,
    )


@pytest.fixture
def fs_factory(tmp_path):
    remote_dir = tmp_path / "remote"
    counter = iter(range(1000))
    return lambda: FsStorage(str(tmp_path / f"local{next(counter)}"), str(remote_dir))


async def _seed_orset(fs_factory):
    """One replica writes a few ops; returns its canonical state bytes."""
    c = await Core.open(make_opts(fs_factory(), orset_adapter()))
    for m in (b"a", b"b", b"c"):
        await c.update(lambda s, m=m: s.add_ctx(c.actor_id, m))
    await c.update(lambda s: s.rm_ctx(b"b"))
    return c.with_state(canonical_bytes)


def test_crash_between_snapshot_write_and_state_gc(fs_factory):
    """Die after the new snapshot is durable but before old states are
    removed: both snapshots remain; readers merge them (idempotent) and a
    re-run of compact finishes the GC."""

    async def go():
        await _seed_orset(fs_factory)
        # first compaction succeeds → one state file exists
        c1 = await Core.open(make_opts(fs_factory(), orset_adapter()))
        await c1.read_remote()
        await c1.compact()
        await c1.update(lambda s: s.add_ctx(c1.actor_id, b"d"))

        crashy = CrashStorage(fs_factory(), "remove_states", when="before")
        c2 = await Core.open(make_opts(crashy, orset_adapter()))
        with pytest.raises(SimulatedCrash):
            await c2.compact()

        # remote now holds the old snapshot, the new snapshot, and
        # possibly op files remove_ops didn't get to — every combination
        # must fold to the same state.  Two independent readers of the
        # dirty remote must agree byte-for-byte (not just on membership —
        # clocks and dots must survive the crash intact too).
        c3 = await Core.open(make_opts(fs_factory(), orset_adapter()))
        await c3.read_remote()
        assert c3.with_state(lambda s: s.members()) == [b"a", b"c", b"d"]
        c3b = await Core.open(make_opts(fs_factory(), orset_adapter()))
        await c3b.read_remote()
        assert c3.with_state(canonical_bytes) == c3b.with_state(canonical_bytes)
        # ...and byte-identically to the writer that survived
        await c1.read_remote()
        assert c1.with_state(canonical_bytes) == c3.with_state(canonical_bytes)

        # re-running compact on a fresh replica completes the GC
        await c3.compact()
        clean = fs_factory()
        assert len(await clean.list_state_names()) == 1
        assert await clean.list_op_actors() == []

    run(go())


def test_crash_between_snapshot_write_and_op_gc(fs_factory):
    """Die before op GC: the snapshot and the op files it covers coexist.
    Readers fold the snapshot first, then skip the already-covered op
    versions via the concurrent-read tolerance (lib.rs:521-525 semantics)."""

    async def go():
        await _seed_orset(fs_factory)
        crashy = CrashStorage(fs_factory(), "remove_ops", when="before")
        c1 = await Core.open(make_opts(crashy, orset_adapter()))
        with pytest.raises(SimulatedCrash):
            await c1.compact()

        c2 = await Core.open(make_opts(fs_factory(), orset_adapter()))
        await c2.read_remote()
        assert c2.with_state(lambda s: s.members()) == [b"a", b"c"]
        # both the snapshot and the covered ops are present right now
        dirty = fs_factory()
        assert len(await dirty.list_state_names()) == 1
        assert len(await dirty.list_op_actors()) == 1

        await c2.compact()
        clean = fs_factory()
        assert await clean.list_op_actors() == []
        assert len(await clean.list_state_names()) == 1

    run(go())


def test_crash_in_meta_rewrite_leaves_mergeable_metas(fs_factory):
    """Die between storing the rewritten remote-meta and deleting the
    superseded files: multiple meta files remain, and because RemoteMeta is
    a CRDT they merge on the next read — the key material survives."""

    async def go():
        c1 = await Core.open(make_opts(fs_factory(), gcounter_adapter()))
        key1 = c1._data.keys.latest_key()
        assert key1 is not None

        # second replica's open rewrites meta (its read-notify-store cycle);
        # crash it between store and delete
        crashy = CrashStorage(fs_factory(), "remove_remote_metas", when="before")
        try:
            await Core.open(make_opts(crashy, gcounter_adapter()))
        except SimulatedCrash:
            pass

        dirty = fs_factory()
        assert len(await dirty.list_remote_meta_names()) >= 1

        c3 = await Core.open(make_opts(fs_factory(), gcounter_adapter()))
        key3 = c3._data.keys.latest_key()
        assert key3 is not None
        assert key3.id == key1.id and key3.material == key1.material

    run(go())


def test_crash_after_op_write_before_cursor_update(fs_factory, tmp_path):
    """Die after the op file is durable but before the producer cursor is
    persisted: on restart the replica must (a) recover the op's effect via
    read_remote and (b) place its next write past the leaked file by
    collision probing — never clobber it."""

    async def go():
        local = str(tmp_path / "producer")
        remote = str(tmp_path / "remote")

        crashy = CrashStorage(
            FsStorage(local, remote), "store_local_meta", when="before",
            # skip the two open-time local-meta writes (replica
            # identity + the key-mint last_key_dot cursor) so the
            # crash lands on the producer-cursor persist in update
            skip=2
        )
        c1 = await Core.open(make_opts(crashy, gcounter_adapter()))
        actor = c1.actor_id
        with pytest.raises(SimulatedCrash):
            await c1.update(lambda s: s.inc(actor, 5))
        # the op file is durable; the cursor write never happened

        # restart the same replica (same local dir)
        c2 = await Core.open(
            make_opts(FsStorage(local, remote), gcounter_adapter(), create=False)
        )
        assert c2.actor_id == actor
        await c2.read_remote()  # recovers the leaked op's effect
        assert c2.with_state(lambda s: s.read()) == 5
        await c2.update(lambda s: s.inc(actor, 7))

        # an independent reader sees both increments, no gaps, no clobber
        c3 = await Core.open(
            make_opts(FsStorage(str(tmp_path / "reader"), remote), gcounter_adapter())
        )
        await c3.read_remote()
        assert c3.with_state(lambda s: s.read()) == 12

    run(go())


def test_restart_without_read_remote_probes_past_leaked_file(fs_factory, tmp_path):
    """Same fault as above, but the restarted replica writes immediately
    (no explicit read_remote): the durable cursor never recorded the
    leaked v1, so only storage can reveal it.  Since the dot-reuse fix
    (``Core._ensure_own_history``, simulator-discovered:
    tests/data/sim/dot_reuse_crash_reopen.json), the first write of an
    incarnation probes its own op tail, finds the orphan, and ingests
    it BEFORE deriving the new op — so the new op lands at v2 (never
    clobbering v1), carries a fresh dot (no overlap with the leaked
    op's), and the crashed increment survives: readers converge to
    5 + 7 = 12, not to a max-masked 7."""

    async def go():
        local = str(tmp_path / "producer")
        remote = str(tmp_path / "remote")

        crashy = CrashStorage(
            FsStorage(local, remote), "store_local_meta", when="before",
            # skip the two open-time local-meta writes (replica
            # identity + the key-mint last_key_dot cursor) so the
            # crash lands on the producer-cursor persist in update
            skip=2
        )
        c1 = await Core.open(make_opts(crashy, gcounter_adapter()))
        actor = c1.actor_id
        with pytest.raises(SimulatedCrash):
            await c1.update(lambda s: s.inc(actor, 5))

        c2 = await Core.open(
            make_opts(FsStorage(local, remote), gcounter_adapter(), create=False)
        )
        await c2.update(lambda s: s.inc(actor, 7))  # own-tail probe found v1
        assert c2.with_state(lambda s: s.read()) == 12

        # both op files exist: the leaked v1 was not clobbered
        dirty = FsStorage(str(tmp_path / "probe-local"), remote)
        files = await dirty.load_ops([(actor, 1)])
        assert [v for _, v, _ in files] == [1, 2]

        c3 = await Core.open(
            make_opts(FsStorage(str(tmp_path / "reader"), remote), gcounter_adapter())
        )
        await c3.read_remote()
        assert c3.with_state(lambda s: s.read()) == 12

    run(go())


def test_torn_tmp_files_are_invisible(fs_factory, tmp_path):
    """A crash mid-write leaves only ``.tmp-*`` files (tmp+fsync+link
    publish).  Listings, op scans, and opens must not see them."""

    async def go():
        await _seed_orset(fs_factory)
        remote = tmp_path / "remote"
        # simulate torn writes in every remote family (states/ may not exist
        # yet — no compaction has run — exactly like a crash mid-first-write)
        (remote / "states").mkdir(exist_ok=True)
        (remote / "states" / ".tmp-dead").write_bytes(b"\x00garbage")
        (remote / "meta" / ".tmp-dead").write_bytes(b"\x00garbage")
        ops_dirs = list((remote / "ops").iterdir())
        (ops_dirs[0] / ".tmp-dead").write_bytes(b"\x00garbage")

        c = await Core.open(make_opts(fs_factory(), orset_adapter()))
        await c.read_remote()
        assert c.with_state(lambda s: s.members()) == [b"a", b"c"]
        await c.compact()  # GC also tolerates the junk
        c2 = await Core.open(make_opts(fs_factory(), orset_adapter()))
        await c2.read_remote()
        assert c2.with_state(lambda s: s.members()) == [b"a", b"c"]

    run(go())


def test_interrupted_compact_is_idempotent_under_retry(fs_factory):
    """Run compact repeatedly with a crash at a different point each time;
    the remote must remain convergent throughout and end clean."""

    async def go():
        await _seed_orset(fs_factory)
        for point, when in [
            ("store_state", "before"),
            ("store_state", "after"),
            ("remove_states", "before"),
            ("remove_ops", "before"),
        ]:
            crashy = CrashStorage(fs_factory(), point, when=when)
            c = await Core.open(make_opts(crashy, orset_adapter()))
            with pytest.raises(SimulatedCrash):
                await c.compact()
            probe = await Core.open(make_opts(fs_factory(), orset_adapter()))
            await probe.read_remote()
            assert probe.with_state(lambda s: s.members()) == [b"a", b"c"]

        final = await Core.open(make_opts(fs_factory(), orset_adapter()))
        await final.compact()
        clean = fs_factory()
        assert len(await clean.list_state_names()) == 1
        assert await clean.list_op_actors() == []

    run(go())


# ------------------------------------- the seal tail's one worker job
# A failure at each step of the tail, with the tail run as one job (the
# ports' sync twins) and call by call on the loop (tests/_seal_drive.py):
# the same failure must leave the same remote, the same local files and the
# same bookkeeping, and in both the order holds.

_STEPS = [
    ("host", "encrypt:1"), ("host", "store_state"),
    ("host", "encrypt:2"), ("host", "store_delta"),
    ("host", "store_local_meta"), ("host", "remove_states"),
    ("host", "remove_ops"), ("host", "encrypt:3"),
    ("host", "store_local_checkpoint"), ("skipped", "remove_deltas"),
]


async def _fail_one_step(fleet, case, step):
    from _seal_drive import (
        FailingCryptor, Injected, add_members, bookkeeping, published,
        remove_members,
    )

    writer = await fleet.open("w")
    await add_members(writer, [b"a%d" % i for i in range(12)])
    cryptor = FailingCryptor("seal-drive")
    sealer = await fleet.open("s", cryptor=cryptor)
    await sealer.compact()  # snapshot only: the base of the next delta
    await add_members(writer, [b"b%d" % i for i in range(5)])
    if case == "skipped":
        await sealer.compact()  # delta v1, which the failing seal prunes
        await remove_members(
            writer, [b"a%d" % i for i in range(12)]
            + [b"b%d" % i for i in range(5)]
        )
    storage = fleet.inner["s"]
    before = await published(storage)
    if step.startswith("encrypt:"):
        cryptor.nth = int(step.split(":")[1])
    else:
        storage.fail = step
    with pytest.raises(Injected):
        await sealer.compact()
    storage.fail = None
    after = await published(storage)
    book = bookkeeping(sealer)
    checkpoint = (
        await sealer._open_sealed(after["checkpoint"])
        if after["checkpoint"] else None
    )
    # the retry cleans up, and a cold replica converges on it
    await sealer.compact()
    cold = await fleet.open("cold")
    await cold.read_remote()
    assert cold.with_state(canonical_bytes) == sealer.with_state(
        canonical_bytes
    ) == writer.with_state(canonical_bytes)
    return before, after, book, checkpoint


@pytest.mark.parametrize("case, step", _STEPS, ids=[s for _, s in _STEPS])
@pytest.mark.parametrize("kind", ["memory", "fs"])
def test_failed_seal_step_leaves_the_same_remote_in_both_drives(
    kind, case, step, tmp_path
):
    from _seal_drive import DRIVES, Fleet, failing, run_pinned
    from crdt_enc_tpu.backends import MemoryStorage

    cls = failing(MemoryStorage if kind == "memory" else FsStorage)
    seen = {}
    for drive in DRIVES:
        fleet = Fleet(kind, drive, tmp_path / drive, cls=cls)
        seen[drive] = run_pinned(lambda: _fail_one_step(fleet, case, step))
    before, after, book, checkpoint = seen["job"]
    assert (before, after, book, checkpoint) == seen["stepwise"]
    new_states = set(after["states"]) - set(before["states"])
    gone = (
        (set(before["ops"]) - set(after["ops"]))
        | (set(before["states"]) - set(after["states"]))
    )
    # the snapshot is durable before anything is removed
    assert not gone or new_states
    assert bool(new_states) == (step not in ("encrypt:1", "store_state"))
    assert bool(gone) == (step in (
        "remove_states", "remove_ops", "encrypt:3", "store_local_checkpoint",
    )), "GC runs after the delta and the local meta, and as a pair"
    # no delta without its snapshot
    new_deltas = set(after["deltas"]) - set(before["deltas"])
    assert not new_deltas or new_states
    assert bool(new_deltas) == (case == "host" and step not in (
        "encrypt:1", "store_state", "encrypt:2", "store_delta",
    ))
    # no checkpoint naming a snapshot that was never published (the one
    # that stands may name the snapshot this seal's GC has just collected)
    if checkpoint is not None and checkpoint.get(b"snap") is not None:
        assert bytes(checkpoint[b"snap"]).decode() in (
            set(before["states"]) | set(after["states"])
        )
    assert after["checkpoint"] == before["checkpoint"]
    # bookkeeping follows the steps that completed
    assert (book["local_meta"][b"last_delta"] > 0) == bool(
        new_deltas or case == "skipped"
    )


@pytest.mark.parametrize("step", ["store_state", "remove_ops",
                                  "store_local_checkpoint"])
@pytest.mark.parametrize("drive", ["job", "stepwise"])
def test_failed_seal_job_is_one_tenants_error(drive, step, tmp_path):
    """Through the service: the tenant whose job fails reports ``error``,
    the others seal."""
    from _seal_drive import Fleet, add_members, failing
    from crdt_enc_tpu.backends import MemoryStorage
    from crdt_enc_tpu.serve import FoldService
    from crdt_enc_tpu.utils import trace

    async def go():
        fleets = [
            Fleet("memory", drive, tmp_path / f"t{t}",
                  cls=failing(MemoryStorage))
            for t in range(3)
        ]
        for t, fleet in enumerate(fleets):
            await add_members(
                await fleet.open("w"), [b"t%d-%d" % (t, i) for i in range(9)]
            )
        served = [await fleet.open("s") for fleet in fleets]
        fleets[1].inner["s"].fail = step
        trace.reset()
        results = await FoldService(served).run_cycle()
        assert "Injected" in results[1].error and results[1].path == "error"
        assert not results[1].sealed
        assert results[0].sealed and results[2].sealed
        counted = trace.snapshot()["counters"]
        key = "seal_jobs" if drive == "job" else "seal_stepwise"
        assert counted.get(key) == 3 and counted.get("serve_tenant_errors") == 1

    run(go())
    from crdt_enc_tpu.utils import trace
    trace.reset()
