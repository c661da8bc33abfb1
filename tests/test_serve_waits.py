"""What a served tenant spends waiting and not working (ISSUE 34): the wait
for one of a phase's ``io_width`` slots is the span ``serve.slot_wait`` under
that phase, recorded only by a tenant that did wait; the two hand-offs around
a tenant's worker-thread job (its poll, its seal tail) are four counters, a
job that raises included.  Structure and counts only: no duration is compared
with another.
"""

from __future__ import annotations

import pytest

from test_serve import _fleet_storage, make_opts, run, write_orset

from crdt_enc_tpu.backends import FsStorage
from crdt_enc_tpu.core import Core
from crdt_enc_tpu.serve import FoldService, ServeConfig
from crdt_enc_tpu.utils import trace

TENANTS = 6
HANDOFFS = ("ingest_job_queue_us", "ingest_job_return_us",
            "seal_job_queue_us", "seal_job_return_us")


async def busy_fleet(root, io_width: int):
    """Six tenants over ``FsStorage`` (both ports offer sync twins), each
    with new op files, behind a service ``io_width`` wide."""
    cores = [
        await Core.open(make_opts(_fleet_storage(FsStorage, root, t, "s")))
        for t in range(TENANTS)
    ]
    for t in range(TENANTS):
        await write_orset(_fleet_storage(FsStorage, root, t, "w"), 8, b"m%d" % t)
    return cores, FoldService(cores, ServeConfig(io_width=io_width))


async def one_cycle(root, io_width: int, before_cycle=None):
    cores, service = await busy_fleet(root, io_width)
    if before_cycle is not None:
        before_cycle(cores)
    trace.reset()
    trace.enable_events()
    results = await service.run_cycle()
    service.close()
    snap, tree, events = trace.snapshot(), trace.tree(), trace.events()
    trace.reset()
    return results, snap, tree, events


def test_a_narrow_service_records_slot_waits_under_the_two_phases(tmp_path):
    results, snap, tree, events = run(one_cycle(tmp_path, io_width=2))
    assert all(r.sealed and r.error is None for r in results)
    waits = snap["spans"]["serve.slot_wait"]
    assert waits["parents"] == ["serve.phase.ingest", "serve.phase.seal"]
    # two tenants of six walk straight into each phase; the other four wait
    assert waits["count"] == 2 * (TENANTS - 2)
    assert "serve.slot_wait" in tree["serve.phase.ingest"]
    assert "serve.slot_wait" in tree["serve.phase.seal"]
    assert tree.get("serve.slot_wait") is None, "a wait has no children"
    # the phases stay what partitions the cycle
    assert "serve.slot_wait" not in tree["serve.cycle"]
    assert "serve.slot_wait" not in tree["serve.run_cycle"]
    by_id = {e["id"]: e for e in events if e["kind"] == "span"}
    waited = [e for e in by_id.values() if e["name"] == "serve.slot_wait"]
    for phase in ("serve.phase.ingest", "serve.phase.seal"):
        tenants = sorted(
            e["meta"] for e in waited if by_id[e["parent"]]["name"] == phase
        )
        assert tenants == list(range(2, TENANTS)), (phase, tenants)
    # a tenant's wait ends before its work in that phase starts
    for work, phase in (("serve.ingest", "serve.phase.ingest"),
                        ("serve.seal", "serve.phase.seal")):
        started = {e["meta"]: e["t0"] for e in by_id.values()
                   if e["name"] == work}
        for e in waited:
            if by_id[e["parent"]]["name"] == phase:
                assert e["t1"] <= started[e["meta"]]


def test_a_service_as_wide_as_its_fleet_records_no_slot_wait(tmp_path):
    results, snap, tree, _ = run(one_cycle(tmp_path, io_width=TENANTS))
    assert all(r.sealed and r.error is None for r in results)
    assert "serve.slot_wait" not in snap["spans"]
    assert snap["spans"]["serve.ingest"]["count"] == TENANTS
    assert snap["spans"]["serve.seal"]["count"] == TENANTS


@pytest.mark.parametrize("io_width", [2, TENANTS])
def test_every_job_counts_its_two_hand_offs(io_width, tmp_path):
    _, snap, _, _ = run(one_cycle(tmp_path, io_width=io_width))
    counted = snap["counters"]
    assert counted["ingest_jobs"] == TENANTS and counted["seal_jobs"] == TENANTS
    assert not counted.get("ingest_stepwise") and not counted.get("seal_stepwise")
    for name in HANDOFFS:
        assert counted[name] > 0, name
    # a hand-off is part of the span that holds the job open
    spans = snap["spans"]
    assert (counted["ingest_job_queue_us"] + counted["ingest_job_return_us"]
            <= 1e6 * spans["serve.ingest"]["seconds"])
    assert (counted["seal_job_queue_us"] + counted["seal_job_return_us"]
            <= 1e6 * spans["serve.seal"]["seconds"])


def test_a_job_that_raises_counts_its_hand_offs_too(tmp_path):
    """One tenant's poll raises inside its job, another's seal tail does:
    both jobs ran and came back, so both are in the four counters; a tap
    around the cycle sees exactly one add of each pair a job."""
    adds = {name: 0 for name in HANDOFFS}
    real_add_many = trace.add_many

    def counting(counts):
        for name in counts:
            if name in adds:
                adds[name] += 1
        real_add_many(counts)

    def break_two(cores):
        def no_ops(actor_first_versions):
            raise PermissionError("ops/ is not to be read (test)")

        def no_state(*args):
            raise PermissionError("states/ is not to be written (test)")

        cores[1].storage.load_ops_sync = no_ops
        cores[4].storage.store_state_sync = no_state

    trace.add_many = counting
    try:
        results, snap, _, _ = run(
            one_cycle(tmp_path, io_width=2, before_cycle=break_two)
        )
    finally:
        trace.add_many = real_add_many
    assert "PermissionError" in results[1].error and not results[1].sealed
    assert "PermissionError" in results[4].error and not results[4].sealed
    assert all(r.sealed for i, r in enumerate(results) if i not in (1, 4))
    counted = snap["counters"]
    assert counted["ingest_jobs"] == TENANTS
    # tenant 1 never reached its seal; tenant 4's tail raised inside its job
    assert counted["seal_jobs"] == TENANTS - 1
    assert adds == {"ingest_job_queue_us": TENANTS,
                    "ingest_job_return_us": TENANTS,
                    "seal_job_queue_us": TENANTS - 1,
                    "seal_job_return_us": TENANTS - 1}
    for name in HANDOFFS:
        assert counted[name] > 0, name


def test_a_solo_compact_counts_its_seal_job_and_adds_no_child_to_the_root(tmp_path):
    async def go():
        core = await write_orset(
            FsStorage(str(tmp_path / "l"), str(tmp_path / "r")), 10, b"solo"
        )
        trace.reset()
        await core.compact()
        return trace.snapshot(), trace.tree()

    snap, tree = run(go())
    trace.reset()
    assert snap["counters"]["seal_jobs"] == 1
    assert snap["counters"]["seal_job_queue_us"] > 0
    assert snap["counters"]["seal_job_return_us"] > 0
    assert "ingest_job_queue_us" not in snap["counters"]
    assert not any("job" in child or "wait" in child
                   for child in tree["core.compact"])
