"""Spans record the span that caused them (obs/record.py), and the transfer
counters count exact bytes where the transfer is issued.  All structural:
nothing here reads a time."""

import asyncio

import numpy as np
import pytest

from crdt_enc_tpu.models import ORSet
from crdt_enc_tpu.obs import timeline
from crdt_enc_tpu.parallel import TpuAccelerator
from crdt_enc_tpu.utils import trace

from test_plane_reuse import gen_ops, row_bytes


@pytest.fixture(autouse=True)
def clean_registry():
    trace.reset()
    yield
    trace.reset()


def parents(name):
    return trace.snapshot()["spans"][name]["parents"]


async def _await_inside():
    with trace.span("t.outer"):
        await asyncio.sleep(0)
        with trace.span("t.after_await"):
            pass


async def _thread_hop():
    def work():
        with trace.span("t.on_thread"):
            trace.observe("t.observed", 0.25)

    with trace.span("t.outer"):
        await asyncio.to_thread(work)


async def _interleaved_tasks():
    gate = asyncio.Event()

    async def task(name, first):
        with trace.span(name):
            if first:
                await gate.wait()  # the other task opens its span meanwhile
            else:
                gate.set()
                await asyncio.sleep(0)
            with trace.span("t.leaf." + name[-1]):
                pass

    with trace.span("t.outer"):
        await asyncio.gather(task("t.task.a", True), task("t.task.b", False))


@pytest.mark.parametrize("run, expected", [
    (_await_inside, {"t.outer": [None], "t.after_await": ["t.outer"]}),
    (_thread_hop, {"t.outer": [None], "t.on_thread": ["t.outer"],
                   "t.observed": ["t.on_thread"]}),
    (_interleaved_tasks, {"t.outer": [None], "t.task.a": ["t.outer"],
                          "t.task.b": ["t.outer"], "t.leaf.a": ["t.task.a"],
                          "t.leaf.b": ["t.task.b"]}),
], ids=["await", "to_thread", "interleaved_tasks"])
def test_parent_survives(run, expected):
    asyncio.run(run())
    assert {n: parents(n) for n in expected} == expected
    tree = trace.tree()
    assert tree[None] == ["t.outer"]
    for name, (parent,) in expected.items():
        assert name in tree[parent]


def test_a_name_seen_under_two_parents_lists_both_sorted_root_first():
    with trace.span("t.leaf"):
        pass
    for outer in ("t.b", "t.a"):
        with trace.span(outer):
            with trace.span("t.leaf"):
                pass
    assert parents("t.leaf") == [None, "t.a", "t.b"]
    assert trace.tree() == {None: ["t.a", "t.b", "t.leaf"],
                            "t.a": ["t.leaf"], "t.b": ["t.leaf"]}
    assert trace.snapshot()["spans"]["t.leaf"]["count"] == 3


def test_events_carry_id_and_parent_and_the_timeline_exports_them():
    trace.enable_events()
    with trace.span("t.outer"):
        with trace.span("t.inner", meta=3):
            pass
        trace.observe("t.observed", 0.5)
    by_name = {e["name"]: e for e in trace.events()}
    outer, inner, observed = (by_name[n] for n in
                              ("t.outer", "t.inner", "t.observed"))
    assert outer["parent"] is None
    assert inner["parent"] == outer["id"] == observed["parent"]
    assert len({outer["id"], inner["id"], observed["id"]}) == 3
    xs = {e["name"]: e["args"] for e in
          timeline.to_chrome_trace()["traceEvents"] if e["ph"] == "X"}
    assert xs["t.inner"] == {"chunk": 3, "id": inner["id"],
                             "parent": outer["id"]}
    assert xs["t.outer"] == {"id": outer["id"], "parent": None}


def test_reset_clears_parents():
    with trace.span("t.outer"):
        with trace.span("t.leaf"):
            pass
    trace.reset()
    assert trace.tree() == {}
    with trace.span("t.leaf"):
        pass
    assert parents("t.leaf") == [None]


def test_a_failing_annotation_leaves_no_span_open(monkeypatch):
    import jax.profiler

    class Refuses:
        def __init__(self, name):
            pass

        def __enter__(self):
            raise RuntimeError("no profiler session")

    monkeypatch.setattr(trace, "jax_annotations", True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refuses)
    with pytest.raises(RuntimeError):
        with trace.span("t.refused"):
            pass
    monkeypatch.setattr(trace, "jax_annotations", False)
    with trace.span("t.next"):
        pass
    assert parents("t.next") == [None]
    assert "t.refused" not in trace.snapshot()["spans"]


def test_exit_in_another_context_keeps_the_measurement_and_the_exception():
    import contextvars

    s = trace.span("t.moved")
    contextvars.copy_context().run(s.__enter__)
    with pytest.raises(KeyError):  # the body's exception, not the reset's
        try:
            raise KeyError("body")
        except KeyError as e:
            if not s.__exit__(type(e), e, e.__traceback__):
                raise
    assert trace.snapshot()["spans"]["t.moved"]["count"] == 1


def test_second_dense_fold_counts_exactly_its_columns_up_and_its_planes_back():
    """A dense fold that reuses cached planes uploads only its padded row
    columns and pulls the whole planes back: both counted, exactly."""
    accel = TpuAccelerator(min_device_batch=1)
    state, clock = ORSet(), {}
    accel.fold_ops(state, gen_ops(2000, 21, clock))
    cached = accel._plane_cache.planes
    trace.reset()
    ops = gen_ops(700, 22, clock)
    accel.fold_ops(state, ops)
    counters = trace.snapshot()["counters"]
    assert counters["h2d_bytes"] == row_bytes(len(ops))
    planes = accel._plane_cache.planes
    assert [p.shape for p in planes] == [p.shape for p in cached]
    assert counters["d2h_bytes"] == sum(
        int(np.prod(p.shape)) * p.dtype.itemsize for p in planes
    )
    assert counters["fold_rows_device"] == len(ops)


# --------------------------------------------- the seal tail's worker job
TAIL_SPANS = {
    "compact.seal", "compact.write", "delta.size", "delta.verify",
    "delta.verify.apply", "delta.verify.pack", "delta.seal", "compact.gc",
    "checkpoint.save",
}


@pytest.mark.parametrize("root", ["serve.seal", "core.compact"])
def test_seal_job_spans_keep_their_ancestor_across_the_thread(
    root, tmp_path, monkeypatch
):
    """A tenant's seal tail runs as one job on a worker thread; every span
    it records there still hangs under ``serve.seal`` (solo: under
    ``core.compact``), by name and event by event."""
    import threading

    from crdt_enc_tpu.obs import sink

    # a sink record would drain the event log this test reads
    monkeypatch.setattr(sink, "_configured", None)

    from test_serve import make_opts, write_orset

    from crdt_enc_tpu.backends import FsStorage
    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.serve import FoldService

    def storage(name):
        return FsStorage(str(tmp_path / name), str(tmp_path / "remote"))

    async def go():
        await write_orset(storage("w1"), 12, b"a")
        core = await Core.open(make_opts(storage("s")))
        service = FoldService([core])

        async def call():
            if root == "core.compact":
                return await core.compact()
            (res,) = await service.run_cycle()
            assert res.sealed

        await call()  # a base for the delta
        await write_orset(storage("w2"), 6, b"b")
        trace.reset()
        trace.enable_events()
        await call()
        service.close()
        return threading.get_ident(), trace.events()

    loop_tid, events = asyncio.run(go())
    assert trace.snapshot()["counters"].get("seal_jobs") == 1
    spans = {e["id"]: e for e in events if e["kind"] == "span"}
    on_worker = [
        e for e in spans.values()
        if e["tid"] != loop_tid and e["name"] in TAIL_SPANS
    ]
    assert {e["name"] for e in on_worker} == TAIL_SPANS
    assert len({e["tid"] for e in on_worker}) == 1, "one job, one thread"
    for e in on_worker:
        chain, at = [], e
        while at["parent"] is not None:
            at = spans[at["parent"]]
            chain.append(at["name"])
        assert root in chain, (e["name"], chain)
    direct = {
        name for name in TAIL_SPANS if not name.startswith("delta.verify.")
    }
    tree = trace.tree()
    assert direct <= set(tree[root])
