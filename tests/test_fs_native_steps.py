"""The file steps of ``FsStorage`` run in ``native/io.cpp`` (one call a
step) or, on any surprise, in the Python helpers.  Every case here runs
both ways, through the ``*_sync`` twins where the native step sits, and is
held to ONE expectation: the tree left behind (names and bytes) and the
exception raised.  ``python`` is what a machine without a toolchain runs:
the loader raises, nothing else is switched."""

import os
import threading

import pytest

from crdt_enc_tpu import native
from crdt_enc_tpu.backends import fs as fs_mod
from crdt_enc_tpu.backends.fs import FsStorage
from crdt_enc_tpu.backends.memory import content_name
from crdt_enc_tpu.utils import trace

A, B, C = (bytes([i]) * 16 for i in (1, 2, 3))


def no_toolchain(monkeypatch) -> None:
    """What a machine without a compiler sees: the loader raises."""
    def load():
        raise RuntimeError("native build failed (test)")

    monkeypatch.setattr(native, "load", load)
    monkeypatch.setattr(fs_mod, "_warned_native_scan", True)  # quiet


@pytest.fixture(params=["native", "python"])
def mode(request, monkeypatch):
    if request.param == "python":
        no_toolchain(monkeypatch)
    else:
        native.load()  # a toolchain is part of the test environment
    trace.reset()
    yield request.param
    trace.reset()


def tree(root) -> dict:
    """relative path -> bytes, and ``dir/`` -> None for an empty one."""
    out = {}
    for d, dirs, files in os.walk(root):
        rel = os.path.relpath(d, root)
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.normpath(os.path.join(rel, f))] = fh.read()
        if not dirs and not files:
            out[rel + "/"] = None
    return out


def put(root, rel: str, data: bytes = b"x") -> None:
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as fh:
        fh.write(data)


def steps() -> tuple:
    counted = trace.snapshot()["counters"]
    return counted.get("fs_steps_native", 0), counted.get("fs_steps_python", 0)


# each case: (storage, root) -> what it expects of the tree; raises what it
# raises.


def publish_fresh(s, root):
    name = s.store_state_sync(b"snapshot")
    s.store_delta_sync(A, 1, b"delta")
    assert name == content_name(b"snapshot")
    return {f"r/states/{name}": b"snapshot", f"r/deltas/{A.hex()}/1": b"delta"}


def identical_replay(s, root):
    name = s.store_state_sync(b"snapshot")
    assert s.store_state_sync(b"snapshot") == name
    s.store_delta_sync(A, 1, b"delta")
    s.store_delta_sync(A, 1, b"delta")
    return {f"r/states/{name}": b"snapshot", f"r/deltas/{A.hex()}/1": b"delta"}


def ca_name_holds_other_content(s, root):
    put(root, f"r/states/{content_name(b'snapshot')}", b"not the snapshot")
    s.store_state_sync(b"snapshot")


def versioned_name_taken(s, root):
    s.store_delta_sync(A, 1, b"first")
    s.store_delta_sync(A, 1, b"second")


def missing_directories_created(s, root):
    assert not os.path.exists(os.path.join(root, "r"))
    name = s.store_state_sync(b"s")
    s.store_delta_sync(B, 7, b"d")
    s.store_local_meta_sync(b"m")
    s.store_local_checkpoint_sync(b"c")
    return {
        f"r/states/{name}": b"s", f"r/deltas/{B.hex()}/7": b"d",
        "l/meta-data.msgpack": b"m", "l/checkpoint.msgpack": b"c",
    }


def atomic_overwrite(s, root):
    for data in (b"one", b"two, longer", b"3"):
        s.store_local_meta_sync(data)
        s.store_local_checkpoint_sync(data + b"!")
    return {"l/meta-data.msgpack": b"3", "l/checkpoint.msgpack": b"3!"}


def remove_prefix_leaves_the_rest(s, root):
    d = f"r/ops/{A.hex()}"
    for n in ("1", "2", "3", "5", ".tmp-inflight", "junk", "007"):
        put(root, f"{d}/{n}", n.encode())
    s.remove_ops_sync([(A, 3)])
    return {f"{d}/5": b"5", f"{d}/.tmp-inflight": b".tmp-inflight",
            f"{d}/junk": b"junk", f"{d}/007": b"007"}


def absent_actor_directory(s, root):
    put(root, f"r/deltas/{B.hex()}/4")
    s.remove_deltas_sync([(A, 9), (B, 3), (C, 1)])
    s.remove_ops_sync([(A, 9)])  # no ops directory at all
    s.remove_states_sync(["NOSUCHNAME"])  # no states directory either
    return {f"r/deltas/{B.hex()}/4": b"x"}


def emptied_directory_removed(s, root):
    for n in ("1", "2"):
        put(root, f"r/ops/{A.hex()}/{n}")
        put(root, f"r/deltas/{B.hex()}/{n}")
    s.remove_ops_sync([(A, 2)])
    s.remove_deltas_sync([(B, 5)])
    return {"r/ops/": None, "r/deltas/": None}


def non_empty_directory_kept(s, root):
    for n in ("1", "2"):
        put(root, f"r/ops/{A.hex()}/{n}")
    s.remove_ops_sync([(A, 1), (B, 1)])
    return {f"r/ops/{A.hex()}/2": b"x"}


def names_only_python_reads_as_numbers(s, root):
    # int() takes these; the C loop does not judge them and hands the
    # whole step back (EINVAL), so the same files go either way
    d = f"r/ops/{A.hex()}"
    for n in ("+2", "1_0", "3", "99999999999999999999"):
        put(root, f"{d}/{n}")
    s.remove_ops_sync([(A, 10)])
    return {f"{d}/99999999999999999999": b"x"}


def remove_states_tolerates_the_gone(s, root):
    keep, drop = s.store_state_sync(b"keep"), s.store_state_sync(b"drop")
    s.remove_states_sync([drop, "ALREADYGONE"])
    s.remove_states_sync([])
    return {f"r/states/{keep}": b"keep"}


def a_directory_where_a_version_should_be(s, root):
    put(root, f"r/ops/{A.hex()}/2/inner")
    s.remove_ops_sync([(A, 5)])


# (case, what it raises, steps that run clean natively, steps handed back)
CASES = [
    (publish_fresh, None, 2, 0),
    (identical_replay, None, 2, 2),
    (ca_name_holds_other_content, FileExistsError, 0, 1),
    (versioned_name_taken, FileExistsError, 1, 1),
    (missing_directories_created, None, 4, 0),
    (atomic_overwrite, None, 6, 0),
    (remove_prefix_leaves_the_rest, None, 1, 0),
    (absent_actor_directory, None, 3, 0),
    (emptied_directory_removed, None, 2, 0),
    (non_empty_directory_kept, None, 1, 0),
    (names_only_python_reads_as_numbers, None, 0, 1),
    (remove_states_tolerates_the_gone, None, 3, 0),
    (a_directory_where_a_version_should_be, OSError, 0, 1),
]


@pytest.mark.parametrize(
    "case,raises,clean,surprises", CASES, ids=[c[0].__name__ for c in CASES]
)
def test_file_step_leaves_one_tree_either_way(
    mode, case, raises, clean, surprises, tmp_path
):
    root = str(tmp_path)
    s = FsStorage(os.path.join(root, "l"), os.path.join(root, "r"))
    if raises is None:
        expected = {os.path.normpath(k) if v is not None else k: v
                    for k, v in case(s, root).items()}
        assert tree(root) == expected
    else:
        with pytest.raises(raises) as caught:
            case(s, root)
        assert type(caught.value) in (
            raises, IsADirectoryError, PermissionError
        )
        # nothing half-made is left beside what was there
        assert not [p for p in tree(root) if ".tmp-" in p]
    if mode == "python":
        assert steps() == (0, clean + surprises)
    else:
        assert steps() == (clean, surprises)


def test_exceptions_match_between_the_two(tmp_path, monkeypatch):
    """The raising cases raise the SAME type with the same message either
    way: the native step never words an error, the Python helper does."""
    def outcome(sub, case):
        root = str(tmp_path / sub / case.__name__)
        s = FsStorage(os.path.join(root, "l"), os.path.join(root, "r"))
        with pytest.raises(OSError) as caught:
            case(s, root)
        return type(caught.value), str(caught.value).replace(root, ""), tree(root)

    raising = [c for c, r, *_ in CASES if r is not None]
    native.load()
    first = [outcome("native", c) for c in raising]
    no_toolchain(monkeypatch)
    assert [outcome("python", c) for c in raising] == first
    trace.reset()


def test_a_nonzero_status_reaches_the_python_helper(tmp_path, monkeypatch):
    lib = native.load()
    seen = []
    real_new = fs_mod._write_file_new

    def spy(path, data, **kw):
        seen.append((os.path.basename(path), data, kw))
        return real_new(path, data, **kw)

    monkeypatch.setattr(lib, "publish_file_new", lambda *a: 5)  # EIO, say
    monkeypatch.setattr(fs_mod, "_write_file_new", spy)
    trace.reset()
    s = FsStorage(str(tmp_path / "l"), str(tmp_path / "r"))
    s.store_delta_sync(A, 3, b"delta")
    assert seen == [("3", b"delta", {"relink_vanished_collider": False})]
    assert steps() == (0, 1)
    assert tree(str(tmp_path)) == {
        os.path.normpath(f"r/deltas/{A.hex()}/3"): b"delta"
    }
    trace.reset()


def test_a_failed_directory_flush_is_raised_not_replayed(tmp_path, monkeypatch):
    """Negative status: the name is published, its directory's flush
    failed.  A replay would find identical content and report success
    with the directory never flushed; the error surfaces instead."""
    import errno

    lib = native.load()
    monkeypatch.setattr(lib, "publish_file_new", lambda *a: -errno.EIO)
    monkeypatch.setattr(
        fs_mod, "_write_file_new",
        lambda *a, **k: pytest.fail("the Python body must not replay"),
    )
    s = FsStorage(str(tmp_path / "l"), str(tmp_path / "r"))
    with pytest.raises(OSError) as caught:
        s.store_state_sync(b"snapshot")
    assert caught.value.errno == errno.EIO
    trace.reset()


def test_a_bound_no_int64_holds_is_clamped(mode, tmp_path):
    s = FsStorage(str(tmp_path / "l"), str(tmp_path / "r"))
    put(str(tmp_path), f"r/ops/{A.hex()}/1")
    put(str(tmp_path), f"r/ops/{B.hex()}/1")
    s.remove_ops_sync([(A, 1 << 64), (B, -(1 << 70))])
    assert tree(str(tmp_path)) == {os.path.normpath(f"r/ops/{B.hex()}/1"): b"x"}


def test_each_publish_flushes_file_then_directory(tmp_path):
    """Two flushes a publish on the native path, as the Python body's two
    ``os.fsync``: counted where they are made."""
    lib = native.load()
    s = FsStorage(str(tmp_path / "l"), str(tmp_path / "r"))
    before = lib.file_step_flushes()
    s.store_state_sync(b"snapshot")
    s.store_delta_sync(A, 1, b"delta")
    s.store_local_meta_sync(b"meta")
    s.store_local_checkpoint_sync(b"checkpoint")
    assert lib.file_step_flushes() - before == 8
    s.remove_ops_sync([(A, 1)])
    s.remove_states_sync(["X"])
    assert lib.file_step_flushes() - before == 8


def test_sixteen_threads_publish_to_sixteen_directories(mode, tmp_path):
    root = str(tmp_path)
    stores = [
        FsStorage(os.path.join(root, f"t{t}", "l"), os.path.join(root, f"t{t}", "r"))
        for t in range(16)
    ]
    errors = []
    start = threading.Barrier(16)

    def work(t):
        try:
            start.wait(timeout=30)
            for v in range(1, 9):
                stores[t].store_delta_sync(A, v, b"d%d" % v)
                stores[t].store_local_meta_sync(b"m%d" % v)
            stores[t].remove_deltas_sync([(A, 7)])
            stores[t].store_state_sync(b"state of %d" % t)
        except BaseException as e:  # surfaced below, on the test's thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads) and not errors
    expected = {}
    for t in range(16):
        expected[os.path.normpath(f"t{t}/r/deltas/{A.hex()}/8")] = b"d8"
        expected[os.path.normpath(f"t{t}/l/meta-data.msgpack")] = b"m8"
        name = content_name(b"state of %d" % t)
        expected[os.path.normpath(f"t{t}/r/states/{name}")] = b"state of %d" % t
    assert tree(root) == expected  # sixteen of each and no ``.tmp-``


def test_threads_racing_for_one_version_one_wins(mode, tmp_path):
    """O_EXCL holds on either path: sixteen writers of different bytes at
    one version, one file, fifteen ``FileExistsError``."""
    s = FsStorage(str(tmp_path / "l"), str(tmp_path / "r"))
    outcomes = []
    start = threading.Barrier(16)

    def work(t):
        start.wait(timeout=30)
        try:
            s.store_delta_sync(A, 1, b"writer %d" % t)
            outcomes.append(t)
        except FileExistsError:
            outcomes.append(None)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads)
    winners = [t for t in outcomes if t is not None]
    assert len(outcomes) == 16 and len(winners) == 1
    assert tree(str(tmp_path)) == {
        os.path.normpath(f"r/deltas/{A.hex()}/1"): b"writer %d" % winners[0]
    }


# ---- a poll's reads (ISSUE 31): ``list_dir_names`` and ``load_op_window``
# against the Python path, each case run both ways on the same tree ----


def reads() -> tuple:
    counted = trace.snapshot()["counters"]
    return counted.get("fs_reads_native", 0), counted.get("fs_reads_python", 0)


def storage_at(root) -> FsStorage:
    return FsStorage(os.path.join(root, "l"), os.path.join(root, "r"))


def absent_directories_list_empty(s, root):
    got = (s.list_remote_meta_names_sync(), s.list_state_names_sync(),
           s.list_op_actors_sync(), s.load_ops_sync([(A, 1), (B, 7)]))
    return got, ([], [], [], [])


def junk_names_are_not_ours_to_judge(s, root):
    for name in ("ZZ", "AA", ".tmp-0123", "MM"):
        put(root, f"r/meta/{name}")
        put(root, f"r/states/{name}")
    put(root, f"r/ops/{B.hex()}/1")
    put(root, f"r/ops/{A.hex()}/1")
    put(root, "r/ops/.tmp-feed/1")
    put(root, "r/ops/not-hex/1")
    put(root, "r/ops/abcd/1")  # hex, and no actor's length
    put(root, f"r/ops/{C.hex()}")  # a file where an actor's directory is
    put(root, "r/ops/caf\udce9".encode("utf-8", "surrogateescape").decode(
        "utf-8", "surrogateescape"))
    got = (s.list_remote_meta_names_sync(), s.list_state_names_sync(),
           s.list_op_actors_sync(), s.load_ops_sync([(A, 1), (B, 1), (C, 1)]))
    names = ["AA", "MM", "ZZ"]
    return got, (names, names, [A, B, C], [(A, 1, b"x"), (B, 1, b"x")])


def runs_are_dense_and_in_the_order_asked(s, root):
    for v, data in ((1, b"one"), (2, b""), (3, b"three" * 400), (5, b"five")):
        put(root, f"r/ops/{B.hex()}/{v}", data)
    put(root, f"r/ops/{A.hex()}/7", b"seven")
    put(root, f"r/ops/{A.hex()}/8", b"eight")
    os.makedirs(os.path.join(root, f"r/ops/{C.hex()}"))  # nothing in it
    got = (
        s.load_ops_sync([(B, 1), (C, 1), (A, 7)]),  # the gap at 4 ends B's
        s.load_ops_sync([(A, 9), (B, 4)]),  # nothing new for either
        s.load_ops_sync([(A, 8), (B, 5)]),
        s.load_ops_sync([]),
    )
    return got, (
        [(B, 1, b"one"), (B, 2, b""), (B, 3, b"three" * 400),
         (A, 7, b"seven"), (A, 8, b"eight")],
        [],
        [(A, 8, b"eight"), (B, 5, b"five")],
        [],
    )


def a_fifo_where_an_actors_directory_is(s, root):
    put(root, f"r/ops/{A.hex()}/1", b"one")
    os.mkfifo(os.path.join(root, f"r/ops/{C.hex()}"))
    got = s.load_ops_sync([(A, 1), (B, 1), (C, 1)])
    return got, [(A, 1, b"one")]


# case, the reads it makes (each ONE native call, or one Python body)
READ_CASES = [
    (absent_directories_list_empty, 4),
    (junk_names_are_not_ours_to_judge, 4),
    (runs_are_dense_and_in_the_order_asked, 3),
    (a_fifo_where_an_actors_directory_is, 1),
]


@pytest.mark.parametrize(
    "case,calls", READ_CASES, ids=[c[0].__name__ for c in READ_CASES]
)
def test_a_read_gives_one_answer_either_way(mode, case, calls, tmp_path):
    got, expected = case(storage_at(str(tmp_path)), str(tmp_path))
    assert got == expected
    assert reads() == ((calls, 0) if mode == "native" else (0, calls))
    assert steps() == (0, 0)  # the tail's counters are not the reads'


@pytest.mark.parametrize("forced", [5, 34])  # EIO; ERANGE, as a full buffer
def test_a_nonzero_read_status_runs_the_python_path(forced, tmp_path, monkeypatch):
    """Status 0 or the Python path: ``_list_dir`` from its start, and the
    per-file reader over the whole request, which needs no probe."""
    lib = native.load()
    root = str(tmp_path)
    s = storage_at(root)
    _, expected = junk_names_are_not_ours_to_judge(s, root)
    seen = []
    real_file_runs = FsStorage._file_runs

    def file_runs(self, wanted, *budget):
        seen.append(list(wanted))
        return real_file_runs(self, wanted, *budget)

    monkeypatch.setattr(lib, "list_dir_names", lambda *a: forced)
    monkeypatch.setattr(lib, "load_op_window", lambda *a: forced)
    monkeypatch.setattr(FsStorage, "_file_runs", file_runs)
    monkeypatch.setattr(
        FsStorage, "_probe_actors", lambda *a: pytest.fail("a read probed")
    )
    trace.reset()
    got = (s.list_remote_meta_names_sync(), s.list_state_names_sync(),
           s.list_op_actors_sync(), s.load_ops_sync([(A, 1), (B, 1), (C, 1)]))
    assert got == expected
    assert seen == [[(A, 1), (B, 1), (C, 1)]]
    assert reads() == (0, 4)
    trace.reset()


def test_what_is_no_regular_file_ends_the_run_natively_and_is_loud_per_file(
    tmp_path, monkeypatch
):
    """A directory or a FIFO where a version should be: the one call ends
    the dense run there, without opening the FIFO for good.  The per-file
    reader, which a status falls to and a machine without a toolchain
    runs, cannot tell the directory from a defect and raises."""
    lib = native.load()
    root = str(tmp_path)
    s = storage_at(root)
    put(root, f"r/ops/{A.hex()}/1", b"one")
    os.makedirs(os.path.join(root, f"r/ops/{A.hex()}/2"))
    put(root, f"r/ops/{A.hex()}/3", b"three")
    os.makedirs(os.path.join(root, f"r/ops/{B.hex()}/1"))
    put(root, f"r/ops/{C.hex()}/4", b"four")
    os.mkfifo(os.path.join(root, f"r/ops/{C.hex()}/5"))
    wanted = [(A, 1), (B, 1), (C, 4)]
    expected = [(A, 1, b"one"), (C, 4, b"four")]
    trace.reset()
    assert s.load_ops_sync(wanted) == expected
    assert reads() == (1, 0)
    monkeypatch.setattr(lib, "load_op_window", lambda *a: 5)
    with pytest.raises(IsADirectoryError):
        s.load_ops_sync(wanted)
    assert reads() == (1, 1)
    trace.reset()


def test_reads_the_buffers_do_not_hold_are_drained_natively(tmp_path, monkeypatch):
    """A listing larger than ONE call brings back is ERANGE from the
    library itself and Python's; a load larger than one call's buffers
    is several native calls, each going on where the last one stopped,
    and one native read."""
    native.load()
    root = str(tmp_path)
    s = storage_at(root)
    _, expected = runs_are_dense_and_in_the_order_asked(s, root)
    monkeypatch.setattr(fs_mod, "LIST_NAMES_BYTES", 40)  # one actor's name
    trace.reset()
    assert s.list_op_actors_sync() == [A, B, C]
    assert reads() == (0, 1)
    wanted = [(B, 1), (C, 1), (A, 7)]
    hops = count_hops(monkeypatch)
    monkeypatch.setattr(FsStorage, "LOAD_RUNS_BYTES", 2000)  # B's third: 2,000
    assert s.load_ops_sync(wanted) == expected[0]
    assert reads() == (1, 1) and hops == {"native": 3, "per_file": 0}
    monkeypatch.setattr(FsStorage, "LOAD_RUNS_BYTES", 1 << 20)
    monkeypatch.setattr(FsStorage, "LOAD_RUNS_FILES", 4)
    assert s.load_ops_sync(wanted) == expected[0]
    assert reads() == (2, 1) and hops == {"native": 5, "per_file": 0}
    monkeypatch.setattr(FsStorage, "LOAD_RUNS_FILES", 5)
    assert s.load_ops_sync(wanted) == expected[0]
    assert reads() == (3, 1) and hops == {"native": 6, "per_file": 0}
    trace.reset()


def test_a_file_removed_between_a_status_and_the_per_file_read_ends_the_run(
    tmp_path, monkeypatch
):
    """The one native call opens a file before it sizes it, so it finds
    a file or does not; a surprise it does meet is a status, and a file
    the sync tool takes away before the per-file reader comes to it ends
    the run where the file was."""
    lib = native.load()
    root = str(tmp_path)
    s = storage_at(root)
    for v in (1, 2, 3):
        put(root, f"r/ops/{A.hex()}/{v}", b"v%d" % v)

    def raced(*args):
        os.remove(os.path.join(root, f"r/ops/{A.hex()}/2"))
        return 11  # EAGAIN, say

    monkeypatch.setattr(lib, "load_op_window", raced)
    trace.reset()
    assert s.load_ops_sync([(A, 1)]) == [(A, 1, b"v1")]
    assert reads() == (0, 1)
    monkeypatch.undo()
    assert s.load_ops_sync([(A, 1)]) == [(A, 1, b"v1")]
    assert s.load_ops_sync([(A, 3)]) == [(A, 3, b"v3")]
    trace.reset()


def test_a_file_that_does_not_end_at_its_size_is_a_status(tmp_path):
    """``load_op_window`` holds an op file to the size it had when opened:
    a ``/proc`` file says 0 and holds more, as a file still growing
    would, and is a status with nothing brought back."""
    import ctypes

    lib = native.load()
    i64 = ctypes.c_int64
    os.makedirs(tmp_path / "ops" / "aa")
    os.symlink("/proc/self/status", tmp_path / "ops" / "aa" / "1")
    counts, sizes, buf = (i64 * 1)(), (i64 * 8)(), (ctypes.c_uint8 * 64)()
    files, nbytes, stop = i64(7), i64(7), i64(7)
    status = lib.load_op_window(
        os.fsencode(tmp_path / "ops"), 1, b"aa\0", (i64 * 1)(1), 8, 64,
        counts, sizes, buf, ctypes.byref(files), ctypes.byref(nbytes),
        ctypes.byref(stop),
    )
    assert status != 0
    assert (files.value, nbytes.value, counts[0], stop.value) == (0, 0, 0, 1)


def test_sixteen_threads_poll_sixteen_remotes(mode, tmp_path):
    root = str(tmp_path)
    stores = [
        FsStorage(os.path.join(root, f"t{t}", "l"), os.path.join(root, f"t{t}", "r"))
        for t in range(16)
    ]
    for t in range(16):
        put(root, f"t{t}/r/meta/M{t}")
        put(root, f"t{t}/r/states/S{t}")
        for v in (1, 2):
            put(root, f"t{t}/r/ops/{A.hex()}/{v}", b"%d:%d" % (t, v))
    errors, polled = [], {}
    start = threading.Barrier(16)

    def work(t):
        try:
            start.wait(timeout=30)
            for _ in range(20):
                polled[t] = (
                    stores[t].list_remote_meta_names_sync(),
                    stores[t].list_state_names_sync(),
                    stores[t].list_op_actors_sync(),
                    stores[t].load_ops_sync([(A, 1), (B, 1)]),
                )
        except BaseException as e:  # surfaced below, on the test's thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(16)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads) and not errors
    assert polled == {
        t: ([f"M{t}"], [f"S{t}"], [A],
            [(A, 1, b"%d:1" % t), (A, 2, b"%d:2" % t)])
        for t in range(16)
    }
    calls = 16 * 20 * 4
    assert reads() == ((calls, 0) if mode == "native" else (0, calls))


POLL_SPANS = {"meta.list", "states.list", "ops.list", "ops.load", "ops.bulk_unwrap"}


def test_a_polls_spans_fire_once_a_tenant_under_serve_ingest(
    mode, tmp_path, monkeypatch
):
    """The job's reads keep their span names, fire once a tenant, and hang
    under that tenant's ``serve.ingest`` across the hop to the worker
    thread (so ``listing_ms.fleet`` and the idle gaps keep their
    meaning), whichever body made them."""
    import asyncio

    from test_serve import make_opts, write_orset

    from crdt_enc_tpu.core import Core
    from crdt_enc_tpu.obs import sink
    from crdt_enc_tpu.serve import FoldService

    # a sink record would drain the event log this test reads
    monkeypatch.setattr(sink, "_configured", None)
    tenants = 3

    def storage(t, name):
        base = tmp_path / f"t{t}"
        return FsStorage(str(base / name), str(base / "remote"))

    async def go():
        cores = []
        for t in range(tenants):
            await write_orset(storage(t, "w1"), 6, b"a%d" % t)
            cores.append(await Core.open(make_opts(storage(t, "s"))))
        service = FoldService(cores)
        trace.reset()
        trace.enable_events()
        results = await service.run_cycle()
        service.close()
        assert all(r.sealed for r in results)
        return threading.get_ident(), trace.events()

    loop_tid, events = asyncio.run(go())
    snap = trace.snapshot()
    assert snap["counters"].get("ingest_jobs") == tenants
    spans = {e["id"]: e for e in events if e["kind"] == "span"}
    for name in POLL_SPANS:
        fired = [e for e in spans.values() if e["name"] == name]
        assert len(fired) == tenants, name
        ingests = set()
        for e in fired:
            assert e["tid"] != loop_tid, "made by the job, off the loop"
            parent = spans[e["parent"]]
            assert parent["name"] == "serve.ingest"
            ingests.add(parent["id"])
        assert len(ingests) == tenants, "one under each tenant's serve.ingest"
    assert POLL_SPANS <= set(trace.tree()["serve.ingest"])
    calls = 4 * tenants
    assert reads() == ((calls, 0) if mode == "native" else (0, calls))


# ---- the chunk iterator (ISSUE 35): a window of wanted devices is ONE
# call of ``load_op_window`` in one worker hop; any status but 0 hands that
# window to the per-file reader.  A third door on the reads above ----


def chunk_reads() -> tuple:
    counted = trace.snapshot()["counters"]
    return (counted.get("fs_chunk_reads_native", 0),
            counted.get("fs_chunk_reads_python", 0))


def chunked(s, wanted, max_bytes=None) -> list:
    import asyncio

    async def go():
        return [chunk async for chunk in s.iter_op_chunks(wanted, max_bytes)]

    return asyncio.run(go())


def flat(chunks) -> list:
    return [item for chunk in chunks for item in chunk]


def small_windows(monkeypatch, actors, files=64, buffer=4096) -> None:
    """Windows of ``actors`` devices whose one call holds ``files`` files
    and ``buffer`` bytes (a CHUNK_WINDOWS-th of the chunk budget)."""
    monkeypatch.setattr(FsStorage, "CHUNK_WINDOW_ACTORS", actors)
    monkeypatch.setattr(FsStorage, "CHUNK_WINDOW_FILES", files)
    monkeypatch.setattr(
        FsStorage, "CHUNK_BYTES", buffer * FsStorage.CHUNK_WINDOWS
    )


# each shape: (root, monkeypatch) -> the request, the files every door
# gives, the native calls of a clean pass and the windows of them that
# fell back all the same; ``forced`` is what runs when every call of
# ``load_op_window`` is a status: the windows, one fallback each


def shape_no_ops_directory(root, mp):
    return [(A, 1), (B, 7)], [], (1, 0), 1


def shape_absent_device_directory(root, mp):
    put(root, f"r/ops/{A.hex()}/1", b"one")
    return [(B, 1), (A, 1), (C, 4)], [(A, 1, b"one")], (1, 0), 1


def shape_nothing_new(root, mp):
    put(root, f"r/ops/{A.hex()}/1", b"one")
    put(root, f"r/ops/{B.hex()}/3", b"three")
    os.makedirs(os.path.join(root, f"r/ops/{C.hex()}"))
    return [(A, 2), (B, 4), (C, 1)], [], (1, 0), 1


def shape_a_gap_ends_the_run(root, mp):
    for v in (1, 2, 4, 5):
        put(root, f"r/ops/{A.hex()}/{v}", b"v%d" % v)
    put(root, f"r/ops/{B.hex()}/9", b"nine")
    small_windows(mp, actors=1)
    return ([(A, 1), (B, 9)],
            [(A, 1, b"v1"), (A, 2, b"v2"), (B, 9, b"nine")], (2, 0), 2)


def shape_a_run_crosses_a_windows_edge(root, mp):
    """Four files of 30 bytes into buffers of 100: the call ends clean
    after A's third, and the rest of the window (A from 4, then B) is one
    more call, ahead of the window behind it (C)."""
    for v in (1, 2, 3, 4):
        put(root, f"r/ops/{A.hex()}/{v}", bytes([v]) * 30)
    put(root, f"r/ops/{B.hex()}/1", b"b" * 30)
    put(root, f"r/ops/{C.hex()}/5", b"c" * 30)
    small_windows(mp, actors=2, buffer=100)
    expected = [(A, v, bytes([v]) * 30) for v in (1, 2, 3, 4)]
    expected += [(B, 1, b"b" * 30), (C, 5, b"c" * 30)]
    return [(A, 1), (B, 1), (C, 5)], expected, (3, 0), 2


def shape_a_run_longer_than_a_windows_buffers(root, mp):
    """Eleven files into size slots for four: three calls for the one
    device, each going on where the last one stopped."""
    for v in range(1, 12):
        put(root, f"r/ops/{A.hex()}/{v}", b"%02d" % v)
    put(root, f"r/ops/{B.hex()}/1", b"b")
    small_windows(mp, actors=1, files=4)
    expected = [(A, v, b"%02d" % v) for v in range(1, 12)] + [(B, 1, b"b")]
    return [(A, 1), (B, 1)], expected, (4, 0), 2


def shape_one_file_larger_than_the_buffer(root, mp):
    """A's second file alone overflows a call's buffer: the call that
    would start with it is ERANGE, and that window (A from 2, B) is the
    per-file reader's, which takes a first file whatever its size."""
    put(root, f"r/ops/{A.hex()}/1", b"small")
    put(root, f"r/ops/{A.hex()}/2", b"L" * 300)
    put(root, f"r/ops/{A.hex()}/3", b"after")
    put(root, f"r/ops/{B.hex()}/1", b"b")
    put(root, f"r/ops/{C.hex()}/1", b"c")
    small_windows(mp, actors=2, buffer=100)
    expected = [(A, 1, b"small"), (A, 2, b"L" * 300), (A, 3, b"after"),
                (B, 1, b"b"), (C, 1, b"c")]
    return [(A, 1), (B, 1), (C, 1)], expected, (2, 1), 2


def shape_a_zero_byte_file(root, mp):
    put(root, f"r/ops/{A.hex()}/1", b"")
    put(root, f"r/ops/{A.hex()}/2", b"two")
    put(root, f"r/ops/{B.hex()}/1", b"")
    return ([(A, 1), (B, 1)],
            [(A, 1, b""), (A, 2, b"two"), (B, 1, b"")], (1, 0), 1)


def shape_no_regular_file_where_a_version_should_be(root, mp):
    put(root, f"r/ops/{A.hex()}/1", b"one")
    os.makedirs(os.path.join(root, f"r/ops/{A.hex()}/2"))
    put(root, f"r/ops/{A.hex()}/3", b"three")
    os.makedirs(os.path.join(root, f"r/ops/{B.hex()}/1"))
    put(root, f"r/ops/{C.hex()}/4", b"four")
    os.mkfifo(os.path.join(root, f"r/ops/{C.hex()}/5"))
    return ([(A, 1), (B, 1), (C, 4)],
            [(A, 1, b"one"), (C, 4, b"four")], (1, 0), 1)


def shape_a_file_that_does_not_end_at_its_size(root, mp):
    """``/proc/version`` says 0 bytes and holds a line: the one call is a
    status, and the per-file reader reads what is there, as it does
    behind ``load_ops_sync``."""
    put(root, f"r/ops/{A.hex()}/1", b"one")
    os.symlink("/proc/version", os.path.join(root, f"r/ops/{A.hex()}/2"))
    put(root, f"r/ops/{B.hex()}/1", b"b")
    with open("/proc/version", "rb") as fh:
        line = fh.read()
    assert line and os.stat("/proc/version").st_size == 0
    return ([(A, 1), (B, 1)],
            [(A, 1, b"one"), (A, 2, line), (B, 1, b"b")], (0, 1), 1)


CHUNK_SHAPES = [
    shape_no_ops_directory,
    shape_absent_device_directory,
    shape_nothing_new,
    shape_a_gap_ends_the_run,
    shape_a_run_crosses_a_windows_edge,
    shape_a_run_longer_than_a_windows_buffers,
    shape_one_file_larger_than_the_buffer,
    shape_a_zero_byte_file,
    shape_no_regular_file_where_a_version_should_be,
    shape_a_file_that_does_not_end_at_its_size,
]
# behind a status and without a library every read is per file, which is
# loud at a directory and would wait for a FIFO's writer: that shape's
# loud half is the test after this one
DOORS = [
    (shape, door)
    for shape in CHUNK_SHAPES
    for door in ("native", "forced", "python")
    if door == "native"
    or shape is not shape_no_regular_file_where_a_version_should_be
]


@pytest.mark.parametrize(
    "shape,door", DOORS, ids=[f"{s.__name__[6:]}-{d}" for s, d in DOORS]
)
def test_the_chunk_iterator_gives_the_answer_load_ops_gives(
    shape, door, tmp_path, monkeypatch
):
    """Concatenated, the chunks equal ``load_ops_sync`` of the request:
    from the windows' native calls, and from the per-file reader when
    every call is a status and on a machine without a toolchain."""
    lib = native.load()
    root = str(tmp_path)
    s = storage_at(root)
    wanted, expected, clean, windows = shape(root, monkeypatch)
    if door == "forced":
        monkeypatch.setattr(lib, "load_op_window", lambda *a: 5)
    elif door == "python":
        no_toolchain(monkeypatch)
    trace.reset()
    assert s.load_ops_sync(wanted) == expected
    assert chunk_reads() == (0, 0)  # a poll's read counts as ``fs_reads_*``
    assert flat(chunked(s, wanted)) == expected
    assert chunk_reads() == (clean if door == "native" else (0, windows))
    trace.reset()


@pytest.mark.parametrize("door", ["forced", "python"])
@pytest.mark.parametrize("read", ["load_ops_sync", "iter_op_chunks"])
def test_a_directory_where_a_version_should_be_is_loud_per_file(
    read, door, tmp_path, monkeypatch
):
    """The per-file reader cannot tell a directory from a defect and
    raises, behind a status as without a library; the chunk iterator
    hands that on as the poll's read does."""
    root = str(tmp_path)
    s = storage_at(root)
    put(root, f"r/ops/{A.hex()}/1", b"one")
    os.makedirs(os.path.join(root, f"r/ops/{A.hex()}/2"))
    if door == "forced":
        monkeypatch.setattr(native.load(), "load_op_window", lambda *a: 5)
    else:
        no_toolchain(monkeypatch)
    with pytest.raises(IsADirectoryError):
        if read == "load_ops_sync":
            s.load_ops_sync([(A, 1)])
        else:
            chunked(s, [(A, 1)])


@pytest.mark.parametrize("door", ["native", "forced"])
def test_a_file_removed_mid_window_ends_its_run(door, tmp_path, monkeypatch):
    """The sync tool takes A's second file away while the window is being
    read.  The one call opens a file before it sizes it, so it finds the
    file or does not; behind a status the per-file reader finds it gone.
    Either way A's run ends where the file was and B's is whole."""
    lib = native.load()
    root = str(tmp_path)
    s = storage_at(root)
    for v in (1, 2, 3):
        put(root, f"r/ops/{A.hex()}/{v}", b"a%d" % v)
    put(root, f"r/ops/{B.hex()}/1", b"b1")
    real = lib.load_op_window

    def taken_away(*args):
        if os.path.exists(os.path.join(root, f"r/ops/{A.hex()}/2")):
            os.remove(os.path.join(root, f"r/ops/{A.hex()}/2"))
        return real(*args) if door == "native" else 11

    monkeypatch.setattr(lib, "load_op_window", taken_away)
    trace.reset()
    expected = [(A, 1, b"a1"), (B, 1, b"b1")]
    assert flat(chunked(s, [(A, 1), (B, 1)])) == expected
    assert chunk_reads() == ((1, 0) if door == "native" else (0, 1))
    assert s.load_ops_sync([(A, 1), (B, 1)]) == expected
    trace.reset()


# ---- the two readers' one contract (ISSUE 48): ``reader(wanted,
# max_files, max_bytes) -> (files, rest)``, each call of a drain held to
# what the contract says of the request and the files that are there ----


def shape_runs_asked_from_their_middle_and_past_their_end(root, mp):
    blobs = [bytes([i]) * (i * 37 + 1) for i in range(12)]
    for actor in (A, B, C):
        for v, blob in enumerate(blobs, start=1):
            put(root, f"r/ops/{actor.hex()}/{v}", blob)
    expected = [(A, v, blobs[v - 1]) for v in range(5, 13)]
    expected += [(C, 12, blobs[11])]
    return [(A, 5), (B, 13), (C, 12)], expected, (1, 0), 1


# shape -> its budgets ``(max_files, max_bytes)``: one that holds
# everything, one that stops mid-run and one that stops between two pairs,
# where the shape has such a place; no budget is under a file's size (a
# first file that alone overflows is a status natively, by design)
EVERYTHING = {"everything": (64, 1 << 16)}
CONTRACT_SHAPES = {
    shape_no_ops_directory: EVERYTHING,
    shape_absent_device_directory: EVERYTHING,
    shape_nothing_new: EVERYTHING,
    shape_a_gap_ends_the_run: {
        **EVERYTHING, "mid_run": (1, 1 << 16), "between_pairs": (2, 1 << 16)},
    shape_a_run_crosses_a_windows_edge: {
        **EVERYTHING, "mid_run": (64, 100), "between_pairs": (64, 120)},
    shape_a_run_longer_than_a_windows_buffers: {
        **EVERYTHING, "mid_run": (4, 1 << 16), "between_pairs": (11, 1 << 16)},
    shape_one_file_larger_than_the_buffer: {
        **EVERYTHING, "mid_run": (64, 305), "between_pairs": (64, 310)},
    shape_a_zero_byte_file: {
        **EVERYTHING, "mid_run": (1, 1 << 16), "between_pairs": (2, 1 << 16)},
    shape_runs_asked_from_their_middle_and_past_their_end: {
        **EVERYTHING, "mid_run": (64, 700), "between_pairs": (8, 1 << 16)},
}
CONTRACT = [
    (shape, budget, reader)
    for shape, budgets in CONTRACT_SHAPES.items()
    for budget in budgets
    for reader in ("_native_runs", "_file_runs")
]


def the_contract(wanted, there, max_files, max_bytes) -> tuple:
    """What one call answers, ``there`` being the files of the request
    that are there, in its order: it stops before the file its budget
    does not hold, and never before its first."""
    files, size = [], 0
    for actor, version, raw in there:
        if files and (len(files) >= max_files or size + len(raw) > max_bytes):
            behind = [pair[0] for pair in wanted].index(actor)
            return files, [(actor, version), *wanted[behind + 1:]]
        files.append((actor, version, raw))
        size += len(raw)
    return files, []


@pytest.mark.parametrize(
    "shape,budget,reader", CONTRACT,
    ids=[f"{s.__name__[6:]}-{b}-{r}" for s, b, r in CONTRACT],
)
def test_the_two_readers_keep_one_contract(
    shape, budget, reader, tmp_path, monkeypatch
):
    native.load()
    root = str(tmp_path)
    s = storage_at(root)
    wanted, expected, _, _ = shape(root, monkeypatch)
    max_files, max_bytes = CONTRACT_SHAPES[shape][budget]
    got, rest, stops = [], wanted, []
    while rest:
        files, behind = getattr(s, reader)(rest, max_files, max_bytes)
        assert (files, behind) == the_contract(
            rest, expected[len(got):], max_files, max_bytes
        )
        assert files or not behind, "a drain makes progress"
        got += files
        if behind:
            stops.append(got[-1][0] == behind[0][0])
        rest = behind
    assert got == expected
    if budget == "everything":
        assert not stops
    else:  # each stop: was it inside a run?
        assert (budget == "mid_run") in stops


def test_a_window_that_fell_back_is_read_within_its_budget(tmp_path, monkeypatch):
    """The memory a window may hold is its call's budget, whichever
    reader runs: behind a status the per-file reader stops where the
    native call's buffers would have been full (a first file is taken
    whatever its size), and is gone on with from there."""
    lib = native.load()
    root = str(tmp_path)
    s = storage_at(root)
    for v in range(1, 10):
        put(root, f"r/ops/{A.hex()}/{v}", bytes([v]) * 40)
    put(root, f"r/ops/{B.hex()}/1", b"L" * 300)
    put(root, f"r/ops/{B.hex()}/2", b"b" * 40)
    expected = [(A, v, bytes([v]) * 40) for v in range(1, 10)]
    expected += [(B, 1, b"L" * 300), (B, 2, b"b" * 40)]
    small_windows(monkeypatch, actors=2, files=3, buffer=100)
    monkeypatch.setattr(lib, "load_op_window", lambda *a: 5)
    taken = []
    real = FsStorage._file_runs

    def file_runs(self, *args):
        files, rest = real(self, *args)
        taken.append([len(raw) for _, _, raw in files])
        return files, rest

    monkeypatch.setattr(FsStorage, "_file_runs", file_runs)
    trace.reset()
    assert flat(chunked(s, [(A, 1), (B, 1)])) == expected
    assert taken == [[40, 40]] * 4 + [[40], [300], [40]]
    assert chunk_reads() == (0, 1)
    trace.reset()


def thousand_devices(root) -> tuple:
    devices = [(i + 1).to_bytes(16, "big") for i in range(1000)]
    for i, actor in enumerate(devices):
        put(root, f"r/ops/{actor.hex()}/3", b"device %d" % i)
    return ([(actor, 3) for actor in devices],
            [(actor, 3, b"device %d" % i) for i, actor in enumerate(devices)])


def count_hops(monkeypatch) -> dict:
    """Calls of the two readers (in the chunk iterator: worker hops)."""
    hops = {"native": 0, "per_file": 0}
    real_native, real_per_file = FsStorage._native_runs, FsStorage._file_runs

    def native_runs(self, *args, **kw):
        hops["native"] += 1
        return real_native(self, *args, **kw)

    def file_runs(self, *args):
        hops["per_file"] += 1
        return real_per_file(self, *args)

    monkeypatch.setattr(FsStorage, "_native_runs", native_runs)
    monkeypatch.setattr(FsStorage, "_file_runs", file_runs)
    return hops


@pytest.mark.parametrize("window", [None, 256, 64])
def test_a_round_of_a_thousand_devices_is_a_hop_a_window(
    window, tmp_path, monkeypatch
):
    """1,000 devices with one new file each: ``ceil(1,000 / window)``
    worker hops, as many native calls, and nothing else: no probe pass
    and no hop a device."""
    native.load()
    root = str(tmp_path)
    s = storage_at(root)
    wanted, expected = thousand_devices(root)
    if window is not None:
        monkeypatch.setattr(FsStorage, "CHUNK_WINDOW_ACTORS", window)
    windows = -(-1000 // FsStorage.CHUNK_WINDOW_ACTORS)
    hops = count_hops(monkeypatch)
    trace.reset()
    chunks = chunked(s, wanted)
    assert flat(chunks) == expected and len(chunks) == 1
    assert hops == {"native": windows, "per_file": 0}
    assert chunk_reads() == (windows, 0)
    assert reads() == (0, 0) and steps() == (0, 0)
    trace.reset()


def test_a_forced_status_counts_python_once_a_window(tmp_path, monkeypatch):
    """Every call a status: each window is counted once and handed to
    the per-file reader, one more hop, and the files are the same."""
    lib = native.load()
    root = str(tmp_path)
    s = storage_at(root)
    wanted, expected = thousand_devices(root)
    monkeypatch.setattr(FsStorage, "CHUNK_WINDOW_ACTORS", 256)
    monkeypatch.setattr(lib, "load_op_window", lambda *a: 34)
    hops = count_hops(monkeypatch)
    trace.reset()
    assert flat(chunked(s, wanted)) == expected
    assert chunk_reads() == (0, 4)
    assert hops == {"native": 4, "per_file": 4}
    trace.reset()


def test_the_windows_behind_are_being_read_while_the_first_is_emitted(
    tmp_path, monkeypatch
):
    """The emitter never waits on a window that was not started: when the
    first window's files come out, every window in flight behind it is on
    its thread already (here they are held there until the test has seen
    the first chunk: the seven started beside it and the one that took
    its place), and the windows after those are not."""
    import asyncio

    native.load()
    root = str(tmp_path)
    s = storage_at(root)
    devices = [bytes([i + 1]) * 16 for i in range(12)]
    for actor in devices:
        put(root, f"r/ops/{actor.hex()}/1", actor * 4)
    wanted = [(actor, 1) for actor in devices]
    small_windows(monkeypatch, actors=1)  # twelve windows, eight in flight
    entered, release = [], threading.Event()
    real = FsStorage._native_runs

    def held(self, window, *args, **kw):
        entered.append(window[0][0])
        if window[0][0] != devices[0]:
            assert release.wait(timeout=30)
        return real(self, window, *args, **kw)

    monkeypatch.setattr(FsStorage, "_native_runs", held)

    async def go():
        chunks = aiter(s.iter_op_chunks(wanted, max_bytes=1))
        first = await anext(chunks)
        for _ in range(3000):  # the jobs were submitted; let them enter
            if len(entered) > FsStorage.CHUNK_WINDOWS:
                break
            await asyncio.sleep(0.01)
        seen = list(entered)
        release.set()
        return first, seen, [first] + [chunk async for chunk in chunks]

    first, seen, chunks = asyncio.run(go())
    assert first == [(devices[0], 1, devices[0] * 4)]
    assert sorted(seen) == devices[:9]
    assert flat(chunks) == [(actor, 1, actor * 4) for actor in devices]
    assert sorted(entered) == devices


def test_max_bytes_cuts_a_chunk_mid_device(tmp_path, monkeypatch):
    native.load()
    root = str(tmp_path)
    s = storage_at(root)
    for v in range(1, 11):
        put(root, f"r/ops/{A.hex()}/{v}", bytes([v]) * 100)
    put(root, f"r/ops/{B.hex()}/1", b"b" * 100)
    expected = [(A, v, bytes([v]) * 100) for v in range(1, 11)]
    expected.append((B, 1, b"b" * 100))
    for forced in (False, True):
        if forced:
            monkeypatch.setattr(native.load(), "load_op_window", lambda *a: 5)
        chunks = chunked(s, [(A, 1), (B, 1)], max_bytes=250)
        assert [len(chunk) for chunk in chunks] == [3, 3, 3, 2]
        assert flat(chunks) == expected
        assert chunks[0][-1][:2] == (A, 3) and chunks[1][0][:2] == (A, 4)


def test_a_folders_compact_reads_its_op_files_by_the_window(tmp_path):
    """End to end: ``open()`` and ``compact()`` over ``FsStorage`` take
    the writer's files in through the chunk iterator, every window a
    native call."""
    import asyncio

    from test_serve import make_opts, write_orset

    from crdt_enc_tpu.core import Core

    native.load()

    def storage(name):
        return FsStorage(str(tmp_path / name), str(tmp_path / "remote"))

    async def go():
        writer = await write_orset(storage("w"), 12, b"m")
        trace.reset()
        core = await Core.open(make_opts(storage("c")))
        await core.compact()
        assert core.with_state(lambda s: dict(s.entries)) == writer.with_state(
            lambda s: dict(s.entries)
        )

    asyncio.run(go())
    native_reads, python_reads = chunk_reads()
    assert native_reads >= 1 and python_reads == 0
    trace.reset()
