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
