"""Shared by the tests that drive op ingest through the doors the product
serves (test_ingest_doors, test_streaming_pipeline, test_fold_session,
test_obs): a remote is seeded with op files written for arbitrary actors
and sealed by a real ``Core``, then read back through ``Core.read_remote()``
over a storage whose ``iter_op_chunks`` yields a few files at a time (the
solo pipelined door), through a ``HostAccelerator`` replica (the solo
whole-batch door), or through one ``FoldService`` cycle (the serve door).
"""

import copy

import numpy as np

from crdt_enc_tpu.backends import MemoryRemote, MemoryStorage, PlainKeyCryptor
from crdt_enc_tpu.backends.xchacha import XChaChaCryptor
from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


class ChunkedMemoryStorage(MemoryStorage):
    """MemoryStorage whose ``iter_op_chunks`` yields ``files_per_chunk``
    files at a time: the pipeline's chunk boundaries without a real fs."""

    files_per_chunk = 5

    async def iter_op_chunks(self, wanted, max_bytes=1 << 30):
        files = await self.load_ops(wanted)
        for lo in range(0, len(files), self.files_per_chunk):
            yield files[lo : lo + self.files_per_chunk]


def chunked(remote, files_per_chunk, base=ChunkedMemoryStorage):
    storage = base(remote)
    storage.files_per_chunk = files_per_chunk
    return storage


def make_opts(storage, *, accel=None, adapter=None, cryptor=None):
    kw = {"accelerator": accel} if accel is not None else {}
    return OpenOptions(
        storage=storage,
        cryptor=cryptor or XChaChaCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter or orset_adapter(),
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        **kw,
    )


def orset_workload(n_files=40, ops_per_file=6, R=5, E=12, seed=2):
    """``(files, actors, host)``: per-actor op files as ``(actor, [op
    obj, ...])`` in write order, and the per-op host truth (apply order
    == file order, per-actor version order)."""
    from crdt_enc_tpu.models import ORSet

    rng = np.random.default_rng(seed)
    actors = [bytes([a]) * 16 for a in range(1, R + 1)]
    counters = {a: 0 for a in range(R)}
    files = []
    for f in range(n_files):
        a = f % R
        ops = []
        for _ in range(ops_per_file):
            m = int(rng.integers(0, E))
            if rng.random() < 0.75 or counters[a] == 0:
                counters[a] += 1
                ops.append([0, m, [actors[a], counters[a]]])
            else:
                ops.append([1, m, {actors[a]: counters[a]}])
        files.append((actors[a], ops))
    return files, actors, apply_files(ORSet(), files)


async def seed_remote(files, **opts):
    """A fresh remote holding ``files`` (``(actor, [op obj, ...])`` in
    write order; each actor's versions run dense from 1), sealed by a
    writer ``Core`` that is returned with it: ``(remote, writer)``."""
    remote = MemoryRemote()
    writer = await Core.open(make_opts(MemoryStorage(remote), **opts))
    await write_files(writer, files)
    return remote, writer


async def write_files(writer, files, key=None):
    """Append ``files`` to the writer's remote, sealed with the writer's
    latest key (or ``key``); versions continue each actor's run."""
    from crdt_enc_tpu.utils import codec

    key = key or writer._latest_key()  # noqa: SLF001 — white-box wire
    ops_dir = writer.storage.remote.ops
    for actor, ops in files:
        blob = await writer._seal_packed(  # noqa: SLF001
            key, codec.pack(ops), writer.cryptor.encrypt
        )
        await writer.storage.store_ops(
            actor, len(ops_dir.get(actor, ())) + 1, blob
        )


def apply_files(state, files):
    """Apply ``files``' ops to an ORSet per op, in file order: the host
    truth of a reader that folds them."""
    from crdt_enc_tpu.models.orset import AddOp, RmOp
    from crdt_enc_tpu.models.vclock import Dot, VClock

    for _, ops in files:
        for o in ops:
            if o[0] == 0:
                state.apply(AddOp(o[1], Dot.from_obj(o[2])))
            else:
                state.apply(RmOp(o[1], VClock.from_obj(o[2])))
    return state


async def read_pipelined(remote, files_per_chunk, *, accel=None,
                         base=ChunkedMemoryStorage, **opts):
    """Open a reader on the solo pipelined door (``TpuAccelerator`` over a
    chunked storage) and ingest: returns the ``Core``."""
    from crdt_enc_tpu.parallel import TpuAccelerator

    reader = await Core.open(make_opts(
        chunked(remote, files_per_chunk, base),
        accel=accel or TpuAccelerator(min_device_batch=1), **opts
    ))
    await reader.read_remote()
    return reader


def fork(remote):
    """An independent copy of a seeded remote (a door that seals or GCs
    must not be seen by the next)."""
    return copy.deepcopy(remote)
