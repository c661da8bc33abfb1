"""The bulk ingestion front end (batched decrypt → native columnar decode →
jit fold) must be observationally identical to the per-file asyncio path."""

import asyncio
import secrets
import uuid

import numpy as np
import pytest

import crdt_enc_tpu.core.core as core_mod
from crdt_enc_tpu.backends import (
    IdentityCryptor,
    MemoryRemote,
    MemoryStorage,
    PlainKeyCryptor,
)
from crdt_enc_tpu.backends.xchacha import (
    XChaChaCryptor,
    decrypt_blobs,
    decrypt_blob,
    encrypt_blob,
    AeadError,
)
from crdt_enc_tpu.core import Core, OpenOptions, orset_adapter
from crdt_enc_tpu.core.adapters import (
    HostAccelerator,
    gcounter_adapter,
    mvreg_adapter,
    pncounter_adapter,
)
from crdt_enc_tpu.models import ORSet, canonical_bytes
from crdt_enc_tpu.parallel.accel import TpuAccelerator
from crdt_enc_tpu.utils import codec
from crdt_enc_tpu.utils.versions import DEFAULT_DATA_VERSION_1


def run(coro):
    return asyncio.run(coro)


def make_opts(storage, adapter, accel=None, cryptor=None):
    return OpenOptions(
        storage=storage,
        cryptor=cryptor or XChaChaCryptor(),
        key_cryptor=PlainKeyCryptor(),
        adapter=adapter,
        supported_data_versions=(DEFAULT_DATA_VERSION_1,),
        current_data_version=DEFAULT_DATA_VERSION_1,
        create=True,
        accelerator=accel or HostAccelerator(),
    )


async def _write_history(core, n_files=40):
    """Many small op files: adds and removes across members."""
    for i in range(n_files):
        if i % 5 == 4:
            op = core.with_state(lambda s: s.rm_ctx(i % 7))
            if op.ctx.is_empty():
                continue
            await core.apply_ops([op])
        else:
            await core.apply_ops(
                [core.with_state(lambda s: s.add_ctx(core.actor_id, i % 7))]
            )


@pytest.mark.parametrize("reader_accel", ["host", "tpu"])
def test_bulk_ingest_matches_per_file(reader_accel, monkeypatch):
    async def go():
        remote = MemoryRemote()
        writer = await Core.open(
            make_opts(MemoryStorage(remote), orset_adapter())
        )
        await _write_history(writer)

        accel = TpuAccelerator(min_device_batch=1) if reader_accel == "tpu" else HostAccelerator()
        bulk_reader = await Core.open(
            make_opts(MemoryStorage(remote), orset_adapter(), accel=accel)
        )
        assert core_mod.BULK_MIN_FILES <= 16  # history must trip the bulk path
        await bulk_reader.read_remote()

        # per-file reference reader: bulk path disabled
        monkeypatch.setattr(core_mod, "BULK_MIN_FILES", 10**9)
        ref_reader = await Core.open(
            make_opts(MemoryStorage(remote), orset_adapter())
        )
        await ref_reader.read_remote()

        assert bulk_reader.with_state(canonical_bytes) == ref_reader.with_state(canonical_bytes)
        assert (
            bulk_reader.info().next_op_versions.to_obj()
            == ref_reader.info().next_op_versions.to_obj()
        )

    run(go())


def test_bulk_ingest_non_columnar_adapter_falls_back(monkeypatch):
    """A CRDT the accelerator can't columnar-decode still ingests correctly
    through the bulk path's Python fallback."""

    async def go():
        remote = MemoryRemote()
        writer = await Core.open(
            make_opts(MemoryStorage(remote), mvreg_adapter())
        )
        for i in range(20):
            await writer.update(
                lambda s: s.write_ctx(writer.actor_id, i)
            )
        reader = await Core.open(
            make_opts(
                MemoryStorage(remote),
                mvreg_adapter(),
                accel=TpuAccelerator(min_device_batch=1),
            )
        )
        await reader.read_remote()
        assert reader.with_state(lambda s: s.read().values) == [19]

    run(go())


@pytest.mark.parametrize("kind", ["gcounter", "pncounter"])
def test_bulk_ingest_counters_match_per_file(kind, monkeypatch):
    """The native counter bulk path must equal the per-file reference."""

    async def go():
        adapter = gcounter_adapter if kind == "gcounter" else pncounter_adapter
        remote = MemoryRemote()
        writer = await Core.open(make_opts(MemoryStorage(remote), adapter()))
        for i in range(30):
            if kind == "pncounter" and i % 3 == 2:
                await writer.apply_ops(
                    [writer.with_state(lambda s: s.dec(writer.actor_id, i % 4 + 1))]
                )
            else:
                await writer.apply_ops(
                    [writer.with_state(lambda s: s.inc(writer.actor_id, i % 5 + 1))]
                )

        bulk = await Core.open(
            make_opts(
                MemoryStorage(remote),
                adapter(),
                accel=TpuAccelerator(min_device_batch=1),
            )
        )
        await bulk.read_remote()

        monkeypatch.setattr(core_mod, "BULK_MIN_FILES", 10**9)
        ref = await Core.open(make_opts(MemoryStorage(remote), adapter()))
        await ref.read_remote()

        assert bulk.with_state(lambda s: s.read()) == ref.with_state(
            lambda s: s.read()
        )
        assert bulk.with_state(canonical_bytes) == ref.with_state(canonical_bytes)

    run(go())


def test_decode_orset_payload_batch_matches_python():
    from crdt_enc_tpu import ops as K
    from crdt_enc_tpu.ops.native_decode import decode_orset_payload_batch

    actors = sorted(uuid.UUID(int=i + 1).bytes for i in range(5))
    state = ORSet()
    payloads = []
    all_ops = []
    for f in range(30):
        ops = []
        for i in range(7):
            a = actors[(f + i) % 5]
            if (f + i) % 6 == 5:
                op = state.rm_ctx((f * 7 + i) % 11)
                if op.ctx.is_empty():
                    continue
            else:
                op = state.add_ctx(a, (f * 7 + i) % 11)
            state.apply(op)
            ops.append(op)
        payloads.append(codec.pack([op.to_obj() for op in ops]))
        all_ops.extend(ops)

    decoded = decode_orset_payload_batch(payloads, actors)
    assert decoded is not None
    kind, member_idx, actor_idx, counter, members = decoded

    ref = K.orset_ops_to_columns(all_ops)
    assert len(kind) == len(ref.kind)
    np.testing.assert_array_equal(kind, ref.kind)
    np.testing.assert_array_equal(counter, ref.counter)
    # member/actor indices use different intern orders; compare resolved
    for i in range(len(kind)):
        assert members[member_idx[i]] == ref.members.items[ref.member[i]]
        assert actors[actor_idx[i]] == ref.replicas.items[ref.actor[i]]


def test_fold_payloads_bails_on_member_value_collision():
    """Distinct canonical encodings that collide as Python values (1 == True)
    would collapse the member vocab and scatter rows out of range; the
    accelerator must decline so the per-op host path (whose dict semantics
    define the contract) handles the batch."""
    from crdt_enc_tpu.models.vclock import Dot

    actor = uuid.UUID(int=1).bytes
    ops = [
        [0, 1, Dot(actor, 1).to_obj()],
        [0, True, Dot(actor, 2).to_obj()],
        [0, b"x", Dot(actor, 3).to_obj()],
    ]
    payload = codec.pack(ops)
    accel = TpuAccelerator(min_device_batch=1)
    state = ORSet()
    assert accel.fold_payloads(state, [payload], actors_hint=[actor]) is False
    assert canonical_bytes(state) == canonical_bytes(ORSet())  # untouched


def test_decode_unknown_actor_returns_none():
    from crdt_enc_tpu.ops.native_decode import decode_orset_payload_batch

    known = [uuid.UUID(int=1).bytes]
    stranger = uuid.UUID(int=99).bytes
    state = ORSet()
    op = state.add_ctx(stranger, "m")
    payload = codec.pack([op.to_obj()])
    assert decode_orset_payload_batch([payload], known) is None


def test_decrypt_blobs_matches_sequential_and_detects_tamper():
    key = secrets.token_bytes(32)
    blobs = [encrypt_blob(key, f"payload-{i}".encode() * (i % 9 + 1)) for i in range(64)]
    assert decrypt_blobs(key, blobs) == [decrypt_blob(key, b) for b in blobs]
    bad = bytearray(blobs[7])
    bad[-1] ^= 1
    with pytest.raises(AeadError):
        decrypt_blobs(key, blobs[:7] + [bytes(bad)] + blobs[8:])


def test_bulk_gap_leaves_cursors_consistent(monkeypatch):
    """An op file arriving beyond the expected version (a GC'd hole with
    stranded files) must raise OpOrderError WITHOUT advancing cursors past
    ops that never folded — after the remote is repaired, a re-read must
    recover everything.  Regression: the bulk path used to advance cursors
    during validation and fold only afterwards, so a mid-batch gap
    stranded the validated prefix behind advanced cursors forever."""
    from crdt_enc_tpu.core.core import OpOrderError

    class GappedStorage(MemoryStorage):
        gap_on = True

        async def load_ops(self, afv):
            out = await super().load_ops(afv)
            if not self.gap_on:
                return out
            # forge a hole: drop one mid-batch file, keep the rest stranded
            return [f for i, f in enumerate(out) if i != 20]

    async def go():
        remote = MemoryRemote()
        writer = await Core.open(make_opts(MemoryStorage(remote), orset_adapter()))
        await _write_history(writer, n_files=40)

        st = GappedStorage(remote)
        reader = await Core.open(make_opts(st, orset_adapter()))
        with pytest.raises(OpOrderError):
            await reader.read_remote()

        st.gap_on = False  # the missing file "syncs in"
        await reader.read_remote()

        ref = await Core.open(make_opts(MemoryStorage(remote), orset_adapter()))
        await ref.read_remote()
        assert reader.with_state(canonical_bytes) == ref.with_state(canonical_bytes)
        assert (
            reader.info().next_op_versions.to_obj()
            == ref.info().next_op_versions.to_obj()
        )

    run(go())


# ---- ISSUE 13 acceptance: streaming ≡ sequential scalar, adapters × backends


@pytest.mark.parametrize("backend", ["memory", "fs"])
@pytest.mark.parametrize("kind", ["orset", "gcounter", "pncounter"])
def test_streaming_ingest_matches_scalar_adapters_backends(
    kind, backend, tmp_path, monkeypatch
):
    """The pipelined ingest (fold sessions, bytes-keyed remap, split
    sparse fold) must produce
    byte-identical state AND cursors to the sequential per-file scalar
    path, for ≥3 adapters on BOTH storage backends."""
    from crdt_enc_tpu.backends import FsStorage

    adapters = {
        "orset": orset_adapter,
        "gcounter": gcounter_adapter,
        "pncounter": pncounter_adapter,
    }
    mk_adapter = adapters[kind]

    if backend == "memory":
        remote = MemoryRemote()

        def make(name):
            return MemoryStorage(remote)
    else:
        remote_dir = tmp_path / "remote"

        def make(name):
            return FsStorage(str(tmp_path / f"local-{name}"), str(remote_dir))

    def build(core, i):
        if kind == "orset":
            if i % 5 == 4:
                op = core.with_state(lambda s: s.rm_ctx(i % 7))
                if op.ctx.is_empty():
                    return None
                return op
            return core.with_state(
                lambda s: s.add_ctx(core.actor_id, i % 7)
            )
        if kind == "pncounter" and i % 3 == 2:
            return core.with_state(lambda s: s.dec(core.actor_id))
        return core.with_state(lambda s: s.inc(core.actor_id, 1 + i % 3))

    async def go():
        writer = await Core.open(make_opts(make("w"), mk_adapter()))
        for i in range(core_mod.BULK_MIN_FILES + 20):
            op = build(writer, i)
            if op is not None:
                await writer.apply_ops([op])

        streaming = await Core.open(make_opts(
            make("s"), mk_adapter(),
            accel=TpuAccelerator(min_device_batch=1),
        ))
        await streaming.read_remote()

        monkeypatch.setattr(core_mod, "BULK_MIN_FILES", 10**9)
        scalar = await Core.open(make_opts(make("r"), mk_adapter()))
        await scalar.read_remote()

        assert streaming.with_state(canonical_bytes) == scalar.with_state(
            canonical_bytes
        )
        assert (
            streaming.info().next_op_versions.to_obj()
            == scalar.info().next_op_versions.to_obj()
        )

    run(go())
