"""Multi-host execution: DCN bootstrap + host-aware meshes + global batches.

The reference has no distributed backend at all — its transport is a synced
filesystem (SURVEY.md §2.3).  This module is the TPU-native scale-out layer
the rebuild adds on top: many hosts, each with a slice of TPU chips, jointly
folding one op batch with XLA collectives.  Three pieces:

* :func:`initialize` — one-call ``jax.distributed`` bootstrap (idempotent,
  env-var driven, a no-op for single-process runs), the moral equivalent of
  the NCCL/MPI rendezvous other frameworks need — except after it returns
  there is nothing else to manage: collectives are compiled into the
  program by XLA.
* :func:`make_multihost_mesh` — a ``(dp, mp)`` mesh with **hosts on the
  ``dp`` axis and each host's chips on ``mp``**.  Why this way around: op
  rows shard over ``dp`` (parallel/mesh.py), so each host folds ONLY the
  rows it decoded locally — raw op data never crosses a host boundary.
  The fold's single collective, the ``pmax`` of folded partial planes over
  ``dp`` (mesh.py:79-81), is the one thing that must cross DCN and is
  exactly the data-parallel all-reduce pattern: dense partial state, moved
  once.  ``mp`` (the member-sharded plane axis) carries no fold-time
  collectives and stays on ICI inside each host.
* :func:`global_op_batch` — assemble the globally-``dp``-sharded op batch
  from each process's *local* rows
  (``jax.make_array_from_process_local_data``): host i's rows ARE dp shard
  i, so no host ever materializes the full batch.

Typical multi-host compaction::

    distributed.initialize()                    # env/TPU-pod autodetected
    mesh = distributed.make_multihost_mesh()
    batch = distributed.global_op_batch(mesh, kind, member, actor, counter,
                                        num_replicas=R)
    clock, add, rm = pmesh.orset_fold_sharded(mesh, clock0, add0, rm0, *batch)

Validated single-process on a virtual 8-device CPU mesh in
tests/test_distributed.py; the device placement logic is exercised by
faking process boundaries in the device list.
"""

from __future__ import annotations

import logging
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import ops as K
from ..utils import trace

logger = logging.getLogger("crdt_enc_tpu.distributed")

_INITIALIZED = False


def _backend_untouched() -> bool:
    """Whether the XLA backend is still uninitialized.  No public API
    answers this without initializing the backend as a side effect, so
    this reads jax 0.9's ``xla_bridge.backends_are_initialized``."""
    from jax._src import xla_bridge

    return not xla_bridge.backends_are_initialized()


def _already_initialized() -> bool:
    """Probe the distributed client WITHOUT touching the XLA backend
    (``jax.process_count()`` would initialize it, after which
    ``jax.distributed.initialize`` refuses to run)."""
    return bool(jax.distributed.is_initialized())


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    **kwargs,
) -> bool:
    """Bootstrap ``jax.distributed`` for a multi-host run.

    Arguments default to the standard env vars (``JAX_COORDINATOR_ADDRESS``,
    ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``).  With explicit configuration
    (args or env) the bootstrap is mandatory: failures propagate — a
    misconfigured pod must die loudly, not degrade to a single-process run
    while its peers block in the rendezvous.  With no configuration at all,
    pod auto-detection is attempted if (and only if) the XLA backend is
    still untouched; "no cluster detected" is logged and treated as a plain
    single-process run.  Returns True iff the distributed runtime is
    initialized after the call.  Safe to call more than once.
    """
    global _INITIALIZED
    if _INITIALIZED or _already_initialized():
        _INITIALIZED = True
        return True
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    explicit = (
        coordinator_address is not None
        or num_processes is not None
        or process_id is not None
    )
    if explicit:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        )
        _INITIALIZED = True
        return True
    if not _backend_untouched():
        return False  # backend already up — too late to auto-detect; no-op
    # backend untouched: attempt auto-detection — the call itself
    # degrades gracefully when there is no pod metadata
    try:
        jax.distributed.initialize(**kwargs)
    except Exception as e:  # no pod metadata → plain single-process run
        logger.info("no cluster auto-detected (%s); running single-process", e)
        return False
    _INITIALIZED = True
    return True


def make_multihost_mesh(devices=None, local_count: int | None = None) -> Mesh:
    """A ``(dp, mp)`` mesh with hosts along ``dp`` and each host's chips
    along ``mp``.

    Op rows shard over ``dp``, so each host folds only its locally-decoded
    rows; the ``pmax`` of folded partial planes over ``dp`` is the single
    cross-host (DCN) collective — dense partial state moved once, the
    data-parallel all-reduce shape.  ``mp`` shards the state planes on the
    member axis with no fold-time collectives, riding ICI within a host.

    ``devices`` defaults to all global devices in process order (JAX's
    guarantee: ``jax.devices()`` groups by process).  ``local_count``
    overrides devices-per-host for testing (fake process boundaries).
    On one host this degrades to ``(1, n_chips)`` — all chips plane-sharded;
    use :func:`crdt_enc_tpu.parallel.make_mesh` instead when you want a
    custom single-host split.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if local_count is None:
        local_count = (
            jax.local_device_count()
            if jax.process_count() > 1
            else len(devices)
        )
    n = len(devices)
    if n % local_count:
        raise ValueError(
            f"{n} devices do not split into hosts of {local_count}"
        )
    hosts = n // local_count
    # process-major device order ⇒ row i of (hosts, local) is host i's chips
    arr = np.asarray(devices).reshape(hosts, local_count)
    return Mesh(arr, axis_names=("dp", "mp"))


def global_op_batch(
    mesh: Mesh,
    kind,
    member,
    actor,
    counter,
    num_replicas: int,
    rows_per_host: int | None = None,
):
    """Assemble globally-``dp``-sharded op columns from process-local rows.

    Each process passes ONLY the rows it decoded locally; the returned
    ``jax.Array``s are global views sharded ``P("dp")`` — host i's rows are
    dp shard i, so no host gathers the whole batch.  All hosts must
    contribute the same row count for the global array to be rectangular:
    rows are sentinel-padded (``ops.pad_orset_rows``) up to ``rows_per_host``
    — computed collectively (max over hosts, one tiny allgather) when not
    given.  Single-process this degrades to a sharded ``device_put`` over
    the dp axis — the same downstream code path, so tests exercise it
    without a cluster.
    """
    cols = K.OrsetColumns(
        np.asarray(kind, np.int8),
        np.asarray(member, np.int32),
        np.asarray(actor, np.int32),
        np.asarray(counter, np.int32),
    )
    dp = mesh.shape["dp"]
    procs = jax.process_count()
    n_local = len(cols.kind)
    if rows_per_host is not None:
        # capacity check: single-process the bucket spans all dp shards,
        # multi-process it holds just this host's rows
        capacity = rows_per_host * dp if procs == 1 else rows_per_host
        if capacity < n_local:
            raise ValueError(
                f"rows_per_host={rows_per_host} cannot hold {n_local} rows"
            )
    if procs == 1:
        # whole batch is local: pad so the row count divides dp (or fills
        # the explicit per-shard bucket) and shard over the dp axis
        target = (
            rows_per_host * dp
            if rows_per_host is not None
            else -(-len(cols.kind) // dp) * dp
        )
        K.pad_orset_rows(cols, target, num_replicas)
        sharding = NamedSharding(mesh, P("dp"))
        columns = (cols.kind, cols.member, cols.actor, cols.counter)
        trace.add("h2d_bytes", sum(x.nbytes for x in columns))
        return tuple(jax.device_put(x, sharding) for x in columns)
    if dp != procs:
        raise ValueError(
            f"multi-process batches need the dp axis ({dp}) to equal the "
            f"process count ({procs}): one dp shard per host "
            "(make_multihost_mesh builds exactly this)"
        )
    if rows_per_host is None:
        from jax.experimental import multihost_utils

        counts = multihost_utils.process_allgather(
            np.asarray([len(cols.kind)], np.int64)
        )
        rows_per_host = int(np.max(counts))
    K.pad_orset_rows(cols, rows_per_host, num_replicas)
    sharding = NamedSharding(mesh, P("dp"))
    columns = (cols.kind, cols.member, cols.actor, cols.counter)
    # this host's shard of the global batch, counted at issue like the
    # single-process branch (each process counts its own contribution)
    trace.add("h2d_bytes", sum(x.nbytes for x in columns))
    return tuple(
        jax.make_array_from_process_local_data(sharding, x)
        for x in columns
    )


def replicate(mesh: Mesh, *arrays):
    """Place arrays fully replicated over the mesh (clocks, initial planes
    that are not member-sharded)."""
    sharding = NamedSharding(mesh, P())
    host = tuple(np.asarray(a) for a in arrays)
    trace.add("h2d_bytes", sum(a.nbytes for a in host))
    out = tuple(jax.device_put(a, sharding) for a in host)
    return out if len(out) != 1 else out[0]
