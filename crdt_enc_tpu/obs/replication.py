"""Replication & convergence observability: the per-device status math.

Every signal PR 2 added dies at the process boundary — nothing could
answer "how far behind is this device?", "has the fleet converged?", or
"how stale would a strong read be?".  This module computes, from data
the core already tracks (its ingest cursor ``next_op_versions``, the
remote op listing, and the **cursor matrix** of other replicas' published
ingest cursors — each compacted snapshot carries its sealer's cursor, so
reading a snapshot is learning a replica's progress), the per-device
replication status:

* **causal stability watermark** — the vector-clock frontier EVERY known
  replica has provably reached: ``watermark[a] = min over replicas r of
  cursor_r[a]``.  Ops at or below the watermark are causally stable
  with respect to the KNOWN membership (no replica this one has heard
  of — published cursor or produced ops — can still be missing them);
  a never-heard-from pure consumer is invisible to any
  observation-only frontier, so the strong-read tier of
  "Linearizable SMR of State-Based CRDTs without Logs"
  (arXiv:1905.08733) must additionally pin membership.  A
  replica with no published cursor contributes only its *implied
  self-knowledge* (it has certainly seen its own sealed ops), so one
  silent replica collapses the watermark for every other actor's entries
  — silence is indistinguishable from lag, and the math says so.  The
  same fact is what the computation costs: two silent replicas make the
  watermark ``{}`` after one pass over the replicas, one leaves a single
  entry, and with none the min runs over what was published — never
  replicas × actors (:func:`stability_watermark`, span
  ``repl.watermark``).
* **per-actor op backlog** — sealed-but-unfolded op files past the local
  cursor, in files and bytes (from ``Storage.stat_ops``, which sizes the
  tail without reading it).
* **divergence** — the local clock vs. the union of everything known to
  exist (remote listing ∪ published cursors): actors behind, total
  version lag, and the watermark's lag behind the union.
* **checkpoint staleness** — versions folded since the last sealed
  warm-open checkpoint (how much a crash right now would have to refold).

:func:`compute_status` is a pure function (exactly unit-testable);
``Core.replication_status()`` gathers the inputs and calls it, and
:func:`sample` publishes the scalar summary into registered gauges on
every ``open`` / ``read_remote`` / ``compact`` (opt out with
``CRDT_REPL_SAMPLE=0``).  The full status rides into the metrics sink on
every compaction (``"replication"`` key, sink schema 2) — the substrate
``obs.fleet`` aggregates across devices.

All actor ids in the returned status are lowercase hex strings and every
collection is sorted, so ``json.dumps(status, sort_keys=True)`` is
byte-stable for a given replica state — the differential tests assert
exact output, not shapes.
"""

from __future__ import annotations

from ..models.vclock import Actor, VClock
from . import record


def _hex_clock(clock: VClock) -> dict[str, int]:
    return {a.hex(): c for a, c in sorted(clock.counters.items()) if c > 0}


def stability_watermark(
    actor_id: Actor,
    local_clock: VClock,
    cursor_matrix: dict[Actor, VClock],
    union: VClock,
    replicas=None,
) -> dict[Actor, int]:
    """The causal stability watermark: pointwise min over every known
    replica's cursor (module docs) — factored out of
    :func:`compute_status` so the delta-replication layer can tag each
    sealed delta with the sealer's watermark (docs/delta.md) without
    paying the full status probe.  ``union`` is everything known to
    exist; by default replicas are this one, every published cursor,
    and every actor that ever produced ops.  The strong-read tier
    passes an explicit ``replicas`` denominator instead — its
    membership policy may pin an expected set or quarantine silent
    replicas out of the min (crdt_enc_tpu/read/policy.py); the math
    here stays one implementation either way.

    Cost: what was published, not replicas × actors.  A *silent*
    replica (in the denominator, but neither this one nor the owner of
    a published cursor) holds 0 for every actor but itself, so the min
    is read off the denominator's structure: two silent replicas give
    ``{}`` after one pass over ``replicas``; one silent replica leaves
    only its own entry to compute; with none, an actor's min stops at
    the first row that lacks it — O(replicas + actors + published
    entries) row lookups in every case (counters are non-negative, as
    ``VClock`` keeps them)."""
    with record.span("repl.watermark"):
        if replicas is None:
            replicas = set(cursor_matrix) | set(union.counters) | {actor_id}
        rows = []  # the speaking replicas: (replica, the cursor it holds)
        silent = set()
        for r in replicas:
            row = local_clock if r == actor_id else cursor_matrix.get(r)
            if row is not None:
                rows.append((r, row))
                continue
            silent.add(r)
            if len(silent) > 1:
                # every actor has a silent replica other than itself
                return {}
        # one silent replica zeroes every entry but its own
        candidates = (
            [s for s in silent if s in union.counters] if silent
            else union.counters
        )
        watermark: dict[Actor, int] = {}
        for a in candidates:
            # implied self-knowledge: a replica has certainly seen its
            # own sealed ops, published cursor or not
            lo = max(0, union.get(a)) if silent else None
            for r, row in rows:
                k = row.get(a)
                if r == a:
                    k = max(k, union.get(a))
                lo = k if lo is None else min(lo, k)
                if not lo:
                    break
            if lo:
                watermark[a] = lo
        return watermark


def compute_status(
    actor_id: Actor,
    local_clock: VClock,
    cursor_matrix: dict[Actor, VClock],
    backlog_stats: list[tuple[Actor, int, int]],
    remote_id: bytes,
    checkpoint_cursor: dict[Actor, int] | None,
    checkpoint_enabled: bool,
) -> dict:
    """The replication status dict (see module docs).

    ``backlog_stats`` is ``Storage.stat_ops`` output for versions past
    the local cursor: ``(actor, version, nbytes)`` in version order per
    actor.  ``cursor_matrix`` maps OTHER replicas' actor ids to their
    last published ingest cursor; the local replica's live cursor is
    ``local_clock``.  ``checkpoint_cursor`` is the cursor of the last
    durably sealed checkpoint (None when none was sealed)."""
    # union of everything known to exist: local history ∪ the sealed tail
    # past it ∪ every published cursor (a cursor claims the ops it counts)
    union = local_clock.copy()
    per_actor: dict[Actor, list[int]] = {}
    backlog_files = backlog_bytes = 0
    for actor, version, nbytes in backlog_stats:
        if version > union.get(actor):
            union.counters[actor] = version
        slot = per_actor.setdefault(actor, [0, 0])
        slot[0] += 1
        slot[1] += int(nbytes)
        backlog_files += 1
        backlog_bytes += int(nbytes)
    for clock in cursor_matrix.values():
        union.merge(clock)

    # stability watermark: pointwise min over every known replica's
    # cursor.  Replicas = this one, every published cursor, and every
    # actor that ever produced ops (producers are replicas by
    # construction — op files are written under the writer's actor id).
    replicas = set(cursor_matrix) | set(union.counters) | {actor_id}
    watermark = stability_watermark(actor_id, local_clock, cursor_matrix, union)

    actors_behind = sum(
        1 for a, c in union.counters.items() if c > local_clock.get(a)
    )
    version_lag = sum(
        c - local_clock.get(a) for a, c in union.counters.items()
        if c > local_clock.get(a)
    )
    watermark_lag = sum(
        c - watermark.get(a, 0) for a, c in union.counters.items()
    )

    sealed = checkpoint_cursor is not None
    base = checkpoint_cursor or {}
    staleness = sum(
        c - base.get(a, 0)
        for a, c in local_clock.counters.items()
        if c > base.get(a, 0)
    )

    return {
        "actor": actor_id.hex(),
        "remote_id": remote_id.hex(),
        "local_clock": _hex_clock(local_clock),
        "union_clock": _hex_clock(union),
        "watermark": {a.hex(): c for a, c in sorted(watermark.items())},
        "matrix": {
            r.hex(): _hex_clock(clock)
            for r, clock in sorted(cursor_matrix.items())
        },
        "backlog": {
            "files": backlog_files,
            "bytes": backlog_bytes,
            "per_actor": {
                a.hex(): {"files": f, "bytes": b}
                for a, (f, b) in sorted(per_actor.items())
            },
        },
        "divergence": {
            "actors_behind": actors_behind,
            "version_lag": version_lag,
            "watermark_lag": watermark_lag,
            "known_replicas": len(replicas),
        },
        "checkpoint": {
            "enabled": bool(checkpoint_enabled),
            "sealed": sealed,
            "staleness_versions": staleness,
        },
    }


def sample(status: dict) -> None:
    """Publish one status' scalar summary into the registered gauges —
    the names `docs/observability.md` registers and SPN001 lints."""
    record.gauge("repl_backlog_files", status["backlog"]["files"])
    record.gauge("repl_backlog_bytes", status["backlog"]["bytes"])
    record.gauge("repl_actors_behind", status["divergence"]["actors_behind"])
    record.gauge("repl_version_lag", status["divergence"]["version_lag"])
    record.gauge("repl_watermark_lag", status["divergence"]["watermark_lag"])
    record.gauge(
        "repl_known_replicas", status["divergence"]["known_replicas"]
    )
    record.gauge(
        "checkpoint_staleness_versions",
        status["checkpoint"]["staleness_versions"],
    )
    record.add("repl_samples", 1)
