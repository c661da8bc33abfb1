"""One sync service left running: the timed call is ``FleetDaemon.step()``, and
the op files arrive by a clock that never waits for it.

``fleet.py``'s tenants (a ``Core`` each with its own key, its own remote and
``FsStorage``), served as ``python -m crdt_enc_tpu.tools.daemon run --tenant ...
--interval <interval_s>`` serves them: ``FleetDaemon(cores,
DaemonConfig(interval_s=...))``, every other knob the dataclass's default.  The
daemon, not the harness, chooses the tenants of a cycle (the due ones, up to
``batch``), stat-polls the others, and waits ``interval_s`` before the next.
One timed call is one ``await daemon.step()``: the pass ``run_forever`` loops
over, pacing included.

**Two questions first.**  Before anything is opened the driver asks the
program whether ``FleetDaemon`` has ``step`` and whether a daemon with the
CLI's configuration admits a fleet of this shape (stand-in cores whose states
have the deployment's members and devices; no file, no key).  Where either
answer is no it says so in one line on standard error and the run ends with
status 2 and nothing on standard output.

**The arrivals are an open loop.**  The mix (``loop: "open"``) gives
``tick_s``: every ``tick_s`` of wall one round of the plan lands, one file for
each of its ``active_tenants`` tenants, stored through ``FsStorage`` objects of
the writers' own by a thread with an event loop of its own, so neither an
``await`` nor a synchronous stretch of the program's loop holds a tick back.
The mix is the cell's own, as the harness loaded it (``plan.traffic``): one
deployment has a cell below its knee and a cell above it, and the driver is
told which by nothing else.  The clock starts with the first ``publish()``
(the first warm-up step) and stops in ``check()``; tick 0 is stored before
that ``publish()`` returns.  A clock that runs out of prepared ticks before
then fails the run: its tail was offered another load than the cell's.  The
harness's ``publish(r)`` / ``call(r)`` protocol stays as it is: ``publish``
only makes sure the clock runs (and hands ``withhold`` to the next tick to
land), ``call`` is the step.  The plan is the harness's; where it has fewer
rounds than ``min_clock_s`` of ticks (a toy window), the driver draws it again
from the same seed with that many.

**Which seal took a file in** is read from the tenant's cursor
(``Core.info().next_op_versions``) after a cycle that sealed the tenant: a
file is sealed by the first such cycle whose cursor covers its version, never
by clock order.  Its latency is *store returned -> snapshot durably sealed*:
the step's start on this driver's clock plus the tenant's ``latency_s``, which
the service counts from the start of its own cycle, a ``daemon.select`` (some
ms) later.  ``seal_p95_ms`` is its 95th percentile over every file sealed
inside the timed steps; ``serve_ops_per_s`` is the ops of those files over the
steps' summed wall, pacing included: at a sustained rate it reads the offered
rate, and it falls where the loop falls behind (both by ``fleet.py``'s
``end_to_end``, over what ``call`` returns).

**Set-up.**  The head is taken in by untimed cycles (``step(pace=False)``),
``batch`` tenants each.  Then every bucket shape a cycle of the window can meet
is folded once over throw-away tenants by a second ``FoldService`` of the same
``ServeConfig`` (the compiled programs are the process's): slots 1 to the
power of two that holds ``batch``, rows of one to ``shape_files`` files (the
mix's key, 3 where it has none: the most files a tenant may have waiting at a
visit and find its fold compiled; one cycle for each rows class, which the
service pads to a power of two, so 1, 2, 3, 6 and 11 files of 24 ops).  The
mix's warm-up steps then run under the ticking clock, so that the window opens
on a loop in its steady state: after the head the tenants come due by idleness
in the blocks the head was taken in, ``max_idle_cycles`` later, and the
arrivals have broken the blocks up by then.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
import types
from collections import deque

from cellbench import gen
from cellbench.drivers import fleet

SHAPE_FILES = 3  # the mix's ``shape_files`` where it states none
WATCHED = ("daemon.select", "serve.run_cycle", "daemon.poll", "daemon.pace")
COUNTED = ("daemon_due", "daemon_selected", "daemon_deferred", "daemon_polled",
           "serve_rows_folded")


def say(*a) -> None:
    print("cellbench:", *a, file=sys.stderr, flush=True)


def shape_file_counts(most: int, opf: int) -> list:
    """The file counts from 1 to ``most`` that each open a rows class of their
    own: a tenant's rows are padded to a power of two, so of the counts that
    share a class the first folds it for all."""
    counts, top = [], 0
    for files in range(1, most + 1):
        rows = 1 << (files * opf - 1).bit_length()
        if rows > top:
            counts.append(files)
            top = rows
    return counts


def daemon_config(config: dict):
    """What the CLI builds: the interval, and every default."""
    from crdt_enc_tpu.serve import DaemonConfig

    return DaemonConfig(interval_s=config["daemon"]["interval_s"])


def refuse_unless_daemon_serves(config: dict) -> None:
    """The two questions (module docs).  Exits with status 2 on a no."""
    from crdt_enc_tpu.models import ORSet
    from crdt_enc_tpu.serve import FleetDaemon
    from crdt_enc_tpu.serve.daemon import AdmissionError

    why = None
    if not hasattr(FleetDaemon, "step"):
        why = ("FleetDaemon has no step(): the pass its run_forever makes "
               "cannot be timed without a copy of it")
    else:
        actors = gen.actor_table(config["devices"])
        shape = ORSet()
        shape.entries = {m: {a: 1 for a in actors} for m in range(config["members"])}
        shape.clock.counters.update({a: 1 for a in actors})
        stand_in = types.SimpleNamespace(_data=types.SimpleNamespace(state=shape))
        daemon = None
        try:
            daemon = FleetDaemon([stand_in] * config["tenants"], daemon_config(config))
        except AdmissionError as e:
            why = (f"FleetDaemon(cores, DaemonConfig(interval_s=...)) refuses a fleet "
                   f"of {config['tenants']} tenants x {config['members']} members x "
                   f"{config['devices']} devices: {e}")
        finally:
            if daemon is not None:
                daemon.service.close()
    if why:
        say(f"this program cannot run the cell: {why}")
        raise SystemExit(2)


class Arrivals:
    """The clock: round ``k`` of the plan lands at ``t0 + k * tick_s``, on a
    thread and an event loop of its own.  ``landed`` takes one record a file
    as its store returns, ``ticks`` one a tick; both are only appended to
    here and only read elsewhere."""

    def __init__(self, storages: list, batches: dict, tick_s: float, n_ticks: int):
        self.storages = storages
        self.batches = batches  # round -> [(tenant, actor, version, blob, ops)]
        self.tick_s = tick_s
        self.n_ticks = n_ticks
        self.landed: deque = deque()  # (tenant, actor, version, ops, t_returned)
        self.ticks: list = []  # (k, seconds late at its start, t_done)
        self.published: list = []  # rounds the reference counts
        self.withheld: list = []  # (tenant, actor, version, ops) never stored
        self.withhold_next = False
        self.ran_out = False
        self.error = None  # what ended the clock's thread, raised by stop()
        self._stop = threading.Event()
        self._thread = None

    async def store_tick(self, k: int, late: float) -> None:
        blobs = self.batches.pop(k)
        if self.withhold_next:
            self.withhold_next = False
            self.withheld.append(blobs[-1][:3] + blobs[-1][4:])
            blobs = blobs[:-1]
        self.published.append(k)

        async def one(tenant, ab, version, blob, ops):
            await self.storages[tenant].store_ops(ab, version, blob)
            self.landed.append((tenant, ab, version, ops, time.perf_counter()))

        await asyncio.gather(*(one(*b) for b in blobs))
        self.ticks.append((k, late, time.perf_counter()))

    async def start(self) -> None:
        """Tick 0 now, on the caller's loop; the rest by the clock."""
        self.t0 = time.perf_counter()
        await self.store_tick(0, 0.0)
        self._thread = threading.Thread(
            target=self._thread_main, name="cellbench-arrivals", daemon=True)
        self._thread.start()

    def _thread_main(self) -> None:
        try:
            asyncio.run(self._run())
        except Exception as e:  # kept for stop(): a thread's end is silent
            self.error = e

    async def _run(self) -> None:
        for k in range(1, self.n_ticks):
            wait = self.t0 + k * self.tick_s - time.perf_counter()
            # in slices, so that stop() is seen within one of them
            while wait > 0 and not self._stop.is_set():
                await asyncio.sleep(min(wait, 0.05))
                wait = self.t0 + k * self.tick_s - time.perf_counter()
            if self._stop.is_set():
                return
            await self.store_tick(k, -wait)
        self.ran_out = True

    @property
    def running(self) -> bool:
        return self._thread is not None

    def stop(self) -> None:
        """No tick starts after this; the one in flight lands whole."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=60)
            if self._thread.is_alive():
                raise RuntimeError("the arrival clock's thread did not stop")
        if self.error is not None:
            error, self.error = self.error, None
            raise RuntimeError("the arrival clock failed") from error


class Driver(fleet.Driver):
    def __init__(self, config: dict, plan: gen.Plan, workdir: str):
        refuse_unless_daemon_serves(config)
        self.config = config
        mix = plan.traffic
        if not str(mix.get("loop", "")).startswith("open"):
            raise SystemExit(f"cellbench: the driver fleet_daemon needs an open-loop "
                             f"mix, and {mix.get('name')!r} is not one")
        self.tick_s = mix["tick_s"]
        self.shape_files = mix.get("shape_files", SHAPE_FILES)
        need = -(-mix["min_clock_s"] // self.tick_s)
        if plan.n_rounds < need:
            # a toy window: the harness prepared by --seconds, the warm-up's
            # steps run under the clock besides
            files = len(plan.files_of_round(0))
            plan = gen.plan_run(
                config, {**mix, "active_tenants": files, "active_devices": 1,
                         "files_per_device": 1}, plan.seed, int(need))
        super().__init__(config, plan, workdir)
        self.pending: dict = {}  # tenant -> [[actor, version, ops, t_stored]]
        self.unsealed = 0  # files stored and in no seal yet
        self.steps: list = []  # one record a step, printed by check()
        self.marks: list = []  # the program's counters and spans before each step

    # ------------------------------------------------------------ set-up

    async def open(self) -> None:
        from crdt_enc_tpu.backends import FsStorage
        from crdt_enc_tpu.serve import FleetDaemon

        plan = self.plan
        t0 = time.perf_counter()
        self.cores = await self._open_tenants(range(plan.tenants))
        t1 = time.perf_counter()
        self.storages = [c.storage for c in self.cores]
        batches = {}
        for r in range(-1, plan.n_rounds):
            per_file = plan.live[plan.rows_of_round(r)].reshape(-1, plan.opf).sum(axis=1)
            batches[r] = [(*b, int(n)) for b, n in
                          zip(await gen.seal_round(plan, r, self.cores), per_file)]
        t2 = time.perf_counter()
        self.daemon = FleetDaemon(self.cores, daemon_config(self.config))
        self.service = self.daemon.service
        head = batches.pop(-1)
        self.published.append(-1)
        await gen.store_blobs(self.storages, [b[:4] for b in head])
        for tenant, ab, version, _, ops in head:
            self.pending.setdefault(tenant, []).append([ab, version, ops, t2])
        self.unsealed = len(head)
        cfg = self.daemon.config
        for _ in range(-(-plan.tenants // cfg.batch)):
            self._account(await self.daemon.step(pace=False), time.perf_counter())
        if self.unsealed:
            raise RuntimeError(f"{self.unsealed} head files are in no seal after "
                               "the head's cycles")
        t3 = time.perf_counter()
        shapes = await self._fold_every_shape(min(plan.tenants, cfg.batch))
        # the writers' own storage objects: the clock's thread has a loop of
        # its own, and a served replica's FsStorage belongs to the program's
        writers = [FsStorage(os.path.join(self.workdir, f"t{t}", "devices"),
                             c.storage.remote) for t, c in enumerate(self.cores)]
        self.arrivals = Arrivals(writers, batches, self.tick_s, plan.n_rounds)
        say(f"set-up: opening the tenants {t1 - t0:.1f} s, sealing every op file "
            f"{t2 - t1:.1f} s, the head through {self.daemon.cycle} untimed cycles "
            f"{t3 - t2:.1f} s, {shapes} bucket shapes folded once over throw-away "
            f"tenants {time.perf_counter() - t3:.1f} s; {plan.n_rounds} ticks of "
            f"{len(batches[0])} files prepared, one every {self.tick_s} s")

    async def _open_tenants(self, names) -> list:
        """A served replica for each name, ``fleet.OPEN_WIDTH`` at a time."""
        from crdt_enc_tpu.parallel import TpuAccelerator

        names, cores = list(names), []
        for first in range(0, len(names), fleet.OPEN_WIDTH):
            cores += await asyncio.gather(*(
                self._replica(name, "served", TpuAccelerator())
                for name in names[first:first + fleet.OPEN_WIDTH]))
        return cores

    async def _fold_every_shape(self, most: int) -> int:
        """Every ``(slots, rows)`` class a cycle over at most ``most`` tenants
        with one to ``shape_files`` new files each can make, folded (and cut)
        once: the same throw-away tenants serve every class, more than half
        the slots of each."""
        from crdt_enc_tpu.serve import FoldService

        config = self.config
        opf, members = config["ops_per_file"], config["members"]
        ids = gen.actor_table(config["devices"])
        top = 1
        while top < most:
            top *= 2
        cores = await self._open_tenants([f"shape{t}" for t in range(top // 2 + 1)])
        service = FoldService(cores, self.daemon.config.serve)
        dots, versions = [0] * len(ids), [0] * len(ids)
        written = 0

        async def cycle(n: int, files: int) -> None:
            """``files`` files of ``opf`` adds for each of the first ``n``
            tenants, then one cycle over them."""
            nonlocal written
            blobs = []
            for f in range(files):
                d = (written + f) % len(ids)
                ops = [[0, (written * opf + f * opf + i) % members,
                        [ids[d], dots[d] + i + 1]] for i in range(opf)]
                dots[d], versions[d] = dots[d] + opf, versions[d] + 1
                for t in range(n):
                    blobs.append((t, ids[d], versions[d], await cores[t]._seal(ops)))
            written += files
            await gen.store_blobs([c.storage for c in cores], blobs)
            results = await service.run_cycle(cores[:n])
            if not all(r.error is None and r.sealed for r in results):
                raise RuntimeError(f"a throw-away cycle did not seal: {results}")

        try:
            # the head names every member, from every device: the classes of
            # members and replicas are the fleet's, and the warm entries exist
            await cycle(len(cores), max(len(ids), -(-members // opf)))
            count, slots = 0, top
            while slots >= 1:
                for files in shape_file_counts(self.shape_files, opf):
                    await cycle(slots // 2 + 1, files)
                    count += 1
                slots //= 2
        finally:
            service.close()
        return count

    # ------------------------------------------------------- the window

    async def publish(self, r: int, withhold: bool = False) -> None:
        if withhold:
            self.arrivals.withhold_next = True
        if not self.arrivals.running:
            await self.arrivals.start()
        self.marks.append(self._mark())

    def _mark(self) -> dict:
        from crdt_enc_tpu.utils import trace

        snap = trace.snapshot()
        return {**{k: snap["counters"].get(k, 0) for k in COUNTED},
                **{k: snap["spans"].get(k, {"seconds": 0.0})["seconds"]
                   for k in WATCHED}}

    def _take_landed(self) -> None:
        landed = self.arrivals.landed
        while landed:
            tenant, ab, version, ops, t = landed.popleft()
            self.pending.setdefault(tenant, []).append([ab, version, ops, t])
            self.unsealed += 1

    def _account(self, report, t_start: float) -> dict:
        """What one cycle sealed, by the sealed tenants' cursors."""
        if report is None:  # the cycle raised: every tenant with files failed
            waiting = sum(1 for files in self.pending.values() if files)
            return {"ops": 0, "attempted": max(1, waiting),
                    "failed": max(1, waiting), "latencies": [], "most": 0}
        ops, latencies, most = 0, [], 0
        for tid, res in report["results"].items():
            if res["outcome"] != "sealed":
                continue
            tenant = int(tid[1:])
            files = self.pending.get(tenant)
            if not files:
                continue
            cursor = self.cores[tenant].info().next_op_versions
            t_sealed = t_start + res["latency_s"]
            left = []
            for f in files:
                if f[1] <= cursor.get(f[0]):
                    ops += f[2]
                    latencies.append(t_sealed - f[3])
                else:
                    left.append(f)
            self.pending[tenant] = left
            most = max(most, len(files) - len(left))
        self.unsealed -= len(latencies)
        return {
            "ops": ops,
            "attempted": len(report["selected"]),
            "failed": sum(1 for res in report["results"].values()
                          if res["outcome"] == "error"),
            "latencies": latencies,
            "most": most,  # files the fullest visit took in: the rows class
        }

    async def call(self, r: int) -> dict:
        ticks = len(self.arrivals.ticks)
        t0 = time.perf_counter()
        report = await self.daemon.step()
        wall = time.perf_counter() - t0
        self._take_landed()
        outcome = self._account(report, t0)
        inside = self.arrivals.ticks[ticks:]
        self.steps.append({
            "r": r, "cycle": self.daemon.cycle, "wall": wall,
            "files": len(outcome["latencies"]), "most": outcome["most"],
            "ticks": len(inside),
            "late": max((late for _, late, _ in inside), default=0.0),
            "unsealed": self.unsealed,
        })
        return outcome

    # --------------------------------------------------------- the check

    async def check(self) -> list:
        arrivals, cfg = self.arrivals, self.daemon.config
        arrivals.stop()
        self.marks.append(self._mark())
        self._print_steps()
        self._take_landed()
        if arrivals.ran_out:
            # the window's tail was offered nothing: another load, not this cell's
            raise RuntimeError("the prepared ticks ran out before the window closed: "
                               "raise the mix's max_ops_per_s")
        for tenant, ab, version, ops in arrivals.withheld:
            self.pending.setdefault(tenant, []).append([ab, version, ops, None])
            self.unsealed += 1
        # no new arrivals: every stored file is in a seal within the bound
        bound = cfg.max_idle_cycles + -(-self.plan.tenants // cfg.batch)
        cycles = 0
        while self.unsealed and cycles < bound:
            self._account(await self.daemon.step(pace=False), time.perf_counter())
            cycles += 1
        say(f"after the clock stopped: {cycles} cycles (bound {bound}), "
            f"{self.unsealed} files in no seal")
        never = self.unsealed
        errors = await self.daemon.drain()
        if errors:
            say(f"drain: {len(errors)} checkpoints failed: {sorted(errors)[:5]}")
        self.published += arrivals.published
        return await super().check() + [("files_never_sealed", never, 0)]

    def _print_steps(self) -> None:
        """One line a step, from the marks taken before each ``publish``: the
        step's growth of the program's counters and spans, read here so that
        a run without ``--trace 1`` shows them too."""
        for i, s in enumerate(self.steps):
            a, b = self.marks[i], self.marks[i + 1]
            d = {k: b[k] - a[k] for k in a}
            say(f"step {s['r']} (cycle {s['cycle']}) {s['wall']:.3f} s: due "
                f"{d['daemon_due']} selected {d['daemon_selected']} deferred "
                f"{d['daemon_deferred']} polled {d['daemon_polled']}; sealed "
                f"{s['files']} files, {s['most']} in the fullest visit, "
                f"{d['serve_rows_folded']} rows; select "
                f"{1e3 * d['daemon.select']:.1f} ms, service "
                f"{1e3 * d['serve.run_cycle']:.0f}, poll {1e3 * d['daemon.poll']:.0f}, "
                f"pace {1e3 * d['daemon.pace']:.0f}; {s['ticks']} ticks landed "
                f"inside it, the latest {1e3 * s['late']:.1f} ms late; "
                f"{s['unsealed']} files stored and unsealed at its end")

    async def close(self) -> None:
        arrivals = getattr(self, "arrivals", None)
        if arrivals is not None:
            arrivals.stop()
        daemon = getattr(self, "daemon", None)
        if daemon is not None and daemon.state != "drained":
            await daemon.drain()
