"""``folder``'s driver for a folder whose planes have to live on the chip.

The configuration's ``layout`` says that the folder's three OR-Set planes stay
resident on the chip between rounds.  A program that folds such a round on the
host instead would run the cell with the chip idle, which is no line the
benchmark admits.  So before anything is opened the driver asks the program's
own routing where a round of this folder folds (``TpuAccelerator()``, every
default, ``orset_fold_route(members, devices, rows of a round)``); unless the
answer is ``"resident"`` it says so in one line and the run ends at once, with
nothing on standard output and a non-zero exit code.  The decision is the
program's answer, never a version or a commit.
"""

from __future__ import annotations

import sys

from cellbench.drivers import folder


def refuse_unless_resident(accel, members: int, devices: int, rows: int) -> None:
    """Exit with status 2 unless ``accel`` routes a fold of ``rows`` op rows
    over ``members`` x ``devices`` plane cells to planes resident on the
    chip."""
    route = getattr(accel, "orset_fold_route", None)
    answer = route(members, devices, rows) if route else None
    if answer == "resident":
        return
    how = (f"routes it to {answer!r}" if route else
           "does not say where it folds it (no orset_fold_route)")
    print(f"cellbench: this program {how}: a batch of {rows} rows over "
          f"{members} x {devices} plane cells; it cannot keep the planes on "
          "the chip, as the configuration's layout has them, and the cell "
          "does not run on it", file=sys.stderr, flush=True)
    raise SystemExit(2)


class Driver(folder.Driver):
    def __init__(self, config: dict, plan, workdir: str):
        from crdt_enc_tpu.parallel import TpuAccelerator

        rows = len(plan.files_of_round(0)) * plan.opf
        refuse_unless_resident(
            TpuAccelerator(), plan.members, plan.tenants * plan.devices, rows
        )
        super().__init__(config, plan, workdir)
