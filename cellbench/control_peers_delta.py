"""The controls of ``orset_folder_peers_delta``'s own guarantees: ``python -m
cellbench.control_peers_delta --workload <cell> --seed <n> --seconds <s>
--fault withhold_peer|withhold_link``.

``withhold_peer``: the first peer's snapshots **and** links stop arriving from
the first timed round on.  The reference counts that peer's op files, nothing
else carries them to the measured remote, and the run must end in
``"correct": false``.

``withhold_link``: the first peer's link of the first timed round never
arrives; its snapshot does.  "A missing link costs bytes, never data": the run
must end in ``"correct": true``.  It is traced, so that the line says what the
program did about it: ``delta_fallbacks_pct`` above 0 (the gap, counted when
the peer's next link is read), ``delta_route_pct`` under 100 (that round's
snapshot was loaded and merged) and, the peer's later links applied again,
one applied link short of four a pass over the window.

Both run on the chip at the cell's own size; the benchmark's command never
lays these keys over the configuration.  ``cellbench.control --fault
withhold_file`` is the third control, as in every ``*.backlog`` cell.
"""

from __future__ import annotations

import argparse

from cellbench import run

FAULTS = ("withhold_peer", "withhold_link")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=FAULTS, required=True)
    args = ap.parse_args(argv)
    first = run.load_cell(run.ROOT, args.workload)["traffic"]["warmup_rounds"]
    fault = {
        "withhold_peer": {"peer": 0, "from_round": first},
        "withhold_link": {"peer": 0, "round": first},
    }[args.fault]
    return run.run_cell(args.workload, args.seed, args.seconds,
                        args.fault == "withhold_link",
                        shrink={"config": {args.fault: fault}})


if __name__ == "__main__":
    raise SystemExit(main())
