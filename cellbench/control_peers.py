"""The control of ``orset_folder_peers``' own guarantee: ``python -m
cellbench.control_peers --workload <cell> --seed <n> --seconds <s>`` is the
cell's run with **the first peer's snapshots withheld** from the first timed
round on: "a snapshot that was published is merged whole" broken on purpose.
The reference counts that peer's op files, nothing else carries them to the
measured remote, and the run must end in ``"correct": false``.  (A single
lost snapshot would be healed by the peer's next one, which covers it.)  It
runs on the chip at the cell's own size; the benchmark's command never lays
this key over the configuration.  ``cellbench.control --fault withhold_file``
is the other control, as in every ``*.backlog`` cell.
"""

from __future__ import annotations

import argparse

from cellbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    first = run.load_cell(run.ROOT, args.workload)["traffic"]["warmup_rounds"]
    fault = {"withhold_peer": {"peer": 0, "from_round": first}}
    return run.run_cell(args.workload, args.seed, args.seconds, False,
                        shrink={"config": fault})


if __name__ == "__main__":
    raise SystemExit(main())
