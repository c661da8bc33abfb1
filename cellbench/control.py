"""The builder's entry: a cell run with a guarantee broken on purpose, or
with the trace kept for reading.

``python -m cellbench.control --workload <name> --seed <n> --seconds <s>
--fault withhold_file`` is the control of "how ``correct`` is decided": one
op file of the first timed batch never reaches the program, though the
reference counts it, and the run must end in ``"correct": false``.  It runs on
the chip at the cell's own size; the benchmark's command never passes a fault.
``--keep-trace <dir>`` (with ``--trace 1``) writes a readable summary of the
profiler's trace there: planes, lines and the longest events of each.
"""

from __future__ import annotations

import argparse

from cellbench import run

FAULTS = ("withhold_file",)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--keep-trace")
    args = ap.parse_args(argv)
    return run.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        fault=args.fault, keep_trace=args.keep_trace,
    )


if __name__ == "__main__":
    raise SystemExit(main())
