"""Measure and commit the canonical pinned host baselines.

VERDICT r4 weak items 1/6: same-run host rates swing 1.5× with machine
weather, so published speedups need ONE committed idle-box denominator
per config.  This tool runs ONLY the host loops of the four suite
configs and the daemon fleet (exact same generators and subsamples —
the ``host_only`` mode of each ``bench_*``) under the median-of-N
protocol and writes
``benchmarks/pinned_baselines.json`` with raw samples.

Run it on an otherwise-idle box:

    python benchmarks/pin_baselines.py [--runs 5]

Re-pin deliberately (a better box, a protocol change) — never as part
of a bench run; the whole point is that the denominator does not move
with the weather.  bench.py / suite.py pick the pin up automatically
when the workload shape matches (``bench.load_pinned``).

Spread gate (VERDICT item 4): a pin measured on a noisy box is a noisy
denominator forever, so a config whose ``host_spread_pct`` exceeds
:data:`SPREAD_LIMIT_PCT` is REFUSED (exit 1, nothing written for that
config).  ``--force`` overrides with a printed warning — for when the
spread is the box's honest steady state and you accept it knowingly.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: maximum tolerated host sample spread for a committed pin, in percent
#: — above this the box was not idle enough to be a denominator.
SPREAD_LIMIT_PCT = 30.0


def spread_gate(config_name: str, rec: dict, force: bool = False) -> bool:
    """Whether ``rec`` (one measured pin record) may be written.
    Refuses — with the reason printed — when ``host_spread_pct``
    exceeds :data:`SPREAD_LIMIT_PCT`; ``force`` overrides with a
    printed warning instead (the operator owns the judgment call)."""
    spread = rec.get("host_spread_pct")
    if spread is None or float(spread) <= SPREAD_LIMIT_PCT:
        return True
    if force:
        print(
            f"WARNING: pinning {config_name} with host_spread_pct "
            f"{float(spread):.1f} > {SPREAD_LIMIT_PCT:.0f} (--force): "
            "this denominator carries the noise of a busy box",
            file=sys.stderr,
        )
        return True
    print(
        f"REFUSING to pin {config_name}: host_spread_pct "
        f"{float(spread):.1f} > {SPREAD_LIMIT_PCT:.0f} — rerun on an "
        "idle box, or pass --force to accept the noisy denominator",
        file=sys.stderr,
    )
    return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=0,
                    help="host runs per config (default BENCH_HOST_RUNS)")
    ap.add_argument("--config", type=int, default=0,
                    help="re-pin one config (1-4, 6) only")
    ap.add_argument("--force", action="store_true",
                    help="write pins even past the spread gate (warns)")
    args = ap.parse_args()
    if args.runs:
        os.environ["BENCH_HOST_RUNS"] = str(args.runs)

    # host loops only — keep the chip out of this
    import jax

    jax.config.update("jax_platforms", "cpu")

    from bench import PINNED_PATH, e2e_daemon_host
    from benchmarks.suite import (
        bench_gcounter, bench_lwwmap, bench_orset, bench_pncounter,
    )

    runners = {
        1: lambda: bench_gcounter(1_000, 4, 0, host_only=True),
        2: lambda: bench_pncounter(100_000, 1_000, 0, host_only=True),
        3: lambda: bench_orset(1_000_000, 10_000, 4096, n_host=100_000,
                               iters=0, host_only=True),
        4: lambda: bench_lwwmap(1_000_000, 1_000_000, 10_000,
                                n_host=50_000, iters=0, host_only=True),
        # the daemon family (ISSUE 12): sequential solo compacts over
        # the default --e2e-daemon fleet head shape — the denominator
        # the daemon's aggregate ops/s is ratioed against, so the
        # `trend --fail-on-regression` ratchet covers daemon
        # throughput/freshness from day one
        6: lambda: e2e_daemon_host(),
    }

    try:
        with open(PINNED_PATH) as f:
            pins = json.load(f)
    except (OSError, ValueError):
        pins = {}

    wanted = [args.config] if args.config else sorted(runners)
    ts = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    refused = []
    for c in wanted:
        print(f"pinning config {c}…", file=sys.stderr, flush=True)
        r = runners[c]()
        rec = {
            "host_rate": round(r["host_rate"], 1),
            "n_ops": r["n_ops"],
            "shape": r["shape"],
            "median_s": round(r["median_s"], 4),
            "host_samples_s": r["host_samples_s"],
            "host_spread_pct": r["host_spread_pct"],
            "ts": ts,
        }
        if not spread_gate(r["config"], rec, force=args.force):
            refused.append(r["config"])
            continue
        pins[r["config"]] = rec
        print(json.dumps({r["config"]: rec}), flush=True)

    with open(PINNED_PATH, "w") as f:
        json.dump(pins, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {PINNED_PATH}", file=sys.stderr)
    if refused:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
