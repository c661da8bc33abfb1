"""``scale`` x the summed growth of the program counters ``args.counters``
per timed call.

A counter the window never bumped counts as 0.  Nothing to read where
``args.present`` is not among the window's counters: that counter is one the
program bumps in every window in which it counts the others at all (the
collector's ``gc_passes``; a job's own hand-off counter), so a program that
lacks the counters leaves the metric out and does not read 0."""


def read(window: dict, args: dict):
    counters = window["counters"]
    if not window["calls"] or args["present"] not in counters:
        return None
    total = sum(counters.get(k, 0) for k in args["counters"])
    return args.get("scale", 1) * total / window["calls"]
