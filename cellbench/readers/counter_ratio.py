"""One sum of program counters over another, times ``scale``.

A counter the window never bumped counts as 0; with a denominator of 0 there
is nothing to read."""


def read(window: dict, args: dict):
    c = window["counters"]
    den = sum(c.get(k, 0) for k in args["den"])
    if not den:
        return None
    return args.get("scale", 1) * sum(c.get(k, 0) for k in args["num"]) / den
