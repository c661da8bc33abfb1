"""Milliseconds per timed call that a span spent outside the listed spans
inside it: ``args.span``'s seconds less the summed seconds of ``args.children``.
Sound only where the children run one after another inside the parent (children
in flight together would sum past it).  Nothing to read where the parent did
not fire, or where none of the children did: a program without these spans has
no such remainder, and the parent's whole wall is not one."""


def read(window: dict, args: dict):
    spans = window["spans"]
    inside = [spans[c]["seconds"] for c in args["children"] if c in spans]
    if args["span"] not in spans or not inside or not window["calls"]:
        return None
    return 1e3 * (spans[args["span"]]["seconds"] - sum(inside)) / window["calls"]
