"""Milliseconds per timed call spent in the named program spans."""


def read(window: dict, args: dict):
    found = [window["spans"][s] for s in args["spans"] if s in window["spans"]]
    if not found or not window["calls"]:
        return None
    return 1e3 * sum(s["seconds"] for s in found) / window["calls"]
