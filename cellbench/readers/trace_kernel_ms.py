"""Device milliseconds per timed call in the trace events, on the named line
of the device's plane, whose name contains one of ``match``."""

from cellbench import trace_reduce


def read(window: dict, args: dict):
    if window["trace"] is None or not window["calls"]:
        return None
    seconds, events = trace_reduce.kernel_seconds(
        window["trace"], args["line"], args["match"]
    )
    if not events:
        return None
    return 1e3 * seconds / window["calls"]
