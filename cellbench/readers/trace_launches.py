"""Events per timed call on the named line of the first device's plane that
start inside the timed calls: on ``XLA Modules``, the device programs the host
launched."""

from cellbench import trace_reduce


def read(window: dict, args: dict):
    if window["trace"] is None or not window["calls"]:
        return None
    calls = trace_reduce.call_windows(window["trace"]).tolist()
    lines = trace_reduce._lines(
        window["trace"], trace_reduce.DEVICE_PLANE, args["line"]
    )
    for _, line in lines:  # the first device's plane only
        inside = sum(
            1 for _, start, _ in line["events"]
            if any(w0 <= start < w1 for w0, w1 in calls)
        )
        return inside / window["calls"]
    return None
