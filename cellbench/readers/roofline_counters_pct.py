"""A kernel's share of its roofline where the program's counters give its
sizes: the least bytes (``merge_bytes.FUNCTIONS[bytes_fn]`` over the growth
of the counters ``args.sizes`` maps its arguments to), over the device's peak
rate, over the device time of the programs ``args.modules`` names."""

from cellbench import merge_bytes, trace_reduce


def read(window: dict, args: dict):
    sizes = {k: window["counters"].get(c) for k, c in args["sizes"].items()}
    if window["trace"] is None or None in sizes.values():
        return None
    seconds, events = trace_reduce.kernel_seconds(
        window["trace"], args["line"], args["modules"]
    )
    if not events or seconds <= 0:
        return None
    least = merge_bytes.FUNCTIONS[args["bytes_fn"]](**sizes)
    return 100.0 * least / window["peaks"][args["peak"]] / seconds
