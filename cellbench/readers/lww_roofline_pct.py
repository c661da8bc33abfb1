"""The LWW winner fold's share of its roofline: the least bytes
(``lww_bytes.FUNCTIONS[bytes_fn]`` over the growth of the counters
``args.sizes`` maps its arguments to), over the device's peak rate, over the
device time of the programs whose name contains one of ``args.match``.
Nothing where a counter is missing (a program that does not count the fold)
or no such program ran on the device."""

from cellbench import lww_bytes, trace_reduce


def read(window: dict, args: dict):
    sizes = {k: window["counters"].get(c) for k, c in args["sizes"].items()}
    if window["trace"] is None or None in sizes.values():
        return None
    seconds, events = trace_reduce.kernel_seconds(
        window["trace"], args["line"], args["match"]
    )
    if not events or seconds <= 0:
        return None
    least = lww_bytes.FUNCTIONS[args["bytes_fn"]](**sizes)
    return 100.0 * least / window["peaks"][args["peak"]] / seconds
