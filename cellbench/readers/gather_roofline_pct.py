"""A gather's share of its roofline: the least bytes it had to move
(``gather_bytes.FUNCTIONS[bytes_fn]`` over the ``args.sizes`` of every timed
round's shape), over the device's peak rate, over the device time of the
programs whose name contains one of ``args.match``.  ``roofline_pct`` with
the bytes of another module: that reader's table is ``kernel_bytes``'."""

from cellbench import gather_bytes, trace_reduce


def read(window: dict, args: dict):
    if window["trace"] is None or not window["shapes"]:
        return None
    seconds, events = trace_reduce.kernel_seconds(
        window["trace"], args["line"], args["match"]
    )
    if not events or seconds <= 0:
        return None
    fn = gather_bytes.FUNCTIONS[args["bytes_fn"]]
    least = sum(fn(**{k: shape[k] for k in args["sizes"]})
                for shape in window["shapes"])
    return 100.0 * least / window["peaks"][args["peak"]] / seconds
