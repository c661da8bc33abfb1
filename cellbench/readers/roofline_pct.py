"""A kernel's share of its roofline: the least bytes its calls had to move
(``kernel_bytes.FUNCTIONS[bytes_fn]`` over the shape of every timed round),
over the device's peak rate, over the kernel's time in the trace."""

from cellbench import kernel_bytes, trace_reduce


def read(window: dict, args: dict):
    if window["trace"] is None or not window["shapes"]:
        return None
    seconds, events = trace_reduce.kernel_seconds(
        window["trace"], args["line"], args["match"]
    )
    if not events or seconds <= 0:
        return None
    fn = kernel_bytes.FUNCTIONS[args["bytes_fn"]]
    least = sum(fn(**shape) for shape in window["shapes"])
    return 100.0 * least / window["peaks"][args["peak"]] / seconds
