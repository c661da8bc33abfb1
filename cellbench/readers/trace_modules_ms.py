"""Device milliseconds per timed call of the named device programs: the
events on ``args.line`` of the device's plane whose name contains one of
``args.modules``.  ``trace_kernel_ms`` with the names under another key: its
``match`` is held to a table of ``tests/cellbench/test_new_readers.py``; the
metrics that use this reader are pinned in ``test_folder_peers.py``."""

from cellbench.readers import trace_kernel_ms


def read(window: dict, args: dict):
    return trace_kernel_ms.read(
        window, {"line": args["line"], "match": args["modules"]}
    )
