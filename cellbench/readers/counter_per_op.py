"""A program counter's growth per op the timed calls took in."""


def read(window: dict, args: dict):
    if not window["ops"] or args["counter"] not in window["counters"]:
        return None
    return window["counters"][args["counter"]] / window["ops"]
