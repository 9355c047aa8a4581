"""Microseconds per call of a closed loop: the window's seconds, from the
first enqueue to the last completion, over the calls it completed."""


def read(run):
    w = run.window
    if w["kind"] != "closed" or not w["calls"]:
        return None
    return (w["t1"] - w["t0"]) / w["calls"] * 1e6
