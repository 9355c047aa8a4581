"""Filled slots over dispatched slots, in percent, of the batches the
window dispatched (in a traced run, those before the traced slice)."""


def read(run):
    w = run.window
    if w["kind"] != "open":
        return None
    end = min(w["t1"], w["host_until"])
    inside = [(width, filled) for t, width, filled in w["batches"] if w["t0"] <= t < end]
    slots = sum(width for width, _ in inside)
    return 100.0 * sum(filled for _, filled in inside) / slots if slots else None
