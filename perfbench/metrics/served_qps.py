"""Requests completed inside the window, per second of the window."""


def read(run):
    w = run.window
    if w["kind"] != "open":
        return None
    done = sum(w["t0"] <= t <= w["t1"] for t in w["completed_at"])
    return done / (w["t1"] - w["t0"])
