"""Mean host microseconds to enqueue one call of the closed loop (in a
traced run, the calls before the traced slice)."""


def read(run):
    w = run.window
    if w["kind"] != "closed" or not w["dispatch_s"]:
        return None
    return sum(w["dispatch_s"]) / len(w["dispatch_s"]) * 1e6
