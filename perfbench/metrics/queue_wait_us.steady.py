"""Mean microseconds from a request's due time to the dispatch of its
batch, over the window's requests (in a traced run, those due before the
traced slice, which the profiler slows)."""


def read(run):
    w = run.window
    if w["kind"] != "open":
        return None
    waits = [
        (disp - due) * 1e6
        for due, done, disp in w["requests"]
        if disp is not None and due < w["host_until"]
    ]
    return sum(waits) / len(waits) if waits else None
