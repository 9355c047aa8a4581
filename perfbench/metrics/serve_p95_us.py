"""95th percentile latency, in microseconds, of every request due in the
window, counted from its due time; a request that never completed counts
as slower than any that did."""

import math


def read(run):
    w = run.window
    if w["kind"] != "open" or not w["requests"]:
        return None
    lat = sorted(
        math.inf if done is None else (done - due) * 1e6 for due, done, _ in w["requests"]
    )
    p95 = lat[math.ceil(0.95 * len(lat)) - 1]  # nearest rank
    return None if math.isinf(p95) else p95
