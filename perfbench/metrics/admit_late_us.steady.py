"""Mean microseconds from a request's due time to its admission into a
batcher queue (the pass that found it due), over the requests dispatched
in the traced slice: how late the serving loop took arrivals in, because
it was busy or asleep elsewhere. From the program's ``batcher.dispatch``
spans."""

from perfbench import spans


def read(run):
    return spans.per_member(run, __file__, "late_us")
