"""Mean microseconds from the pass that made a request's batch ready to
the batch's dispatch, over the requests dispatched in the traced slice:
the wait for room under the in-flight cap and for earlier dispatches of
the same pass. From the program's ``batcher.dispatch`` spans."""

from perfbench import spans


def read(run):
    return spans.per_member(run, __file__, "blocked_us")
