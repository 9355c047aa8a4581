"""Mean microseconds from a request's admission to the pass that found its
queue full, expired or flushed, over the requests dispatched in the traced
slice: the wait for the batch to fill. From the program's
``batcher.dispatch`` spans."""

from perfbench import spans


def read(run):
    return spans.per_member(run, __file__, "fill_us")
