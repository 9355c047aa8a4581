"""Milliseconds of Python garbage collection in the traced slice, the sum
of the program's ``gc.collect`` spans: a collection holds the interpreter
lock, so the serving loop stands still for its length. None where the
slice holds no ``batcher.dispatch`` span, as from a program without these
spans."""

from perfbench import spans


def read(run):
    if not spans.of(run, __file__, spans.DISPATCH):
        return None
    return sum(dur for dur, _ in spans.of(run, __file__, spans.GC)) / 1e6
