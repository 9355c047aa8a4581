"""The longest a sleep of the serving loop overran what it asked for, in
milliseconds, in the traced slice: the program marks each sleep that
returned over 1 ms late (``batcher.late_wake``); 0 where none did. None
where the slice holds no ``batcher.dispatch`` span, as from a program
without these spans."""

from perfbench import spans


def read(run):
    if not spans.of(run, __file__, spans.DISPATCH):
        return None
    late = [s["slept_us"] - s["asked_us"] for _, s in spans.of(run, __file__, spans.LATE_WAKE)]
    return max(late, default=0.0) / 1e3
