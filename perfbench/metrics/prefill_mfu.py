"""The prefill call's share of the chip's bf16 peak, in percent: the
benchmark's own count of the call's operations (``perfbench/lm_work.py``,
from the configuration's widths) over the device time per call and the
peak. The device time per call is that of the program that took the most
device time in the traced window, over its executions that started
there."""

from perfbench.lm_work import prefill_ops


def read(run):
    if run.trace is None or not run.trace.modules or not run.peaks:
        return None
    count, seconds = max(run.trace.modules.values(), key=lambda cs: cs[1])
    if not count:
        return None
    return 100.0 * prefill_ops(run.config) / (seconds / count) / run.peaks["bf16_flop_per_s"]
