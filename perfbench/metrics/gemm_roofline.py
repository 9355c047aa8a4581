"""The GEMM call's share of its roofline, in percent: the least time the
chip could take for 2n^3 operations and 3n^2 bfloat16 elements moved (the
larger of operations over peak FLOP/s and bytes over peak bytes/s) over the
device time per call, whatever implements the call. The device time per
call is that of the program that took the most device time in the traced
window, over its executions that started there."""

from perfbench.peaks import gemm_work, roofline_s


def read(run):
    if run.trace is None or not run.trace.modules or not run.peaks:
        return None
    count, seconds = max(run.trace.modules.values(), key=lambda cs: cs[1])
    if not count:
        return None
    ops, nbytes = gemm_work(run.config["overrides"]["n"], 2)
    least, _ = roofline_s(ops, nbytes, run.peaks)
    return 100.0 * least / (seconds / count)
