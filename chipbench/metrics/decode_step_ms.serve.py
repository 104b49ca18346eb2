"""Median device time of one run of the decode-step program, in ms."""

import statistics

from chipbench import xtrace

DECODE = "jit_serve_step"


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    runs = xtrace.program_runs(trace, trace.devices[0], lo, hi).get(DECODE)
    return 1e3 * statistics.median(runs) if runs else None
