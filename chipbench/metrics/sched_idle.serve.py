"""Share of the traced window in which the device was idle while the serve
engine's host ran its scheduler (every ``serve.*`` span but the batch and
the reads, less the reads inside them), averaged over the chips, in
percent."""

from chipbench import spans


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    sched = spans.scheduler_intervals(trace, lo, hi)
    if not sched:
        return None
    return 100.0 * spans.idle_s(trace, sched, lo, hi) / ctx["window_s"]
