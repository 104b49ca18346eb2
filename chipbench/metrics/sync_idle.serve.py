"""Share of the traced window in which the device was idle while the serve
engine's host waited on a read back from it (``serve.sync`` spans),
averaged over the chips, in percent."""

from chipbench import spans


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    sync = spans.sync_intervals(trace, lo, hi)
    if not sync:
        return None
    return 100.0 * spans.idle_s(trace, sync, lo, hi) / ctx["window_s"]
