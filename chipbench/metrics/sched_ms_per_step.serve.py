"""The serve engine's scheduler host time (every ``serve.*`` span but the
batch and the reads, less the reads inside them) per decode step
(``serve.dispatch`` span), in ms."""

from chipbench import spans, xtrace


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    steps = spans.count(trace, spans.DISPATCH, lo, hi)
    if not steps:
        return None
    sched = spans.scheduler_intervals(trace, lo, hi)
    return 1e3 * xtrace.length(sched) / 1e9 / steps
