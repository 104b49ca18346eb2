"""Time in which a collective operation ran, per chip and per step, in ms
(averaged over the chips)."""

from chipbench import xtrace

STEP = "jit_train_step"


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    total = steps = 0.0
    for dev in trace.devices:
        total += xtrace.collective_s(trace, dev, lo, hi)[0]
        steps += len(xtrace.program_runs(trace, dev, lo, hi).get(STEP, []))
    if not steps or not total:
        return None
    return 1e3 * total / steps
