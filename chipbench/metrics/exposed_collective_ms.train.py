"""The part of the collective time in which no other operation ran on the
same chip, per chip and per step, in ms (averaged over the chips)."""

from chipbench import xtrace

STEP = "jit_train_step"


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    total = exposed = steps = 0.0
    for dev in trace.devices:
        c, e = xtrace.collective_s(trace, dev, lo, hi)
        total += c
        exposed += e
        steps += len(xtrace.program_runs(trace, dev, lo, hi).get(STEP, []))
    if not steps or not total:
        return None
    return 1e3 * exposed / steps
