"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (1 - busy / window), in percent."""

from chipbench import xtrace


def read(ctx):
    lo, hi = ctx["window"]
    return 100.0 * (1.0 - xtrace.busy_s(ctx["trace"], lo, hi)
                    / ctx["window_s"])
