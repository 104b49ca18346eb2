"""Share of the device's busy time spent in the batch-prefill and admission
programs (the serve scheduler's prompt work), in percent; first chip."""

from chipbench import xtrace


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    dev = trace.devices[0]
    runs = xtrace.program_runs(trace, dev, lo, hi)
    prompt = sum(sum(v) for k, v in runs.items()
                 if "prefill" in k or "admit" in k)
    busy = xtrace.length(xtrace.busy_intervals(trace, dev, lo, hi)) / 1e9
    return 100.0 * prompt / busy if busy else None
