"""Least bytes the decode steps must read (the served weights once a step,
and the keys and values of each live request's tokens at the model's bf16
dtype) over the decode program's device time, as a share of the chip's
memory bandwidth, in percent. The count is the same whatever cache
implements it."""

from chipbench import ops, xtrace

DECODE = "jit_serve_step"


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    runs = xtrace.program_runs(trace, trace.devices[0], lo, hi).get(DECODE)
    if not runs or ctx["peaks"] is None:
        return None
    cfg, reqs = ctx["config"], ctx["inputs"]["requests"]
    least = (len(runs) * ops.weight_bytes(cfg)
             + ops.decode_least_bytes(cfg, reqs))
    return 100.0 * least / sum(runs) / ctx["peaks"]["hbm_bytes_per_s"]
