"""``decode_hbm_share.serve`` with layer-typed counts (``ops_hybrid``):
least bytes the decode steps must move (the served weights once a step, the
keys and values of each live request's tokens on the attention layers at
the model's bf16 dtype, and each fed-back token's read and write of its
slot's recurrent state at the dtypes the cache stores) over the decode
program's device time, as a share of the chip's memory bandwidth, in
percent."""

from chipbench import ops_hybrid, xtrace

DECODE = "jit_serve_step"


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    runs = xtrace.program_runs(trace, trace.devices[0], lo, hi).get(DECODE)
    if not runs or ctx["peaks"] is None:
        return None
    cfg, inputs = ctx["config"], ctx["inputs"]
    reqs = inputs["requests"]
    least = (len(runs) * ops_hybrid.weight_bytes(cfg)
             + ops_hybrid.decode_least_bytes(cfg, reqs)
             + ops_hybrid.decode_state_bytes(cfg, inputs["cache_dtype"],
                                             reqs))
    return 100.0 * least / sum(runs) / ctx["peaks"]["hbm_bytes_per_s"]
