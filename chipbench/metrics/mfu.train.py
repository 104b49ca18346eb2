"""Model operations of the traced window's steps (6N, PaLM's attention term
and the state-space work; recomputation not counted) per second of the
window, as a share of the chips' bf16 peak, in percent."""

from chipbench import ops, xtrace

STEP = "jit_train_step"


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    steps = len(xtrace.program_runs(trace, trace.devices[0], lo, hi)
                .get(STEP, []))
    if not steps or ctx["peaks"] is None:
        return None
    inp = ctx["inputs"]
    flops = steps * inp["tokens_per_step"] * ops.train_flops_per_token(
        ctx["config"], inp["seq_len"])
    return 100.0 * flops / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
