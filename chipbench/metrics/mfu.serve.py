"""Model operations of the traced window's served requests (2N of products
and attention at each token's context, for every prompt token and every
served token fed back) per second of the window, as a share of the chips'
bf16 peak, in percent."""

from chipbench import ops


def read(ctx):
    if ctx["peaks"] is None or not ctx["inputs"]["requests"]:
        return None
    flops = ops.serve_flops(ctx["config"], ctx["inputs"]["requests"])
    return 100.0 * flops / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
