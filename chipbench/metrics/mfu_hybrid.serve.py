"""``mfu.serve`` with layer-typed counts (``ops_hybrid``): model operations
of the traced window's served requests (2N of products, attention at each
token's context on the attention layers, the state map's least operations
on the Mamba-2 layers, for every prompt token and every served token fed
back) per second of the window, as a share of the chips' bf16 peak, in
percent."""

from chipbench import ops_hybrid


def read(ctx):
    if ctx["peaks"] is None or not ctx["inputs"]["requests"]:
        return None
    flops = ops_hybrid.serve_flops(ctx["config"], ctx["inputs"]["requests"])
    return 100.0 * flops / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
