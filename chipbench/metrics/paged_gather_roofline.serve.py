"""The page gather kernel's share of its roofline: the least bytes its
calls must move (``ops.paged_gather_least_bytes``) at the chip's memory
bandwidth, over their traced time, in percent. The kernel does no
arithmetic, so memory bounds it. It is found in the decode program as the
custom call whose result is the gathered view,
``[batch x pages, page, kv_heads x head_dim]`` in the cache's dtype."""

from chipbench import ops, xtrace

DECODE = "jit_serve_step"
SHORT = {"float32": "f32", "bfloat16": "bf16"}


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    cfg, inp = ctx["config"], ctx["inputs"]
    e = inp["engine"]
    dev = trace.devices[0]
    rows = e["batch_size"] * -(-e["max_len"] // e["page_size"])
    view = (f"{SHORT[inp['cache_dtype']]}[{rows},{e['page_size']},"
            f"{cfg['num_kv_heads'] * cfg['head_dim']}]")
    calls, secs = 0, 0.0
    for text, s, t, prog in trace.ops[dev]:
        if prog != DECODE or not lo <= s < hi:
            continue
        _, op, typ = xtrace.parse_op(text)
        if op == "custom-call" and typ == view:
            calls += 1
            secs += (t - s) / 1e9
    steps = len(xtrace.program_runs(trace, dev, lo, hi).get(DECODE, []))
    if not calls or not steps or ctx["peaks"] is None:
        return None
    least = ops.paged_gather_least_bytes(
        cfg, e["batch_size"], e["max_len"], e["page_size"],
        inp["cache_dtype"], inp["requests"], calls, steps)
    return 100.0 * least / ctx["peaks"]["hbm_bytes_per_s"] / secs
