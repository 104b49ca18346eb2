"""Reads of device results by the serve engine's host (``serve.sync``
spans) per token served in the traced window."""

from chipbench import spans


def read(ctx):
    trace, (lo, hi) = ctx["trace"], ctx["window"]
    syncs = spans.count(trace, spans.SYNC, lo, hi)
    tokens = sum(g for _, g in ctx["inputs"]["requests"])
    if not syncs or not tokens:
        return None
    return syncs / tokens
