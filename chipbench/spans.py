"""The serve engine's host spans as intervals on the device trace's clock.

``repro.serve.engine`` marks each piece of its continuous-batching loop with
a ``jax.profiler.TraceAnnotation`` named ``serve.*``: ``serve.batch`` around
one batch, ``serve.sync`` around every device-to-host read, and the
scheduler's own pieces (``serve.pool_init``, ``serve.prefill``,
``serve.admit``, ``serve.page_alloc``, ``serve.record``,
``serve.dispatch``). They are host events of the same profiler session as
the device's operations, so :func:`xtrace.load_xplane` keeps them among
``Trace.host``. A program without them gives a trace that holds none, and
the readers built on these functions then read None.

The scheduler's host time is the union of its pieces' intervals less the
union of the reads': a read inside an admission is a read, not scheduling.
"""

from __future__ import annotations

from typing import List

from chipbench import xtrace

PREFIX = "serve."
SYNC = "serve.sync"
DISPATCH = "serve.dispatch"
# spans that are not scheduler work: the whole batch, and the reads
NOT_SCHEDULER = ("serve.batch", SYNC)

Interval = xtrace.Interval


def count(trace: xtrace.Trace, name: str, lo: float, hi: float) -> int:
    """Spans called ``name`` that start in the window."""
    return sum(1 for n, s, _ in trace.host if n == name and lo <= s < hi)


def _union(trace, keep, lo, hi) -> List[Interval]:
    return xtrace.union(xtrace.clip(
        ((s, e) for n, s, e in trace.host if keep(n)), lo, hi))


def sync_intervals(trace: xtrace.Trace, lo: float, hi: float
                   ) -> List[Interval]:
    """The union of the ``serve.sync`` spans, clipped to the window."""
    return _union(trace, lambda n: n == SYNC, lo, hi)


def scheduler_intervals(trace: xtrace.Trace, lo: float, hi: float
                        ) -> List[Interval]:
    """The union of the scheduler's spans less that of the reads, clipped
    to the window."""
    sched = _union(trace, lambda n: (n.startswith(PREFIX)
                                     and n not in NOT_SCHEDULER), lo, hi)
    return xtrace.minus(sched, sync_intervals(trace, lo, hi))


def idle_s(trace: xtrace.Trace, host: List[Interval], lo: float, hi: float
           ) -> float:
    """Seconds of the disjoint sorted ``host`` intervals in which no
    operation ran on the device, averaged over the chips."""
    devs = trace.devices
    if not devs:
        return 0.0
    return sum(xtrace.length(xtrace.minus(
        host, xtrace.busy_intervals(trace, d, lo, hi)))
        for d in devs) / len(devs) / 1e9

