"""From the profiler's trace to numbers.

``capture`` records a window with JAX's profiler (Python function tracing
off, so the host's own work is not slowed by it); ``load_xplane`` keeps of
the ``.xplane.pb`` only what the readers use, as a :class:`Trace` that is
also written and read as JSON (the recorded test trace is one). The
functions below it reduce a trace to device busy time, idle gaps, program
and operation times, and collective time, each clipped to the window the
benchmark marked on the host.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "chipbench window"
# spans the benchmark puts around its own calls into the program
OWN_SPANS = ("generate call", "train step")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv)")
# operations that only hold others: their own interval covers their body's
CONTAINERS = ("while", "conditional", "call")
_MODULE_ID = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")

Interval = Tuple[float, float]


class Trace:
    """Device operations and programs per chip, and host spans; times in
    nanoseconds on the profiler's common clock.

    ``ops[dev]``: ``[name, start, end, program]`` for every operation;
    ``modules[dev]``: ``[program, start, end]`` for every program run;
    ``host``: ``[name, start, end]`` for every host event that has a
    duration.
    """

    def __init__(self, ops, modules, host):
        self.ops: Dict[str, List[list]] = ops
        self.modules: Dict[str, List[list]] = modules
        self.host: List[list] = host

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def window(self) -> Interval:
        """The span the benchmark put around its window."""
        spans = [(s, e) for n, s, e in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        return spans[0]

    def to_json(self) -> Dict:
        return {"ops": self.ops, "modules": self.modules, "host": self.host}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls(d["ops"], d["modules"], d["host"])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


@contextlib.contextmanager
def capture():
    """Profile the block; yields a dict whose ``"trace"`` is the
    :class:`Trace` once the block has ended. The raw files are deleted."""
    import jax

    d = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out: Dict = {}
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True)
            out["trace"] = load_xplane(paths[0]) if paths else None
        finally:
            shutil.rmtree(d, ignore_errors=True)


def program_name(name: str) -> str:
    """``jit_serve_step(123)`` -> ``jit_serve_step``."""
    return _MODULE_ID.sub("", name)


def parse_op(text: str) -> Tuple[str, str, str]:
    """(name, opcode, result type) of an operation as the TPU trace names
    it, ``%copy.1 = f32[8,128]{1,0} copy(f32[8,128]{0,1} %p)``; an event
    name of another form is its own name with no opcode."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, "", ""
    if rest.startswith("("):          # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = rest[: i + 1], rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
    return name, rest.partition("(")[0], _LAYOUT.sub("", typ)


def opcode(text: str) -> str:
    return parse_op(text)[1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[list]] = {}
    modules: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            dev = plane.name[len("/device:"):]
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[dev] = [[program_name(e.name), e.start_ns,
                                     e.start_ns + e.duration_ns]
                                    for e in line.events]
                elif line.name == OPS_LINE:
                    ops[dev] = [[e.name, e.start_ns,
                                 e.start_ns + e.duration_ns, ""]
                                for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                         for e in line.events if e.duration_ns > 0]
    trace = Trace(ops, modules, host)
    attach_programs(trace)
    return trace


def attach_programs(trace: Trace) -> None:
    """Name each operation's program: the program run that contains it."""
    for dev, evs in trace.ops.items():
        mods = sorted(trace.modules.get(dev, []), key=lambda m: m[1])
        j = 0
        for ev in sorted(evs, key=lambda e: e[1]):
            while j < len(mods) and mods[j][2] < ev[1]:
                j += 1
            if j < len(mods) and mods[j][1] <= ev[1]:
                ev[3] = mods[j][0]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def minus(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` outside those of ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    return minus([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def busy_intervals(trace: Trace, dev: str, lo: float, hi: float
                   ) -> List[Interval]:
    return union(clip(((s, e) for _, s, e, _ in trace.ops[dev]), lo, hi))


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    devs = trace.devices
    if not devs:
        return 0.0
    return sum(length(busy_intervals(trace, d, lo, hi))
               for d in devs) / len(devs) / 1e9


def program_runs(trace: Trace, dev: str, lo: float, hi: float
                 ) -> Dict[str, List[float]]:
    """Per program, the device seconds of each of its runs that started in
    the window."""
    out: Dict[str, List[float]] = {}
    for name, s, e in trace.modules.get(dev, []):
        if lo <= s < hi:
            out.setdefault(name, []).append((e - s) / 1e9)
    return out


def op_seconds(trace: Trace, dev: str, lo: float, hi: float,
               match=None) -> Dict[Tuple[str, str], float]:
    """(program, operation) -> seconds in the window, for operations whose
    name ``match`` accepts (all where None)."""
    out: Dict[Tuple[str, str], float] = {}
    for name, s, e, prog in trace.ops[dev]:
        if e <= lo or s >= hi or (match is not None and not match(name)):
            continue
        key = (prog, name)
        out[key] = out.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
    return out


def is_collective(op: str) -> bool:
    return bool(COLLECTIVE.match(op))


def collective_s(trace: Trace, dev: str, lo: float, hi: float
                 ) -> Tuple[float, float]:
    """(seconds in which a collective ran, seconds of that in which no
    other operation ran) on one chip."""
    coll, comp = [], []
    for name, s, e, _ in trace.ops[dev]:
        op = opcode(name)
        if is_collective(op):
            coll.append((s, e))
        elif op not in CONTAINERS:
            comp.append((s, e))
    coll = union(clip(coll, lo, hi))
    comp = union(clip(comp, lo, hi))
    return length(coll) / 1e9, length(minus(coll, comp)) / 1e9


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> Dict:
    """The operations that took most device time (averaged over chips; an
    operation that only holds others is not listed), and the longest idle
    gaps of the first chip, each named by the host event that covers most
    of it, or by the last host event before it."""
    total: Dict[str, float] = {}
    for dev in trace.devices:
        for (prog, text), sec in op_seconds(trace, dev, lo, hi).items():
            name, op, typ = parse_op(text)
            if op in CONTAINERS:
                continue
            key = f"{prog}/{name} {op} {typ}".strip()
            total[key] = total.get(key, 0.0) + sec / len(trace.devices)
    ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    dev = trace.devices[0]
    idle = sorted(gaps(busy_intervals(trace, dev, lo, hi), lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    spans = [h for h in trace.host if h[0] != WINDOW_SPAN]
    events = sorted((h for h in spans if not h[0].startswith(OWN_SPANS)),
                    key=lambda h: h[2])
    ends = [h[2] for h in events]
    named = []
    for s, e in idle:
        cover = [h for h in spans if min(e, h[2]) - max(s, h[1])
                 >= 0.5 * (e - s)]
        inner = min(cover, key=lambda h: h[2] - h[1]) if cover else None
        if inner is not None and not inner[0].startswith(OWN_SPANS):
            label = inner[0]
        else:
            k = bisect.bisect_right(ends, s) - 1
            label = (f"host, after {events[k][0]}" if k >= 0
                     else "host")
        named.append([label, (e - s) / 1e9])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": named}
