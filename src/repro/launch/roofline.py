"""Roofline accounting from the compiled dry-run artifact.

Three terms per (arch x shape x mesh), in seconds:

    compute    = FLOPs            / (chips * 197e12  bf16 FLOP/s)
    memory     = HBM bytes        / (chips * 819e9   B/s)
    collective = ICI link bytes   / (chips * 50e9    B/s per link)

Sources:

* **collective bytes** are parsed from the compiled HLO text. Models scan
  over layers, so collectives inside ``while`` bodies are multiplied by the
  loop trip count, recovered from the loop-condition computation's compare
  constant (XLA's canonical scan lowering); nested loops multiply through.
  Per-op link-byte models: all-reduce 2x, all-gather/reduce-scatter/
  all-to-all (n-1)/n x payload, collective-permute 1x.

* **FLOPs / HBM bytes** use the analytic workload model below.
  ``compiled.cost_analysis()`` counts a while body ONCE (XLA HloCostAnalysis
  semantics), which under layer-scan underestimates by ~L x; we therefore
  report the analytic value as the roofline term and the raw HLO number as a
  cross-check column. MODEL_FLOPS = 6·N_active·D is reported alongside as
  the useful-compute ratio.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional


from repro.configs.base import InputShape, ModelConfig

# TPU v5e
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # B/s / chip
LINK_BW = 50e9             # B/s / ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

def _shape_bytes(sig: str) -> int:
    """Bytes of an HLO type signature like ``bf16[16,128]{1,0}`` or a tuple."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", sig):
        dt, dims = m.group(1), m.group(2)
        b = _DTYPE_BYTES.get(dt)
        if b is None:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * b
    return total


@dataclass
class CollectiveOp:
    kind: str
    bytes_payload: int      # per-device payload (SPMD shapes are per-device)
    group_size: int
    computation: str
    multiplier: int = 1

    @property
    def link_bytes(self) -> float:
        """Per-chip link traffic. SPMD operand shapes are per-device:
        all-gather's operand is the SHARD (each chip ships it n-1 times in a
        ring), while all-reduce / reduce-scatter / all-to-all operands are
        the full per-device buffer (ring cost (n-1)/n x buffer, 2x for AR).
        """
        n = max(self.group_size, 1)
        if self.kind == "all-reduce":
            f = 2.0 * (n - 1) / n
        elif self.kind == "all-gather":
            f = float(n - 1)
        elif self.kind in ("reduce-scatter", "all-to-all"):
            f = (n - 1) / n
        else:  # collective-permute
            f = 1.0
        return self.bytes_payload * f * self.multiplier


def _split_computations(hlo: str) -> Dict[str, str]:
    """computation name -> body text."""
    comps: Dict[str, str] = {}
    cur = None
    buf: List[str] = []
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*{\s*$", line)
        if m:
            if cur is not None:
                comps[cur] = "\n".join(buf)
            cur = m.group(1)
            buf = []
        elif cur is not None:
            if line.startswith("}"):
                comps[cur] = "\n".join(buf)
                cur = None
                buf = []
            else:
                buf.append(line)
    if cur is not None:
        comps[cur] = "\n".join(buf)
    return comps


def _group_size(attrs: str, total_devices: int) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if m:  # iota format [num_groups,group_size]
        return int(m.group(2))
    return total_devices


def _while_trip_counts(comps: Dict[str, str]) -> Dict[str, int]:
    """while body computation -> trip count.

    Preferred source: XLA's ``backend_config={"known_trip_count":{"n":"L"}}``
    annotation on the while op. Fallback: the largest integer constant in the
    loop-condition computation (the canonical ``i < L`` compare).
    """
    trips: Dict[str, int] = {}
    for cname, body in comps.items():
        for m in re.finditer(
                r"while\([^)]*\).*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+)"
                r"(.*)$",
                body, re.M):
            cond, wbody, rest = m.group(1), m.group(2), m.group(3)
            ktc = re.search(r'known_trip_count[^}]*"n"\s*:\s*"(\d+)"', rest)
            if ktc:
                trips[wbody] = int(ktc.group(1))
                continue
            ctext = comps.get(cond, "")
            consts = [int(c) for c in re.findall(
                r"constant\((\d+)\)", ctext)]
            trips[wbody] = max(consts) if consts else 1
    return trips


def _call_multipliers(comps: Dict[str, str], entry: str) -> Dict[str, int]:
    """Effective execution multiplier per computation (nested whiles)."""
    trips = _while_trip_counts(comps)
    mult: Dict[str, int] = {entry: 1}
    # build call edges: computation -> called computations
    call_re = re.compile(
        r"(?:condition=|body=|to_apply=|called_computations=\{|calls=)"
        r"%?([\w.\-]+)")
    edges: Dict[str, List[str]] = {
        c: [m.group(1) for m in call_re.finditer(t) if m.group(1) in comps]
        for c, t in comps.items()
    }
    # BFS from entry, propagating multipliers; while bodies multiply by trip
    import collections
    q = collections.deque([entry])
    seen = {entry}
    while q:
        c = q.popleft()
        for callee in edges.get(c, []):
            m = mult[c] * trips.get(callee, 1)
            if callee not in mult or m > mult[callee]:
                mult[callee] = m
                if callee not in seen or m > 1:
                    q.append(callee)
                    seen.add(callee)
    return mult


def parse_collectives(hlo: str, total_devices: int) -> List[CollectiveOp]:
    comps = _split_computations(hlo)
    entry = None
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    if m:
        entry = m.group(1)
    else:
        entry = next(iter(comps), "main")
    mult = _call_multipliers(comps, entry)

    ops: List[CollectiveOp] = []
    # result type may be a tuple `(f32[..], /*index=5*/f32[..])` when XLA's
    # collective combiner has batched independent streams into one op.
    op_re = re.compile(
        r"=\s+(\([^()]*\)|[^\s]+)\s+(" + "|".join(_COLLECTIVES) +
        r")(?:-start)?\(([^)]*)\)(.*)$")
    for cname, body in comps.items():
        for line in body.splitlines():
            mo = op_re.search(line)
            if not mo:
                continue
            out_sig, kind, operands, attrs = mo.groups()
            if "-done" in line:
                continue
            # payload: use operand shapes (result of AG is bigger by design)
            payload = _shape_bytes(operands)
            if payload == 0:
                payload = _shape_bytes(out_sig)
            ops.append(CollectiveOp(
                kind=kind,
                bytes_payload=payload,
                group_size=_group_size(attrs, total_devices),
                computation=cname,
                multiplier=mult.get(cname, 1),
            ))
    return ops


# op names are lowercase-with-dashes; requiring a leading lowercase letter
# avoids matching layout annotations like {1,0:T(8,128)}. The result type
# may be a tuple with /*index=k*/ comments (combined collectives), so the
# prefix skip is `.*?`, not `[^=]*?`.
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?"
                       r"([a-z][a-z0-9\-]*)\((.*)\)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def collective_critical_depth(hlo: str) -> Dict[str, float]:
    """Longest dependency chain of collective ops (structural serialization).

    The paper's serialization story in one number: a global critical section
    chains EVERY message (depth == #messages); independent VCI streams chain
    only within a stream (depth == messages-per-stream); hybrid progress
    lands in between (the periodic join adds cross-stream edges).

    Depth is computed per computation from the def-use graph of the compiled
    HLO and scaled by the while-loop trip multiplier; the reported value is
    the max over computations. ``parallelism`` = total collectives / depth —
    the speedup an ideal parallel network could extract from this schedule.
    """
    comps = _split_computations(hlo)
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    entry = m.group(1) if m else next(iter(comps), "main")
    mult = _call_multipliers(comps, entry)

    total = 0.0
    worst = 0.0
    for cname, body in comps.items():
        depth: Dict[str, float] = {}
        comp_max = 0.0
        n_coll = 0
        for line in body.splitlines():
            mo = _INSTR_RE.match(line)
            if not mo:
                continue
            name, op, operands = mo.groups()
            is_coll = any(op.startswith(k) for k in _COLLECTIVES)
            d = 0.0
            for om in _OPERAND_RE.finditer(operands):
                d = max(d, depth.get(om.group(1), 0.0))
            # attrs after the operand list may also reference values (e.g.
            # tuple elements) — conservative: operands only.
            if is_coll and not op.endswith("-done"):
                d += 1.0
                n_coll += 1
            depth[name] = d
            comp_max = max(comp_max, d)
        k = mult.get(cname, 1)
        total += n_coll * k
        worst = max(worst, comp_max * k)
    return {"collective_count": total, "critical_depth": worst,
            "parallelism": (total / worst) if worst else 1.0}


def collective_summary(ops: List[CollectiveOp]) -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for op in ops:
        d = out.setdefault(op.kind, {"count": 0, "link_bytes": 0.0})
        d["count"] += op.multiplier
        d["link_bytes"] += op.link_bytes
    return out


# ---------------------------------------------------------------------------
# analytic workload model
# ---------------------------------------------------------------------------

def _attn_flops_fwd(cfg: ModelConfig, batch: int, seq: int,
                    kv_len: Optional[int] = None) -> float:
    if cfg.num_heads == 0:
        return 0.0
    kv_len = seq if kv_len is None else kv_len
    eff = min(kv_len, cfg.sliding_window) if cfg.sliding_window else kv_len
    if kv_len == seq and seq > 1:
        eff_avg = eff / 2 if cfg.sliding_window is None else (
            eff * (1 - eff / (2 * max(seq, 1))))  # causal and/or banded
    else:
        eff_avg = eff
    # QK^T + PV
    return (4.0 * batch * seq * eff_avg * cfg.num_heads * cfg.head_dim
            * _attn_layers(cfg))


def _ssd_flops_fwd(cfg: ModelConfig, batch: int, seq: int) -> float:
    if cfg.ssm is None:
        return 0.0
    c = cfg.ssm
    h = c.num_heads(cfg.d_model)
    n, p, ch = c.d_state, c.head_dim, c.chunk_size
    if seq == 1:
        return batch * h * (4.0 * n * p)  # recurrent step
    # per token: CB row (c*n), W@x (c*p), state in/out (2*n*p/c * c)
    per_tok = 2.0 * ch * n + 2.0 * ch * p + 4.0 * n * p
    return batch * seq * h * per_tok * cfg.num_layers


def analytic_flops(cfg: ModelConfig, shape: InputShape) -> Dict[str, float]:
    b, s = shape.global_batch, shape.seq_len
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = b * s
        matmul = 6.0 * n_active * tokens            # fwd(2) + bwd(4)
        attn = 3.0 * _attn_flops_fwd(cfg, b, s)
        ssd = 3.0 * _ssd_flops_fwd(cfg, b, s)
        # remat="dots" (selective recomputation) saves matmul outputs: the
        # re-forward repeats only cheap elementwise ops — no matmul FLOPs.
        no_refwd = cfg.remat in ("none", "dots")
        remat = 1.0 if no_refwd else (
            2.0 * n_active * tokens + _attn_flops_fwd(cfg, b, s)
            + _ssd_flops_fwd(cfg, b, s))            # re-run fwd
        total = matmul + attn + ssd + (0.0 if no_refwd else remat)
        model = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = b * s
        total = 2.0 * n_active * tokens + _attn_flops_fwd(cfg, b, s) \
            + _ssd_flops_fwd(cfg, b, s)
        model = 2.0 * n_active * tokens
    else:  # decode: one token against a seq_len cache
        tokens = b
        total = 2.0 * n_active * tokens \
            + _attn_flops_fwd(cfg, b, 1, kv_len=s) + _ssd_flops_fwd(cfg, b, 1)
        model = 2.0 * n_active * tokens
    return {"total": total, "model": model}


def analytic_hbm_bytes(cfg: ModelConfig, shape: InputShape) -> float:
    """First-order HBM traffic model (per step,全 global)."""
    b, s = shape.global_batch, shape.seq_len
    pb = {"bfloat16": 2, "float32": 4}[cfg.param_dtype]
    ob = {"bfloat16": 2, "float32": 4}[cfg.optimizer_dtype]
    n_total = cfg.param_count()
    n_active = cfg.active_param_count()
    d = cfg.d_model
    act_b = 2  # bf16 activations
    if shape.kind == "train":
        # weights: fwd read + bwd read + grad write; opt: m,v read+write, p write
        w = n_total * (3 * pb + 4 * ob + pb)
        # activations: residual stream + block internals, written+read once
        # (remat recomputes instead of storing internals -> factor ~8 d_model)
        acts = b * s * d * cfg.num_layers * act_b * 8
        return w + acts
    if shape.kind == "prefill":
        w = n_total * pb
        acts = b * s * d * cfg.num_layers * act_b * 4
        kv = (0 if cfg.num_heads == 0 else
              b * s * cfg.kv_dim * 2 * act_b * _attn_layers(cfg))
        return w + acts + kv
    # decode: every ACTIVE weight read once; KV cache read; states
    w = n_active * pb
    eff = min(s, cfg.sliding_window) if cfg.sliding_window else s
    kv_b = 1 if "kv_fp8" in cfg.opts else act_b  # OPT(kv_fp8): 1-byte cache
    kv = (0 if cfg.num_heads == 0 else
          b * eff * cfg.kv_dim * max(1, cfg.decode_kv_expand)
          * 2 * kv_b * _attn_layers(cfg))
    ssm = 0.0
    if cfg.ssm is not None:
        c = cfg.ssm
        h = c.num_heads(cfg.d_model)
        ssm = b * h * c.d_state * c.head_dim * 4 * 2 * cfg.num_layers
    return w + kv + ssm


def _attn_layers(cfg: ModelConfig) -> int:
    if cfg.num_heads == 0:
        return 0
    if cfg.mixers is None:  # zamba2's shared-attention sites
        return cfg.num_layers // cfg.hybrid_attn_every
    return cfg.mixers.count("attention")


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_total: float
    flops_model: float
    hbm_bytes: float
    link_bytes_per_chip: float
    hlo_flops_raw: Optional[float]
    collectives: Dict[str, Dict[str, float]]
    memory_per_chip: Optional[Dict[str, float]] = None

    @property
    def t_compute(self) -> float:
        return self.flops_total / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.link_bytes_per_chip / LINK_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def model_ratio(self) -> float:
        return self.flops_model / max(self.flops_total, 1.0)

    def row(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "flops_total": self.flops_total, "flops_model": self.flops_model,
            "model_ratio": self.model_ratio,
            "hbm_bytes": self.hbm_bytes,
            "link_bytes_per_chip": self.link_bytes_per_chip,
            "hlo_flops_raw": self.hlo_flops_raw,
            "collectives": self.collectives,
            "memory_per_chip": self.memory_per_chip,
        }


def build_roofline(cfg: ModelConfig, shape: InputShape, mesh_name: str,
                   chips: int, hlo_text: str,
                   cost: Optional[dict], mem: Optional[dict]) -> Roofline:
    ops = parse_collectives(hlo_text, chips)
    summ = collective_summary(ops)
    summ["_structure"] = collective_critical_depth(hlo_text)
    link_per_chip = sum(d["link_bytes"] for d in summ.values()
                        if "link_bytes" in d)
    fl = analytic_flops(cfg, shape)
    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_total=fl["total"], flops_model=fl["model"],
        hbm_bytes=analytic_hbm_bytes(cfg, shape),
        link_bytes_per_chip=link_per_chip,
        hlo_flops_raw=(cost or {}).get("flops"),
        collectives=summ,
        memory_per_chip=mem,
    )
