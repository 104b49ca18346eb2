"""Serve-path communication streams — VCIs for decode/prefill collectives.

The gradient path (``core/bucketing.py``) maps each gradient bucket onto a
CommContext/VCI so XLA may overlap the B reductions. The serve path has the
same shape of user-exposed parallelism, just with different *purposes*: every
decode step issues TP partial-sum all-reduces (attention ``wo`` and FFN
``w_down`` row-parallel matmuls), MoE dispatch/combine resharding, and the
vocab-parallel sampling gather. Running them on XLA's default ordering is the
"one global stream" anti-pattern of the paper's Fig. 4; :class:`ServeCommPlan`
is the serve-side mirror of :class:`~repro.core.bucketing.CommPlan` — a
host-persistent object holding ONE ``CommWorld`` plus per-lane/per-purpose
``CommContext``s, minting a fresh trace-local ``CommRuntime`` per trace.

Purposes (one context — hence one VCI stream — per purpose, per lane):

* ``tp_attn``  — attention output-projection partial sums (row-parallel wo);
* ``tp_mlp``   — FFN down-projection partial sums (row-parallel w_down);
* ``moe``      — MoE expert dispatch/combine resharding (expert-parallel
                 all-gather of expert outputs, or the ff-TP partial-sum
                 all-reduce when experts don't divide the axis);
* ``sample``   — vocab-parallel embedding/logits collectives feeding the
                 sampler (the KV-cache/sampling stream).

A *lane* is one concurrently-decoding batch: ``ServeCommPlan(lanes=G)``
pre-creates G disjoint context sets so G decode batches traced into one
program ride G×4 independent streams. With ``num_vcis`` below the live
context count the pool falls back exactly as §4.2 describes — contexts
collide on VCI 0, their ordering tokens chain, and the lanes serialize: the
serve-side reproduction of the Fig. 17 mapping mismatch, measured by
``benchmarks/serve_streams.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.collectives import CommRuntime
from repro.core.comm import CommContext, CommWorld

PURPOSES = ("tp_attn", "tp_mlp", "moe", "sample")

TP_AXIS = "model"


@dataclass
class ServeComm:
    """Trace-local view threaded through the model's decode/prefill code.

    Binds one lane's contexts to a (possibly shared) :class:`CommRuntime`:
    sharing one runtime across lanes is what lets contexts that COLLIDED in
    the VCI pool serialize through the shared per-VCI ordering token.
    """

    rt: CommRuntime
    contexts: Dict[str, CommContext]
    axis: str = TP_AXIS

    @property
    def size(self) -> int:
        return jax.lax.axis_size(self.axis)

    def rank(self):
        return lax.axis_index(self.axis)

    def psum(self, x, purpose: str):
        """Partial-sum all-reduce on the purpose's VCI stream."""
        return self.rt.all_reduce(x, self.contexts[purpose], axis=self.axis)

    def all_gather(self, x, purpose: str, gather_axis: int):
        return self.rt.all_gather(x, self.contexts[purpose], axis=self.axis,
                                  gather_axis=gather_axis, tiled=True)

    def all_to_all(self, x, purpose: str, *, split_axis: int,
                   concat_axis: int):
        return self.rt.all_to_all(x, self.contexts[purpose], axis=self.axis,
                                  split_axis=split_axis,
                                  concat_axis=concat_axis)

    def drain(self, x):
        """Order ``x`` after every stream (step-end global progress)."""
        return self.rt.barrier(x)


class ServeCommPlan:
    """Host-persistent serve comm plan (the serve mirror of ``CommPlan``).

    Built once per engine/benchmark; every trace mints a fresh runtime via
    :meth:`runtime` (ordering tokens are trace-local) while the world, the
    VCI pool and the contexts persist — so pool statistics accumulate across
    traces and the VCI mapping is decided exactly once, at creation time,
    like ``MPI_Comm_create``.
    """

    def __init__(self, *, num_vcis: int = 8, vci_policy: str = "fcfs",
                 lanes: int = 1, progress: str = "hybrid",
                 join_every: int = 8, token_impl: str = "barrier"):
        if lanes < 1:
            raise ValueError(f"need at least one lane, got {lanes}")
        self.lanes = lanes
        self.progress = progress
        self.join_every = join_every
        self.token_impl = token_impl
        self.world = CommWorld(num_vcis=num_vcis, policy=vci_policy)
        self.contexts: Dict[Tuple[int, str], CommContext] = {}
        for lane in range(lanes):
            for purpose in PURPOSES:
                hint = "dedicated" if vci_policy == "hinted" else None
                self.contexts[(lane, purpose)] = self.world.create(
                    f"lane{lane}.{purpose}", kind="p2p", hint=hint)

    def runtime(self) -> CommRuntime:
        """A fresh per-trace runtime bound to the persistent world."""
        return CommRuntime(self.world, progress=self.progress,
                           join_every=self.join_every,
                           token_impl=self.token_impl)

    def comm(self, lane: int = 0, *, rt: Optional[CommRuntime] = None,
             axis: str = TP_AXIS) -> ServeComm:
        """The lane's trace-local comm view. Pass one shared ``rt`` when
        tracing several lanes into one program (collision semantics)."""
        if not 0 <= lane < self.lanes:
            raise ValueError(f"lane {lane} outside [0, {self.lanes})")
        ctxs = {p: self.contexts[(lane, p)] for p in PURPOSES}
        return ServeComm(rt or self.runtime(), ctxs, axis=axis)

    @property
    def stats(self):
        return self.world.stats

    def vci_map(self) -> Dict[str, int]:
        """{context name: vci index} — the realized mapping, for reporting."""
        return {c.name: c.vci.index for c in self.contexts.values()}


# ---------------------------------------------------------------------------
# manual-TP parameter/cache specs for the comm-mode decode step
# ---------------------------------------------------------------------------

def serve_tp_validate(cfg: ModelConfig, tp: int) -> None:
    """The divisibility contract of the manual-TP serve path."""
    if tp <= 1:
        return
    problems = []
    if cfg.family not in ("dense", "moe"):
        problems.append(f"family {cfg.family!r} (attention archs only)")
    if cfg.modality != "text":
        problems.append(f"modality {cfg.modality!r}")
    if cfg.num_heads % tp:
        problems.append(f"num_heads {cfg.num_heads} % tp")
    if cfg.num_kv_heads % tp:
        problems.append(f"num_kv_heads {cfg.num_kv_heads} % tp")
    if cfg.d_ff % tp:
        problems.append(f"d_ff {cfg.d_ff} % tp")
    if cfg.vocab_size % tp:
        problems.append(f"vocab_size {cfg.vocab_size} % tp")
    if cfg.decode_kv_expand != 1:
        problems.append("decode_kv_expand != 1")
    if cfg.moe is not None and (cfg.moe.num_experts % tp
                                and cfg.d_ff % tp):
        problems.append(f"num_experts {cfg.moe.num_experts} % tp")
    if problems:
        raise ValueError(
            f"arch {cfg.name!r} cannot run the manual-TP serve path at "
            f"tp={tp}: " + "; ".join(problems))


def serve_param_specs(cfg: ModelConfig, params, tp: int, *,
                      axis: str = TP_AXIS):
    """PartitionSpec tree for the comm-mode (manual TP) decode step.

    Megatron layout: wq/wk/wv/w_gate/w_up column-parallel, wo/w_down
    row-parallel, biases follow their matmul (b_down/bo replicated — added
    AFTER the partial-sum all-reduce). Embedding and lm_head are
    vocab-parallel, feeding the ``sample`` stream's psum/all-gather. MoE
    expert tables are expert-parallel over the TP axis when the expert count
    divides, else ff-TP within every expert. Norm scales and the router
    replicate.
    """
    col = frozenset({"wq", "wk", "wv", "w_gate", "w_up"})
    row = frozenset({"wo", "w_down"})
    col_bias = frozenset({"bq", "bk", "bv", "b_up"})
    moe_expert_parallel = (cfg.moe is not None
                           and cfg.moe.num_experts % tp == 0)

    def assign(path, leaf):
        keys = tuple(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path)
        name, parent = keys[-1], (keys[-2] if len(keys) >= 2 else "")
        nd = leaf.ndim
        spec = [None] * nd
        if tp == 1 or nd == 0:
            return P(*spec)
        if parent == "embed" and nd >= 2:
            spec[nd - 2] = axis            # (V, d): vocab-parallel rows
        elif parent == "lm_head":
            spec[nd - 1] = axis            # (d, V): vocab-parallel columns
        elif parent == "moe" and name in ("w_gate", "w_up", "w_down"):
            if moe_expert_parallel:
                spec[nd - 3] = axis        # (E, a, b): expert-parallel
            else:
                ff_dim = nd - 1 if name in ("w_gate", "w_up") else nd - 2
                spec[ff_dim] = axis        # ff-TP within every expert
        elif name == "router":
            pass
        elif name in col and nd >= 2:
            spec[nd - 1] = axis
        elif name in row and nd >= 2:
            spec[nd - 2] = axis
        elif name in col_bias:
            spec[nd - 1] = axis
        return P(*spec)

    return jax.tree_util.tree_map_with_path(assign, params)


def serve_cache_specs(cache, tp: int, batch_shards: int, *,
                      axis: str = TP_AXIS, batch_axis="data"):
    """Spec tree for a DecodeCache: KV heads over the TP axis, batch over
    ``batch_axis`` (a mesh axis name or tuple — pass the SAME entry the
    token spec uses); scalars (cursor lengths) replicate.

    Paged caches (:class:`repro.models.attention.PagedKVCache`): the page
    pool is a SHARED resource — any slot may hold any page — so it cannot
    shard over the batch axes; pools replicate over data and shard only
    their KV heads over the TP axis, and the page table / cursor replicate.
    (That is exactly the paper's argument inverted: the pool is the one
    deliberately-shared resource, and the per-purpose VCI streams are what
    keep the lanes from serializing on it.)
    """
    from repro.models.attention import PagedKVCache

    def assign(leaf):
        if getattr(leaf, "ndim", 0) == 5:   # (L, B, S, KV, hd) stacked cache
            b_ax = batch_axis if (batch_shards > 1
                                  and leaf.shape[1] % batch_shards == 0) else None
            kv_ax = axis if (tp > 1 and leaf.shape[3] % tp == 0) else None
            return P(None, b_ax, None, kv_ax, None)
        return P()

    kv = getattr(cache, "kv", None)
    if isinstance(kv, PagedKVCache):
        # (L, NP, PS, KV, hd), or (L, NP, PS, KV*hd) for heads under 128:
        # a row splits at head boundaries, the TP path holding KV % tp == 0
        kv_ax = axis if (tp > 1 and kv.k.shape[3] % tp == 0) else None
        pool = P(None, None, None, kv_ax, *[None] * (kv.k.ndim - 4))
        kv_spec = PagedKVCache(pool, pool, P(), P(), kv.page_size)
        rest = jax.tree_util.tree_map(assign, cache.ssm)
        return type(cache)(kv_spec, rest, P())
    return jax.tree_util.tree_map(assign, cache)
